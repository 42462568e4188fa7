"""The membench Pallas kernel's share of its roofline, in %: the least time
the chip could take for one invocation (declared bytes over peak HBM
bytes/s, or declared flops over peak flops, whichever is longer; for every
mix in the benchmark today the bytes bound it) over the mean device
duration of the kernel's events in the trace.  None where the cell runs no
Pallas kernel or the trace holds none of its events."""
from perfbench.trace_reduce import PALLAS_KERNEL


def read(ctx):
    work = ctx.session.work
    if ctx.trace is None or "kernel_bytes" not in work or not ctx.peaks:
        return None
    events = ctx.trace.matching(PALLAS_KERNEL)
    if not events:
        return None
    mean_s = sum(e - s for _, s, e in events) / len(events) / 1e9
    least_s = max(work["kernel_bytes"] / ctx.peaks["hbm_bytes_per_s"],
                  work["kernel_flops"] / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least_s / mean_s
