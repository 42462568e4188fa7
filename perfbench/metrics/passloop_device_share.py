"""Share of device busy time, in %, spent outside the mix kernel: the pass
loop's own operations (the entry copy of the working set, each pass's
one-element update, the output slots and their consumption)."""
from perfbench.trace_reduce import PALLAS_KERNEL


def read(ctx):
    if (ctx.trace is None or not ctx.trace.devices
            or "kernel_bytes" not in ctx.session.work):
        return None
    busy = ctx.trace.busy_s() * len(ctx.trace.devices)
    kernel = sum(ctx.trace.seconds_of(PALLAS_KERNEL, d)
                 for d in range(len(ctx.trace.devices)))
    return 100.0 * (busy - kernel) / busy if busy > 0 else None
