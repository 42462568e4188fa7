"""Share of device busy time, in %, outside the all-reduce events: the pass
loop's chain, the output slots' writes and whatever the compiler adds around
the exchange, per chip over the traced window; the mean over the chips.
None where ``allreduce_ici_roofline`` has no events to read
(``perfbench/collective_trace.py``)."""
from perfbench.collective_trace import overlap, per_chip
from perfbench.trace_reduce import _clip, union


def read(ctx):
    chips = per_chip(ctx)
    if not chips:
        return None
    red = ctx.trace
    shares = []
    for d, events in enumerate(chips):
        busy = red.busy(d)
        total = sum(e - s for s, e in busy)
        if total <= 0:
            return None
        inside = overlap(busy, _clip(union(events), red.start_ns, red.end_ns))
        shares.append(100.0 * (total - inside) / total)
    return sum(shares) / len(shares)
