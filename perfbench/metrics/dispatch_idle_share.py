"""Share of the traced window, in %, in which device 0 ran no operation
while the driving thread was inside a ``case.dispatch`` span: the idle time
that is the host's launch of the compiled case (Python entry to enqueue).
``idle_share`` less this is the runtime's launch latency after the
enqueue, the completion round trip and the harness.  It reads every
``dispatch_idle_share.<part>`` metric; None where the trace has no device
plane or no ``case.dispatch`` span.

The device plane is moved onto the host's clock first.  On a v5e its events
run 0.25-1.95 ms ahead of the host plane's, by an amount that holds for a
whole trace and differs between runs (a program starts before the host has
enqueued it), which would put every dispatch inside device time.  Each
dispatch launches one program, in order, and no program can start before
its dispatch enqueues it, near the span's end: the device's busy time is
split into as many programs as there are dispatch spans, at its widest
gaps, and shifted by the least amount that keeps every program from
starting before its span ends.  The enqueue comes some tens of
microseconds before the span ends, so the reading is an upper bound by
that much a call."""
from perfbench.trace_reduce import union

SPAN = "case.dispatch"


def read(ctx):
    red = ctx.trace
    if red is None or not red.devices:
        return None
    spans = union((s, e) for name, s, e in red.host if name == SPAN)
    busy = union((s, e) for _, s, e in red.devices[0])
    if not spans or len(busy) < len(spans):
        return None
    programs = _programs(busy, len(spans))
    shift = max(end - start for (_, end), (start, _) in zip(spans, programs))
    lo, hi = red.start_ns, red.end_ns
    dispatch = _clip(spans, lo, hi)
    idle = (sum(e - s for s, e in dispatch)
            - _overlap(dispatch, _clip([(s + shift, e + shift)
                                        for s, e in busy], lo, hi)))
    return 100.0 * idle / (hi - lo)


def _programs(busy, n: int):
    """``busy`` (sorted, disjoint) grouped into ``n`` runs, split at its
    ``n - 1`` widest gaps: (start, end) of each."""
    widest = sorted(range(1, len(busy)),
                    key=lambda i: busy[i][0] - busy[i - 1][1])
    cuts = [0] + sorted(widest[len(busy) - n:]) + [len(busy)]
    return [(busy[a][0], busy[b - 1][1]) for a, b in zip(cuts, cuts[1:])]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a, b) -> float:
    """Total length common to two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
