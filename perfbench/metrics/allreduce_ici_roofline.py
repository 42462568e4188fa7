"""The all-reduce's share of the ICI roofline, in %: per chip, the bytes
each rank must send in one all-reduce (2(k - 1)/k of its message,
``perfbench/collective_accounting.py``) over the chip's ICI bytes per
second (``perfbench/ici_peaks.json``), over the mean device duration of
that chip's all-reduce events (``perfbench/collective_trace.py``); the mean
over the chips.  None off a TPU, and None unless every chip's trace holds
exactly passes x traced calls all-reduces."""
from perfbench.collective_trace import ici_peak, per_chip


def read(ctx):
    chips = per_chip(ctx)
    if not chips or not chips[0]:
        return None
    least_s = ctx.session.work["bus_bytes"] / ici_peak(ctx)
    shares = [100.0 * least_s * len(evs) * 1e9 / sum(e - s for s, e in evs)
              for evs in chips]
    return sum(shares) / len(shares)
