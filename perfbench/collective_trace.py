"""Each chip's all-reduce events in a reduced trace (``trace_reduce``), for
the readers of the collective cells' per-layer metrics.

On the chip an all-reduce is one HLO operation, ``all-reduce``, or an async
pair, ``all-reduce-start`` then ``all-reduce-done``.  A pair counts as one
event, from the start's start to the done's end, matched in order.
"""
from __future__ import annotations

import json

from perfbench.trace_reduce import op_label

OPS = ("all-reduce", "all-reduce-start", "all-reduce-done")


def all_reduces(red, device: int) -> list[tuple[float, float]]:
    """(start_ns, end_ns) of each all-reduce the chip ran, in order."""
    out, started = [], []
    for name, s, e in red.devices[device]:
        parts = op_label(name).split(" ")
        op = parts[1] if len(parts) > 1 else ""
        if op == "all-reduce":
            out.append((s, e))
        elif op == "all-reduce-start":
            started.append(s)
        elif op == "all-reduce-done" and started:
            out.append((started.pop(0), e))
    return sorted(out)


def per_chip(ctx) -> list[list[tuple[float, float]]] | None:
    """Every chip's all-reduces, where the run is a traced collective cell
    on a TPU: None off a TPU, in a cell that declares no exchanges, or
    where some chip's trace holds another count of them than the traced
    calls ran (a merged, hoisted or split exchange)."""
    red, work = ctx.trace, ctx.session.work
    if red is None or not red.devices or not ctx.peaks \
            or "exchanges" not in work:
        return None
    want = ctx.window.traced_calls * work["exchanges"]
    chips = [all_reduces(red, d) for d in range(len(red.devices))]
    return chips if all(len(c) == want for c in chips) else None


def ici_peak(ctx) -> float:
    """The chip's ICI bytes per second (``ici_peaks.json``, by device kind;
    an unknown kind is an error)."""
    import jax
    kind = jax.devices()[0].device_kind
    table = json.loads((ctx.cell.bench_dir / "ici_peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in ici_peaks.json "
                       f"({sorted(table)})")
    return table[kind]["ici_bytes_per_s"]


def overlap(a, b) -> float:
    """Total length common to two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
