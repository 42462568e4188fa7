"""The benchmark's own byte formulas for a collective mix, per rank and pass.

A copy of the collective accounting of ``repro.bench.mixes`` as it stood
when the ``osu_allreduce`` cell was defined, kept here so that no change to
the program can rescale the cell's GB/s; ``perfbench/tests/
test_collective_cell.py`` holds the two side by side.  A working set of
``nbytes`` is split over ``k`` ranks, so each rank's message is
S = nbytes / k.  As nccl-tests reports it (``doc/PERFORMANCE.md``), algbw
counts S and busbw counts the bytes each rank must send at least:
2(k - 1)/k * S for an all-reduce.
"""
from __future__ import annotations

#: mix -> bytes each rank sends per byte of its message, for k ranks
_BUS_FACTOR = {"all_reduce": lambda k: 2.0 * (k - 1) / k}


def payload_bytes(nbytes: int, k: int) -> float:
    """One rank's message: its 1/k share of the working set."""
    return nbytes / k


def bus_bytes(mix: str, nbytes: int, k: int) -> float:
    """What each rank must send in one pass of ``mix`` over ``k`` ranks."""
    return _BUS_FACTOR[mix](k) * payload_bytes(nbytes, k)
