"""The benchmark's own byte and flop formulas, per pass over a working set.

A copy of the per-element accounting of ``repro.bench.mixes`` as it stood
when the benchmark was defined, kept here so that no change to the program
can rescale the benchmark's GB/s.  ``perfbench/tests/test_accounting.py``
holds the two side by side, so any drift between them shows.
"""
from __future__ import annotations

import re

#: full-buffer generator sweeps per probe pass of a loaded chase
GEN_SWEEPS_PER_PASS = 16

#: mix -> (flops, reads, writes) per element per pass
_FIXED = {
    "load_only": (0.0, 1.0, 0.0),
    "load_sum": (1.0, 1.0, 0.0),
    "copy": (0.0, 1.0, 1.0),
    "triad": (2.0, 2.0, 1.0),
    "mxu": (256.0, 1.0, 0.0),
    "latency_chase": (0.0, 1.0, 0.0),
}
_FMA = re.compile(r"fma_([1-9]\d*)\Z")
_RW = re.compile(r"rw_([1-9]\d*)to([1-9]\d*)\Z")


def per_element(mix: str) -> tuple[float, float, float]:
    """(flops, reads, writes) per element per pass of ``mix``."""
    if mix in _FIXED:
        return _FIXED[mix]
    m = _FMA.match(mix)
    if m:
        return 2.0 * int(m.group(1)), 1.0, 0.0
    m = _RW.match(mix)
    if m:
        reads, writes = int(m.group(1)), int(m.group(2))
        return 2.0 * (reads - 1), float(reads), float(writes)
    raise KeyError(f"no accounting for mix {mix!r}")


def bytes_per_pass(mix: str, nbytes: int) -> float:
    """Bytes one pass of ``mix`` moves over a working set of ``nbytes``."""
    _, reads, writes = per_element(mix)
    return (reads + writes) * nbytes


def flops_per_pass(mix: str, n_elems: int) -> float:
    """Arithmetic one pass of ``mix`` does over ``n_elems`` elements."""
    return per_element(mix)[0] * n_elems
