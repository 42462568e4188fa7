"""Plain reference of the ``copy`` mix: the output is the input, element for
element, and the pass loop's accumulator folds the first element of each
pass's output, then the last element of the last one."""
from __future__ import annotations

import numpy as np


def timed_acc(x, passes: int, dt) -> float:
    """The accumulator a timed call of ``passes`` copy passes returns.

    Between passes the loop adds ``acc * 1e-30`` to the first element, which
    keeps the passes from being hoisted; it is carried out here too, in
    ``dt``."""
    first, last = dt(float(x[0, 0])), dt(float(x[-1, -1]))
    acc = dt(0)
    for _ in range(passes):
        acc = dt(acc + first)
        first = dt(first + dt(acc * dt(1e-30)))
    return float(dt(acc + last))


def kernel_output(x, dt):
    """What one call of the copy kernel writes: ``x`` itself, held in
    ``dt`` and returned in ``x``'s type, where ``x`` is (host or device).
    A type as wide as ``x``'s holds it exactly."""
    if np.dtype(dt).itemsize >= x.dtype.itemsize:
        return x
    return x.astype(dt).astype(x.dtype)
