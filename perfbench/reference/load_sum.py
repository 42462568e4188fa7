"""Plain reference of the ``load_sum`` mix: the sum of every element, once
per pass, accumulated over the passes."""
from __future__ import annotations

import numpy as np


def kernel_output(x, dt) -> float:
    """One pass: the sum of ``x``, held and summed in ``dt``."""
    return float(np.sum(np.asarray(x).astype(dt), dtype=dt))


def timed_acc(x, passes: int, dt) -> float:
    """The accumulator of ``passes`` passes.  Between passes the loop adds
    ``acc * 1e-30`` to the first element; that step is carried out here
    too, in ``dt``, and moves the next pass's sum by the same amount."""
    host = np.asarray(x)
    total = dt(kernel_output(host, dt))
    first = dt(host[0, 0])
    acc = dt(0)
    for _ in range(passes):
        acc = dt(acc + total)
        moved = dt(first + dt(acc * dt(1e-30)))
        total = dt(total + dt(moved - first))
        first = moved
    return float(acc)
