"""Plain reference of the ``all_reduce`` mix on the host: the working set's
row blocks, one a rank, summed element by element, which is what every rank
ends with; and the accumulator of the timed pass loop, which folds the
first and the last element of each pass's output, then the last element of
its one output slot."""
from __future__ import annotations

import numpy as np


def reduced(x, ranks: int, dt):
    """The sum of ``x``'s ``ranks`` row blocks, each held and added in
    ``dt``, rank by rank."""
    blocks = np.asarray(x).reshape(ranks, -1, np.shape(x)[-1])
    total = blocks[0].astype(dt)
    for block in blocks[1:]:
        total = total + block.astype(dt)
    return total


def timed_acc(total, passes: int, dt) -> float:
    """The accumulator a timed call of ``passes`` all-reduces returns, on
    every rank, added in ``dt``."""
    first, last = dt(total[0, 0]), dt(total[-1, -1])
    acc = dt(0)
    for _ in range(passes):
        acc = dt(dt(acc + first) + last)
    return float(dt(acc + last))
