"""Plain reference of the idle pointer chase: from index 0, ``n`` dependent
steps ``j = succ[j]`` per pass, the index after each pass folded into the
accumulator, and the final index added once more."""
from __future__ import annotations

import numpy as np


def timed_acc(succ, passes: int, dt) -> float:
    """Indices and the accumulator are held in ``dt``: float64 holds every
    index exactly; a narrower type rounds them, and the walk goes astray."""
    nxt = np.asarray(succ).reshape(-1).tolist()
    j, acc = 0, dt(0)
    for _ in range(passes):
        for _ in range(len(nxt)):
            j = nxt[int(dt(j))]
        acc = dt(acc + dt(j))
    return float(dt(acc + dt(j)))
