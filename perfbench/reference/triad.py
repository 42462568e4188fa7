"""Plain reference of the STREAM ``triad`` mix as the Pallas pass loop runs
it: ``out = x + 1.5 * y`` with ``y = x / 2``, the first element of each
pass's output folded into the accumulator, then the last element of the
last output."""
from __future__ import annotations

import numpy as np


def timed_acc(x, passes: int, dt) -> float:
    first, last = dt(float(x[0, 0])), dt(float(x[-1, -1]))
    half, scale = dt(0.5), dt(1.5)
    y_first = dt(first * half)
    acc = dt(0)
    for _ in range(passes):
        acc = dt(acc + dt(first + dt(scale * y_first)))
        eps = dt(acc * dt(1e-30))
        first, y_first = dt(first + eps), dt(y_first + eps)
    return float(dt(acc + dt(last + dt(scale * dt(last * half)))))


def kernel_output(x, dt):
    """What one call of the triad kernel writes for ``x`` and ``y = x / 2``,
    computed on the host in ``dt`` and returned in ``x``'s type."""
    host = np.asarray(x)
    xs = host.astype(dt)
    return (xs + dt(1.5) * (xs * dt(0.5))).astype(host.dtype)
