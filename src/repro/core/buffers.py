"""Benchmark buffer initialization — the paper's denormal-avoiding discipline.

x86-membench initializes buffers with a cycle of a user-defined number, its
reciprocal, and the additive inverses of both: (v, 1/v, -v, -1/v).  This
guarantees no denormals (which stall FP pipelines) while keeping non-trivial
data (data values influence power draw and, under power caps, throughput —
paper §2/§3.2).  Property-tested in tests/test_core.py.

The four values are computed in float64 and cast to the buffer's dtype on
the host; the buffer itself is built on the device by one jitted
broadcast/iota fusion (``_fill``) that only selects among those four
already-cast values.  A cast rounds each element alone, whatever the array's
length, so the buffer holds the same bits as the float64 cycle tiled to full
length on the host and cast whole (the reference in tests/test_core.py),
with no host array of the working set's size and no host-to-device copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_VALUE = 1.234567


@functools.partial(jax.jit, static_argnums=1)
def _fill(cycle, shape):
    """``cycle`` (4,) repeated over ``shape`` in row-major order: element i
    of the flattened buffer is ``cycle[i % 4]``.  Pure selection, so every
    element is one of the four given values, bit for bit.  ``i % 4`` is
    summed per axis from small residues, so no index overflows int32."""
    phase = jnp.zeros(shape, jnp.int32)
    stride = 1
    for axis in reversed(range(len(shape))):
        pos = lax.rem(lax.broadcasted_iota(jnp.int32, shape, axis), 4)
        phase = phase + pos * (stride % 4)
        stride *= shape[axis]
    return lax.select_n(lax.rem(phase, 4),
                        *(jnp.broadcast_to(c, shape) for c in cycle))


def _cycle(value: float, dtype):
    """The four values (v, 1/v, -v, -1/v), computed in float64 and cast to
    ``dtype`` on the host.  The cast is elementwise, so each value rounds as
    it would inside a buffer of any length cast whole."""
    if value == 0 or not np.isfinite(value):
        raise ValueError("init value must be finite and nonzero")
    cycle = np.array([value, 1.0 / value, -value, -1.0 / value], dtype=np.float64)
    return jnp.asarray(cycle, dtype=dtype)


def init_pattern(n: int, value: float = DEFAULT_VALUE, dtype=jnp.float32):
    """(v, 1/v, -v, -1/v) cycled to length n, built on the default device."""
    return _fill(_cycle(value, dtype), (n,))


def working_set_shape(nbytes: int, dtype=jnp.float32, lanes: int = 128
                      ) -> tuple[int, int]:
    """The (rows, lanes) shape ``working_set`` would allocate for ~nbytes —
    lets callers plan/validate a sweep without touching device memory."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = max(8, int(round(nbytes / (lanes * itemsize) / 8)) * 8)
    return (rows, lanes)


def working_set(nbytes: int, dtype=jnp.float32, value: float = DEFAULT_VALUE,
                lanes: int = 128):
    """A 2D (rows, lanes) buffer of ~nbytes — 2D so Pallas BlockSpecs tile it
    natively ((8,128)-aligned, the v5e register tile).  Built on the device
    in place (``_fill``): the only allocation is the buffer itself."""
    shape = working_set_shape(nbytes, dtype, lanes)
    if jnp.issubdtype(dtype, jnp.integer):
        cycle = jnp.asarray(np.array([1, 7, -1, -7]).astype(jnp.dtype(dtype)))
    else:
        cycle = _cycle(value, dtype)
    return _fill(cycle, shape)


def has_denormals(arr) -> bool:
    a = np.asarray(arr, dtype=np.float64)
    finfo = np.finfo(np.asarray(arr).dtype) if np.asarray(arr).dtype.kind == "f" \
        else None
    if finfo is None:
        return False
    nz = a[a != 0.0]
    return bool(np.any(np.abs(nz) < finfo.tiny))


def sizes_logspace(lo: int, hi: int, per_decade: int = 8) -> list[int]:
    """Log-spaced working-set sizes (bytes), 8-row aligned by working_set()."""
    n = max(2, int(np.ceil((np.log10(hi) - np.log10(lo)) * per_decade)))
    out = np.unique(np.geomspace(lo, hi, n).astype(np.int64))
    return [int(x) for x in out]


# --------------------------------------------------------------------------
# shared sweep grids — ONE grid constructor for the figure scripts and the
# adaptive characterization driver (previously every script carried its own
# size list, and no two agreed on the span)
# --------------------------------------------------------------------------

#: canonical hierarchy span: below the smallest L1d the paper studies up to
#: decisively DRAM-resident on every host we run on
HIERARCHY_SPAN = (16 * 2**10, 128 * 2**20)

#: the fixed quick/smoke ladder: one size per typical level (L1/L2/LLC/DRAM)
QUICK_SIZES = (32 * 2**10, 256 * 2**10, 2 * 2**20, 16 * 2**20)


def snap_sizes(sizes, dtype=jnp.float32, lanes: int = 128) -> list[int]:
    """Requested byte counts -> the *real* working-set sizes
    ``working_set`` would allocate, deduplicated and sorted.  Two requests
    that round to the same (rows, lanes) tile are one measurement — the
    adaptive driver relies on this to avoid re-timing a size it already has
    (and to notice when a bisection bracket is below tile resolution)."""
    itemsize = jnp.dtype(dtype).itemsize
    out = set()
    for s in sizes:
        rows, l = working_set_shape(int(s), dtype, lanes)
        out.add(rows * l * itemsize)
    return sorted(out)


def size_grid(lo: int = HIERARCHY_SPAN[0], hi: int = HIERARCHY_SPAN[1],
              per_decade: int = 6, dtype=jnp.float32) -> list[int]:
    """Log-spaced grid snapped to real working-set sizes (the grid every
    sweep actually measures; ``sizes_logspace`` kept as the raw generator)."""
    return snap_sizes(sizes_logspace(lo, hi, per_decade), dtype=dtype)


def hierarchy_grid(quick: bool = False, lo: int = HIERARCHY_SPAN[0],
                   hi: int = HIERARCHY_SPAN[1], per_decade: int = 6
                   ) -> tuple[int, ...]:
    """The canonical hierarchy-sweep working-set grid (fig scripts, the
    characterize driver's coarse round).  ``quick`` returns the fixed
    one-size-per-level ladder shared by every ``--quick`` mode."""
    if quick:
        return QUICK_SIZES
    return tuple(size_grid(lo, hi, per_decade))
