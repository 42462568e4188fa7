"""Pallas TPU membench throughput kernels — the paper's measurement loop with
explicit VMEM tiling.

Knobs (mapping to the paper, DESIGN.md §2):
  mix         load_only | load_sum | copy | fma_k | mxu     (C2: LOAD/FADD/NOP)
  block_rows  rows per (block_rows, 128) VMEM tile           (C4: LD1D/LD2D/LD4D)
              -- unset, the bench takes ``default_block_rows``: the largest
              power-of-two tile whose double-buffered streams fit
              ``VMEM_BUDGET`` and leave ``MIN_GRID_STEPS`` steps a pass,
              never smaller than ``floor_block_rows``
  streams     1 = sequential block walk (post-increment analogue);
              S > 1 = S interleaved streams via the index_map (the paper's
              four offset address pointers breaking AGU dependencies)   (C3)

``load_only`` is the mix XLA cannot express (a dead load is DCE'd): here the
block is *loaded* into VMEM by the pipeline regardless, and only one lane ever
feeds the accumulator, so the measured time is pure data movement + grid
overhead — the LD1/LD2D-only loop of §4.

The grid accumulates into a (1, 1) output revisited every step; TPU grids are
sequential per core, so the accumulation is race-free.  The accumulator lives
in SMEM: Mosaic refuses scalar stores to VMEM.

Interpret mode follows the platform (``resolve_interpret``): the kernels run
interpreted exactly on the CPU and compile everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the pipeline's double-buffered tiles may take: half of Mosaic's
#: default scoped VMEM limit on v5e (16 MiB), the rest left to its scratch
VMEM_BUDGET = 8 * 2**20
#: fewest grid steps a default tile leaves a pass, so the DMA of step i+1
#: still overlaps step i
MIN_GRID_STEPS = 16
#: the tile the default never goes below (when it divides the rows)
FLOOR_BLOCK_ROWS = 128

#: the revisited scalar accumulator of the load-family and chase kernels
_SCALAR_OUT = pl.BlockSpec((1, 1), lambda i: (0, 0),
                           memory_space=pltpu.SMEM)


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode for the default JAX platform: ``None`` means
    interpreted on the CPU and compiled on an accelerator.  Asking for the
    interpreter on an accelerator is an error — its timings would be the
    interpreter's, labelled with the device's name."""
    platform = jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and platform != "cpu":
        raise ValueError(f"Pallas interpret mode requested on a {platform} "
                         f"device; membench kernels compile there")
    return interpret


def floor_block_rows(rows: int) -> int:
    """The largest sublane multiple <= ``FLOOR_BLOCK_ROWS`` that divides
    ``rows`` (a multiple of 8, so 8 always does)."""
    r = min(FLOOR_BLOCK_ROWS, rows)
    while r > 8 and rows % r:
        r -= 8
    return r


def default_block_rows(rows: int, itemsize: int, n_streams: int,
                       streams: int = 1, lanes: int = 128) -> int:
    """The default tile of a streaming kernel over ``rows`` x ``lanes``
    elements of ``itemsize`` bytes whose pipeline double-buffers
    ``n_streams`` tiles (read plus write streams): the largest power-of-two
    row count that fits ``n_streams`` x 2 tiles in ``VMEM_BUDGET``, leaves
    at least ``MIN_GRID_STEPS`` grid steps, divides ``rows`` into a block
    count ``streams`` divides; ``floor_block_rows`` when no such tile is
    larger than it.  From 256 rows up a power of two is a multiple of every
    dtype's sublane tile."""
    best, tile = floor_block_rows(rows), 2 * FLOOR_BLOCK_ROWS
    while (n_streams * 2 * tile * lanes * itemsize <= VMEM_BUDGET
           and rows // tile >= MIN_GRID_STEPS):
        if rows % tile == 0 and (rows // tile) % streams == 0:
            best = tile
        tile *= 2
    return best


def _mix_body(mix: str, depth: int, blk, w=None, interleave: int = 1):
    """blk: (rows, 128) f32 tile already in VMEM.  Returns scalar contribution."""
    if mix == "load_only":
        # touch one lane only: the DMA moved the whole tile, the VPU does ~nothing
        return blk[0, 0]
    if mix == "load_sum":
        if interleave == 1:
            return jnp.sum(blk)
        # `interleave` independent per-chunk accumulator chains, combined
        # only at the end (same elements summed; shorter dependence chains)
        rr = blk.shape[0] // interleave
        parts = [jnp.sum(blk[j * rr:(j + 1) * rr]) for j in range(interleave)]
        s = parts[0]
        for p in parts[1:]:
            s = s + p
        return s
    if mix == "fma":
        v = blk
        a = jnp.float32(1.0000001)
        b = jnp.float32(1e-9)
        for _ in range(depth):
            v = v * a + b
        return jnp.sum(v)
    if mix == "mxu":
        y = jnp.dot(blk, w, preferred_element_type=jnp.float32)
        return jnp.sum(y[:1, :1])
    raise KeyError(mix)


def _acc_kernel(mix: str, depth: int, interleave: int, *refs):
    # refs order: (x_ref[, w_ref], o_ref)
    x_ref, o_ref = refs[0], refs[-1]
    w_ref = refs[1] if mix == "mxu" else None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[0, 0] = jnp.float32(0.0)

    blk = x_ref[...].astype(jnp.float32)
    wv = w_ref[...].astype(jnp.float32) if w_ref is not None else None
    o_ref[0, 0] += _mix_body(mix, depth, blk, wv, interleave)


def _copy_kernel(interleave, x_ref, o_ref):
    if interleave == 1:
        o_ref[...] = x_ref[...]
        return
    # per-chunk stores: `interleave` independent copy streams inside one tile
    rr = x_ref.shape[0] // interleave
    for j in range(interleave):
        o_ref[j * rr:(j + 1) * rr, :] = x_ref[j * rr:(j + 1) * rr, :]


def _triad_kernel(b_ref, c_ref, o_ref):
    """STREAM triad a = b + s*c per tile (2 read streams, 1 write stream)."""
    o_ref[...] = b_ref[...] + jnp.asarray(1.5, b_ref.dtype) * c_ref[...]


def _rw_kernel(reads, writes, interleave, *refs):
    """R:W ratio tile: fold R read tiles triad-style (v = s0 + c*s1 + ...),
    store v to each of W output tiles — the same ratio the xla oracle (k_rw)
    emits, inside one grid program.  refs: R in-refs then W out-refs.
    ``interleave`` > 1 folds each of the tile's row chunks independently
    (identical values — chunked folds of an elementwise combine — with
    shorter per-chunk dependence chains)."""
    from repro.bench.mixes import RW_COMBINE_COEF
    rr = refs[0].shape[0] // interleave
    chunks = []
    for j in range(interleave):
        sl = slice(j * rr, (j + 1) * rr) if interleave > 1 else ...
        v = refs[0][sl]
        coef = jnp.asarray(RW_COMBINE_COEF, v.dtype)
        for r in range(1, reads):
            v = v + coef * refs[r][sl]
        chunks.append((sl, v))
    for w in range(writes):
        for sl, v in chunks:
            refs[reads + w][sl] = v


def _chase_kernel(x_ref, o_ref):
    """Latency probe tile: x_ref is an int32 (rows, lanes) tile holding one
    full permutation cycle of TILE-LOCAL flat indices; walk it end to end
    (``j = flat[j]``) so every load's address is the previous load's value —
    dependent loads the pipeline cannot overlap.  The final index folds into
    the revisited (1, 1) accumulator, keeping the whole chain live."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[0, 0] = jnp.float32(0.0)

    flat = x_ref[...].reshape(-1)
    j = jax.lax.fori_loop(0, flat.shape[0], lambda _, jj: flat[jj],
                          jnp.int32(0))
    o_ref[0, 0] += j.astype(jnp.float32)


def _stream_index_map(streams: int, n_blocks: int):
    """Block visit order: i -> interleaved across `streams` equal segments.
    streams=1 is the sequential (single-pointer) walk."""
    seg = n_blocks // streams

    def index_map(i):
        return (jax.lax.rem(i, streams) * seg + i // streams, 0)

    return index_map


def membench_call(x, *, mix: str = "load_sum", depth: int = 8,
                  block_rows: int = 128, streams: int = 1,
                  interpret: bool | None = None, y=None, ys=(),
                  interleave: int = 1):
    """x: (rows, 128) f32/bf16; returns scalar (load-family) or array (copy /
    triad) or tuple-of-arrays (rw family) output.  ``triad`` needs a second
    same-shape operand ``y``; ``rw_RtoW`` needs its R-1 extra read streams as
    ``ys`` and returns its W outputs as a tuple.  ``interleave`` splits each
    VMEM tile into independent row-chunk dependence chains (load_sum / copy /
    rw only — the bench backend gates the rest).  ``interpret=None`` follows
    the platform (``resolve_interpret``)."""
    interpret = resolve_interpret(interpret)
    rows, lanes = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    n_blocks = rows // block_rows
    assert n_blocks % streams == 0, (n_blocks, streams)
    if interleave > 1:
        assert mix in ("load_sum", "copy") or mix.startswith("rw_"), \
            f"mix {mix!r} has no interleaved variant"
        assert block_rows % interleave == 0, (block_rows, interleave)
    imap = _stream_index_map(streams, n_blocks)

    in_specs = [pl.BlockSpec((block_rows, lanes), imap)]
    operands = [x]
    base_mix = "fma" if mix.startswith("fma") else \
        ("rw" if mix.startswith("rw_") else mix)

    if base_mix == "rw":
        # one grid program emitting R tile-loads + W tile-stores per step
        from repro.bench.mixes import get_mix
        reads, writes = get_mix(mix).rw
        assert len(ys) == reads - 1, (mix, len(ys))
        assert all(s.shape == x.shape for s in ys), mix
        return pl.pallas_call(
            functools.partial(_rw_kernel, reads, writes, interleave),
            grid=(n_blocks,),
            in_specs=in_specs * reads,
            out_specs=tuple(pl.BlockSpec((block_rows, lanes), imap)
                            for _ in range(writes)),
            out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                            for _ in range(writes)),
            interpret=interpret,
        )(x, *ys)
    if base_mix == "mxu":
        w = jnp.eye(lanes, dtype=x.dtype)
        in_specs.append(pl.BlockSpec((lanes, lanes), lambda i: (0, 0)))
        operands.append(w)

    if base_mix == "latency_chase":
        # x is the int32 permutation buffer (see core.instruction_mix
        # .chase_perm with parts = rows / block_rows): one pointer cycle per
        # VMEM tile, walked serially inside the grid program
        return pl.pallas_call(
            _chase_kernel,
            grid=(n_blocks,),
            in_specs=in_specs[:1],
            out_specs=_SCALAR_OUT,
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            interpret=interpret,
        )(x)[0, 0]

    if base_mix == "copy":
        return pl.pallas_call(
            functools.partial(_copy_kernel, interleave),
            grid=(n_blocks,),
            in_specs=in_specs[:1],
            out_specs=pl.BlockSpec((block_rows, lanes), imap),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x)

    if base_mix == "triad":
        assert y is not None and y.shape == x.shape, "triad needs y of x.shape"
        return pl.pallas_call(
            _triad_kernel,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((block_rows, lanes), imap),
                      pl.BlockSpec((block_rows, lanes), imap)],
            out_specs=pl.BlockSpec((block_rows, lanes), imap),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret,
        )(x, y)

    kern = functools.partial(_acc_kernel, base_mix, depth, interleave)
    return pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=_SCALAR_OUT,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(*operands)[0, 0]
