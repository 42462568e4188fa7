"""Collective / interconnect throughput — C6's remote-access study, mesh-native.

The paper measures NUMA-remote access and multi-core scaling; the TPU analogue
is per-link ICI throughput under each collective pattern.  Runs on any mesh
(host CPU devices for harness validation; real ICI on hardware).  Reports
algorithm bandwidth *and* ring-model link bandwidth so results compare directly
against the documented ~50 GB/s/link.

``all_reduce`` is a mix of the registry (``repro.bench.mixes``): the
``sharded`` backend's case for it is ``make_passloop``, its plain reference
is ``reference_all_reduce``, and ``bench_collective(op="all_reduce")`` runs
that case through the Runner.  The other four ops (all_gather,
reduce_scatter, all_to_all, ppermute) keep their own one-call timing loop
here until they become mixes too.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import buffers, timing
from repro.obs import metrics


@dataclass
class CollectiveResult:
    op: str
    axis: str
    group_size: int
    nbytes: int
    mean_s: float
    std_s: float
    algo_gbps: float       # payload bytes / time
    link_gbps: float       # ring-model per-link wire bandwidth


def _ring_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all_reduce":
        from repro.bench.mixes import get_mix
        return get_mix(op).bus_factor(n)
    return {"all_gather": (n - 1) / n,
            "reduce_scatter": (n - 1) / n,
            "all_to_all": (n - 1) / n,
            "ppermute": 1.0}[op]


def _all_reduce(v, axis: str):
    """One all-reduce: every rank's ``v``, summed, on every rank."""
    return jax.lax.psum(v, axis)


def make_passloop(mesh, op: str, passes: int, unroll: int = 1):
    """The timed program of a collective mix over the 1-D ``mesh``:
    ``fn(x) -> (ranks,)`` runs ``passes`` exchanges of each rank's shard of
    ``x`` (rows split over the ranks) in one call and returns every rank's
    accumulator.

    Each pass reads the same, never-written shard and writes a rotating
    output slot (``instruction_mix._rotating_pass_loop``).  ``(x, acc)`` go
    through an ``optimization_barrier`` before each exchange, tying its
    operand to the previous pass's accumulator, so XLA can neither hoist an
    exchange out of the loop nor merge two.  The accumulator folds the first
    and the last element of each pass's output, each of which depends on
    every rank's shard, then one element of every slot.  The module is
    ``jit_collective_passloop_<op>``; each pass calls the nested jit
    ``collective_<op>``, which a check can take out and run alone.  Each
    build counts ``collective_cases_built`` and ``passloop_chain_barrier``
    in ``metrics.REGISTRY``."""
    from repro.core import instruction_mix as im
    from repro.kernels.membench.ops import _jit_named
    if op != "all_reduce":
        raise KeyError(f"no collective pass loop for {op!r}")
    (axis,) = mesh.axis_names
    exchange = _jit_named(f"collective_{op}")(lambda v: _all_reduce(v, axis))

    def sweep(_, state, _out):
        x_k, acc = jax.lax.optimization_barrier(state)
        out = exchange(x_k)
        acc = (acc + out[0, 0].astype(jnp.float32)
               + out[-1, -1].astype(jnp.float32))
        return (state[0], acc), out

    def rank(x):
        (_, acc), slots = im._rotating_pass_loop(
            sweep, passes, unroll, (x, jnp.float32(0)), jnp.zeros_like(x))
        return im._consume_slots(acc, slots).reshape(1)

    ranks = jax.shard_map(rank, mesh=mesh, in_specs=P(axis, None),
                          out_specs=P(axis), check_vma=False)
    metrics.REGISTRY.inc("collective_cases_built")
    metrics.REGISTRY.inc("passloop_chain_barrier")

    @_jit_named(f"collective_passloop_{op}")
    def passloop(x):
        return ranks(x)
    return passloop


def reference_all_reduce(x, ranks: int, passes: int, unroll: int = 1,
                         dt=np.float64):
    """The plain reference of ``make_passloop(op="all_reduce")`` on the
    host, with no mesh: the sum of the ``ranks`` row blocks of ``x``, and
    the accumulator ``passes`` passes leave, all in ``dt``."""
    shards = np.asarray(x).astype(dt)
    shards = shards.reshape(ranks, -1, *shards.shape[1:])
    total = shards[0]
    for s in shards[1:]:
        total = total + s
    first, last = dt(total[0, 0]), dt(total[-1, -1])
    acc = dt(0)
    for _ in range(passes):
        acc = dt(dt(acc + first) + last)
    for _ in range(unroll):
        acc = dt(acc + last)
    return total, float(acc)


def _all_reduce_via_runner(axis: str, n: int, payload: int, reps: int,
                           dtype) -> CollectiveResult:
    """All-reduce of about ``payload`` bytes a rank over the first ``n``
    devices, timed by the Runner on the ``sharded`` backend (each rank's
    shard rounded to whole 8-row tiles)."""
    from repro.bench import BenchSpec, Runner
    tile = 8 * 128 * jnp.dtype(dtype).itemsize
    shard = max(1, round(payload / tile)) * tile
    spec = BenchSpec(mixes=("all_reduce",), sizes=(n * shard,),
                     dtype=jnp.dtype(dtype).name, backend="sharded",
                     devices=n, reps=reps, warmup=2)
    (pt,) = Runner().run(spec).points
    mean_s, std_s = pt.mean_s / pt.passes, pt.std_s / pt.passes
    return CollectiveResult(
        op="all_reduce", axis=axis, group_size=n, nbytes=shard,
        mean_s=mean_s, std_s=std_s, algo_gbps=shard / mean_s / 1e9,
        link_gbps=shard * _ring_factor("all_reduce", n) / mean_s / 1e9)


def bench_collective(mesh, axis: str, op: str, nbytes: int,
                     reps: int = 10, dtype=jnp.float32) -> CollectiveResult:
    """One collective over ``axis`` of ``mesh``, ``nbytes`` in all, so
    ``nbytes / mesh.shape[axis]`` a rank.  ``all_reduce`` runs the Runner's
    case over the first ``mesh.shape[axis]`` devices; the other ops time
    one call of a ``shard_map`` over ``mesh`` itself."""
    n = mesh.shape[axis]
    if op == "all_reduce":
        return _all_reduce_via_runner(axis, n, nbytes // n, reps, dtype)
    elems = max(128, nbytes // jnp.dtype(dtype).itemsize)
    elems = (elems // (128 * n)) * 128 * n or 128 * n
    x = buffers.init_pattern(elems, dtype=dtype).reshape(n, -1)

    if op == "all_gather":
        body = lambda v: jax.lax.all_gather(v, axis, tiled=True)
        in_spec, out_spec = P(axis), P()
    elif op == "reduce_scatter":
        # replicated input (n, m); each device ends with its (n/size, m) slice
        body = lambda v: jax.lax.psum_scatter(v, axis, tiled=True)
        in_spec, out_spec = P(), P(axis)
    elif op == "all_to_all":
        def body(v):  # local (1, m) -> (n, m/n) lanes -> a2a -> back to (1, m)
            w = jax.lax.all_to_all(v.reshape(n, -1), axis, 0, 0, tiled=False)
            return w.reshape(v.shape)
        in_spec, out_spec = P(axis), P(axis)
    elif op == "ppermute":
        perm = [(i, (i + 1) % n) for i in range(n)]
        body = lambda v: jax.lax.ppermute(v, axis, perm)
        in_spec, out_spec = P(axis), P(axis)
    else:
        raise KeyError(op)

    def fn(x):
        out = jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                            out_specs=out_spec, check_vma=False)(x)
        return jax.tree.leaves(out)[0]

    fjit = jax.jit(fn)
    payload = x.size * x.dtype.itemsize // n      # per-device payload
    t = timing.time_fn(fjit, x, reps=reps, warmup=2, bytes_per_call=payload)
    link = payload * _ring_factor(op, n) / t.mean_s / 1e9
    return CollectiveResult(op=op, axis=axis, group_size=n,
                            nbytes=payload, mean_s=t.mean_s, std_s=t.std_s,
                            algo_gbps=payload / t.mean_s / 1e9, link_gbps=link)


def bench_all(mesh, nbytes: int = 4 * 2**20, ops=None, reps: int = 10):
    ops = ops or ["all_reduce", "all_gather", "reduce_scatter", "all_to_all",
                  "ppermute"]
    out = []
    for axis in mesh.axis_names:
        if mesh.shape[axis] < 2:
            continue
        for op in ops:
            out.append(bench_collective(mesh, axis, op, nbytes, reps=reps))
    return out
