"""jit'd wrappers + work accounting for the membench Pallas kernels.

Accounting delegates to the shared mix registry (``repro.bench.mixes``) so the
Pallas path and the XLA oracles can never disagree about bytes/flops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.membench.membench import membench_call, resolve_interpret
from repro.obs import metrics


def _split_mix(mix: str, depth: int) -> tuple[str, int]:
    """'fma_4' -> ('fma', 4); other names pass through with default depth."""
    if mix.startswith("fma_"):
        return "fma", int(mix.split("_")[1])
    return mix, depth


def _jit_named(name: str):
    """``jax.jit`` under a stable ``name``: the compiled module reads
    ``jit_<name>`` and a call inlined from it keeps the name, so a profiler
    trace finds the program by it."""
    def jit(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)
    return jit


def make_kernel(mix: str = "load_sum", depth: int = 8, block_rows: int = 128,
                streams: int = 1, interpret: bool | None = None,
                interleave: int = 1):
    """Returns jit'd fn(x) -> jax array (scalar or array output).

    ``triad`` returns fn(x, y) — two read streams, one write stream.
    ``interleave`` > 1 splits each VMEM tile into independent row-chunk
    dependence chains (load_sum / copy / rw only).  ``interpret=None``
    follows the platform (``membench.resolve_interpret``); tests pass
    ``True``/``False`` to steer it.  The jitted function is named
    ``membench_<mix>``.
    """
    base_mix, depth_eff = _split_mix(mix, depth)
    named = _jit_named(f"membench_{mix}")

    if base_mix == "triad":
        @named
        def fn2(x, y):
            return membench_call(x, mix="triad", depth=depth_eff,
                                 block_rows=block_rows, streams=streams,
                                 interpret=interpret, y=y)
        return fn2

    if mix.startswith("rw_"):
        @named
        def fnr(x, *ys):
            return membench_call(x, mix=mix, depth=depth_eff,
                                 block_rows=block_rows, streams=streams,
                                 interpret=interpret, ys=ys,
                                 interleave=interleave)
        return fnr

    @named
    def fn(x):
        return membench_call(x, mix=base_mix, depth=depth_eff,
                             block_rows=block_rows, streams=streams,
                             interpret=interpret, interleave=interleave)

    return fn


def make_timed_kernel(mix: str = "load_sum", depth: int = 8,
                      block_rows: int = 128, streams: int = 1,
                      interpret: bool | None = None, passes: int = 1,
                      unroll: int = 1, interleave: int = 1,
                      load: int = 0):
    """Like make_kernel, but loops ``passes`` times over the buffer inside one
    compiled call (the paper's measurement loop) so dispatch overhead does not
    swamp cache-resident working sets.  Each sweep depends on the one before
    it, so XLA can neither hoist the kernel out of the loop nor merge two
    sweeps.  How that chain is made follows what the kernel is
    (``resolve_interpret``), and each build counts which in
    ``metrics.REGISTRY``:

    * ``passloop_chain_write``: a one-element self-dependent write into the
      working set (as in the XLA oracles).  Scalar-output mixes, the
      loaded chase, and every mix whose kernel is interpreted.
    * ``passloop_chain_barrier``: array-output mixes with a compiled kernel
      pass ``(x, extra, acc)`` through ``jax.lax.optimization_barrier``
      before each sweep and write nothing, so the working set is read-only
      loop state that XLA neither copies at entry nor writes per pass.

    ``unroll`` runs that many chained kernel sweeps per loop trip
    (``core.instruction_mix._pass_loop`` — the same unroll discipline as the
    oracles, so accounting parity holds by construction).
    Always returns a scalar fn — fn(x), or fn(x, y) for ``triad`` — named
    ``membench_passloop_<mix>``.

    Mixes whose kernel produces array outputs (copy / triad / rw) loop-carry
    those outputs through the pass loop with ROTATING per-sweep slots
    (``core.instruction_mix._rotating_pass_loop``): while-loop state must be
    fully materialized every iteration, and one slot per unrolled sweep
    means EVERY sweep's outputs are loop state — interpret-mode XLA can
    narrow neither the whole timed sweep down to the one element the
    accumulator consumes (the repro.audit DCE finding,
    ``tests/data/hlo/dce_pallas_copy.txt``) nor the interior unrolled sweeps
    (the dead-interior-sweep finding,
    ``tests/data/hlo/dead_sweep_xla_copy_u4.txt``).  On real TPU the opaque
    pallas_call never had either hazard, and the slots only alias the output
    buffers the kernel writes anyway.  The compiled pass loop's HLO (no
    entry copy, ``unroll`` kernel calls a trip) is pinned by
    ``tests/test_tpu_compile.py``; ``repro.audit`` sees the interpreted one.

    ``load`` > 0 (``latency_chase`` only — the bench spec gates it) builds
    the loaded-latency composite fn(perm, gen): each probe pass is followed
    by ``load * GEN_SWEEPS_PER_PASS`` load_sum generator sweeps of ``gen``,
    chained through the accumulator — the same time-shared emulation as the
    xla oracle ``k_chase_loaded``, so accounting parity holds.
    """
    from repro.core.instruction_mix import (_consume_slots, _pass_loop,
                                            _rotating_pass_loop)
    base_mix, _ = _split_mix(mix, depth)
    named = _jit_named(f"membench_passloop_{mix}")
    interpret = resolve_interpret(interpret)
    array_out = base_mix in ("copy", "triad") or mix.startswith("rw_")
    barrier = array_out and not interpret
    metrics.REGISTRY.inc("passloop_chain_barrier" if barrier
                         else "passloop_chain_write")
    one = make_kernel(mix, depth=depth, block_rows=block_rows,
                      streams=streams, interpret=interpret,
                      interleave=interleave)

    def _fold(r, acc):
        val = r if getattr(r, "ndim", 0) == 0 else r.reshape(-1)[0]
        return acc + val.astype(jnp.float32)

    def _chain(x, r, acc):
        acc = _fold(r, acc)
        eps = (acc * 1e-30).astype(x.dtype).reshape(())
        return x.at[(0,) * x.ndim].add(eps), acc

    def _perturb(t, acc):
        eps = (acc * 1e-30).astype(t.dtype).reshape(())
        return t.at[(0,) * t.ndim].add(eps)

    def _carried(call, x, extra):
        """Pass loop with the kernel outputs in rotating per-sweep carry
        slots — every unrolled sweep's outputs stay live loop state.

        The chain between sweeps: a compiled kernel is an opaque custom call
        that XLA cannot look into, so an ``optimization_barrier`` that ties
        its operands to the previous sweep's ``acc`` is enough to keep each
        call in the loop and apart from the others, and the working set is
        never written (nor copied into the loop state first).  An
        interpreted kernel is plain HLO: XLA:CPU drops the barrier and
        merges the sweeps (the audit then counts half the work), so there
        each sweep writes one element of every read stream and the kernel
        reads distinct data."""
        out0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            jax.eval_shape(call, x, *extra))

        def sweep(_, state, _outs):
            x, extra, acc = state
            if barrier:
                x_k, extra_k, acc = jax.lax.optimization_barrier(
                    (x, extra, acc))
                outs = call(x_k, *extra_k)
                for o in jax.tree.leaves(outs):
                    acc = _fold(o, acc)
                return (x, extra, acc), outs
            outs = call(x, *extra)
            for o in jax.tree.leaves(outs):
                x, acc = _chain(x, o, acc)
            # Extra read streams must be perturbed too: a loop-invariant
            # operand lets XLA hoist its arithmetic (e.g. triad's a*y scale)
            # out of the timed loop, halving the executed flops.
            extra = tuple(_perturb(e, acc) for e in extra)
            return (x, extra, acc), outs

        (_, _, acc), slots = _rotating_pass_loop(
            sweep, passes, unroll, (x, tuple(extra), jnp.float32(0)), out0)
        return _consume_slots(acc, slots)

    if base_mix == "triad":
        @named
        def fn2(x, y):
            return _carried(one, x, (y,))
        return fn2

    if mix.startswith("rw_"):
        @named
        def fnr(x, *ys):
            return _carried(one, x, ys)
        return fnr

    if base_mix == "copy":
        @named
        def fnc(x):
            return _carried(one, x, ())
        return fnc

    if base_mix == "latency_chase" and load:
        from repro.bench.mixes import GEN_SWEEPS_PER_PASS
        gen_one = make_kernel("load_sum", depth=depth, block_rows=block_rows,
                              streams=streams, interpret=interpret)
        sweeps = load * GEN_SWEEPS_PER_PASS

        @named
        def fnl(x, g):         # x: int32 perm buffer; g: generator buffer
            def gsweep(_, c):
                g, acc = c
                return _chain(g, gen_one(g), acc)

            def body(_, carry):
                x, g, acc = carry
                # _chain's eps converts to x's int32 dtype, truncating the
                # tiny float to 0 — a value-preserving, data-dependent write
                # that keeps the perm cycle intact while chaining passes
                x, acc = _chain(x, one(x), acc)
                g, acc = jax.lax.fori_loop(0, sweeps, gsweep, (g, acc))
                return (x, g, acc)

            _, _, acc = _pass_loop(body, passes, unroll,
                                   (x, g, jnp.float32(0)))
            return acc

        return fnl

    @named
    def fn(x):                 # scalar-output mixes: nothing to narrow
        def body(_, carry):
            x, acc = carry
            x, acc = _chain(x, one(x), acc)
            return (x, acc)
        _, acc = _pass_loop(body, passes, unroll, (x, jnp.float32(0)))
        return acc

    return fn


def work_per_call(mix: str, x, depth: int = 8) -> tuple[float, float]:
    """(bytes, flops) moved/executed by one kernel invocation — straight from
    the shared mix registry."""
    from repro.bench import mixes as mixreg
    name = mix
    if mix == "fma":
        name = f"fma_{depth}"
    m = mixreg.get_mix(name)
    return m.bytes_per_pass(x.size * x.dtype.itemsize), m.flops_per_pass(x.size)
