"""How the Pallas pass loop chains its sweeps, read from the traced program
(``jax.make_jaxpr``; nothing is compiled or run).

A compiled kernel is an opaque custom call: array-output mixes chain their
sweeps through an ``optimization_barrier`` and never write the working set.
An interpreted kernel is HLO that XLA:CPU sees into: the pass loop keeps its
one-element write (a ``scatter-add``) into every read stream.  Each build
counts the chaining it took in ``repro.obs.metrics.REGISTRY``."""
import jax
import jax.numpy as jnp
import pytest

from repro.bench import BenchSpec, Runner
from repro.kernels.membench import ops
from repro.obs import metrics

SHAPE = (256, 128)
STREAMS = {"copy": 1, "triad": 2, "rw_2to1": 2}     # read streams a call


def _primitives(jaxpr) -> list:
    """Every equation of ``jaxpr`` and of the programs nested in it."""
    found = []
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_primitives(sub))
    return found


def _build(mix, interpret, **kw):
    """The timed kernel, its program's equations, and the chain counters
    its build moved."""
    with metrics.REGISTRY.scope() as scope:
        fn = ops.make_timed_kernel(mix, interpret=interpret, block_rows=64,
                                   passes=4, **kw)
    x = jax.ShapeDtypeStruct(SHAPE, jnp.float32)
    eqns = _primitives(jax.make_jaxpr(fn)(*[x] * STREAMS.get(mix, 1)).jaxpr)
    counted = {k: v for k, v in scope.delta()["counters"].items()
               if k.startswith("passloop_chain_")}
    return eqns, counted


def _named(eqns, name):
    return [e for e in eqns if e.primitive.name == name]


def _writes_to_working_set(eqns):
    return [e for e in eqns
            if e.primitive.name in ("scatter-add", "scatter",
                                    "dynamic_update_slice")
            and tuple(e.outvars[0].aval.shape) == SHAPE]


@pytest.mark.parametrize("unroll", [1, 2])
@pytest.mark.parametrize("mix", sorted(STREAMS))
def test_compiled_array_mix_chains_through_a_barrier(mix, unroll):
    eqns, counted = _build(mix, interpret=False, unroll=unroll)
    assert len(_named(eqns, "optimization_barrier")) == unroll
    assert not _writes_to_working_set(eqns)
    assert len(_named(eqns, "pallas_call")) == unroll
    assert counted == {"passloop_chain_barrier": 1}


@pytest.mark.parametrize("unroll", [1, 2])
@pytest.mark.parametrize("mix", sorted(STREAMS))
def test_interpreted_array_mix_writes_every_read_stream(mix, unroll):
    eqns, counted = _build(mix, interpret=True, unroll=unroll)
    assert not _named(eqns, "optimization_barrier")
    writes = _writes_to_working_set(eqns)
    assert len(writes) == STREAMS[mix] * unroll, [e.primitive for e in writes]
    assert counted == {"passloop_chain_write": 1}


@pytest.mark.parametrize("interpret", [False, True])
def test_scalar_mix_writes_whatever_the_kernel(interpret):
    """``load_sum``'s pass loop is the same compiled or interpreted."""
    eqns, counted = _build("load_sum", interpret=interpret)
    assert not _named(eqns, "optimization_barrier")
    assert len(_writes_to_working_set(eqns)) == 1
    assert counted == {"passloop_chain_write": 1}


def test_chain_counters_count_builds_not_calls():
    before = metrics.REGISTRY.snapshot()["counters"]
    fns = [ops.make_timed_kernel("copy", interpret=i, block_rows=64,
                                 passes=2) for i in (False, False, True)]
    x = jnp.ones(SHAPE, jnp.float32)
    fns[2](x).block_until_ready()
    fns[2](x).block_until_ready()
    after = metrics.REGISTRY.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("passloop_chain_barrier", "passloop_chain_write")}
    assert moved == {"passloop_chain_barrier": 2, "passloop_chain_write": 1}


def test_runner_result_shows_the_chaining_it_built():
    """``BenchResult.meta["obs"]`` counts one chaining per case built: on
    the CPU every Pallas kernel is interpreted, so every pass loop writes."""
    res = Runner().run(BenchSpec(
        mixes=("copy", "load_sum"), sizes=(64 * 128 * 4,), backend="pallas",
        block_rows=64, passes=2, reps=1, warmup=1))
    counters = res.meta["obs"]["counters"]
    assert counters["passloop_chain_write"] == 2
    assert "passloop_chain_barrier" not in counters
