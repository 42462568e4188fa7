"""The ``all_reduce`` collective mix through BenchSpec → Runner on the
``sharded`` backend: its accounting in the registry, the backends that
refuse it, the one-device case in process, and on 4 forced host devices (a
subprocess — tests see one device by design, see conftest.py) the Runner's
case on seeded data against the plain reference, every rank's copy, and the
compiled-case cache."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.bench import BenchSpec, BenchSpecError, Runner
from repro.bench.mixes import get_mix
from repro.core import collective_bench as cb

SRC = str(Path(__file__).resolve().parents[1] / "src")
ROWS, LANES, PASSES = 64, 128, 4

#: float32 sums of positive values: each of the k - 1 adds of the exchange
#: and each of the 2 * passes + 1 adds of the accumulator rounds by at most
#: 2**-24 of its running sum, so at k = 4 and 4 passes the gap from the
#: float64 reference stays under 12 * 2**-24 = 7.2e-7 of it; a bfloat16 sum
#: (2**-9 a rounding) misses it by three orders of magnitude
TOL = 1e-6


def _seeded(seed, shape=(ROWS, LANES)):
    return jax.random.uniform(jax.random.key(seed), shape, jnp.float32,
                              1.0, 2.0)


# ---------------------------------------------------------------------------
# in process: accounting, refusals, one device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 4])
def test_registry_accounting_is_payload_and_bus_bytes(k):
    mix = get_mix("all_reduce")
    nbytes = 4 * 2**20
    assert mix.bytes_per_pass(nbytes, k) == nbytes / k
    assert mix.bus_bytes_per_pass(nbytes, k) == pytest.approx(
        2 * (k - 1) / k * nbytes / k)
    assert cb._ring_factor("all_reduce", k) == pytest.approx(
        0.0 if k == 1 else 2 * (k - 1) / k)


def test_element_mixes_keep_their_bytes_and_have_no_bus_formula():
    copy = get_mix("copy")
    assert copy.bytes_per_pass(1024) == copy.bytes_per_pass(1024, 4) == 2048
    with pytest.raises(ValueError, match="not a collective"):
        copy.bus_factor(4)


@pytest.mark.parametrize("backend", ["xla", "pallas", "distributed"])
def test_single_device_backends_refuse_all_reduce(backend):
    with pytest.raises(BenchSpecError, match="collective"):
        BenchSpec(mixes=("all_reduce",), backend=backend)


@pytest.mark.parametrize("knob", [{"streams": 2}, {"block_rows": 16},
                                  {"interleave": 2}])
def test_sharded_refuses_walk_knobs_for_all_reduce(knob):
    spec = BenchSpec(mixes=("all_reduce",), backend="sharded", sizes=(2**16,),
                     **knob)
    with pytest.raises(BenchSpecError, match="collectives take no walk"):
        Runner().run(spec)


def test_one_device_all_reduce_returns_its_input():
    """With one rank the exchange is empty: the output is the input, and
    the Runner's case returns the reference accumulator."""
    from repro.bench.backends import get_backend
    spec = BenchSpec(mixes=("all_reduce",), sizes=(ROWS * LANES * 4,),
                     backend="sharded", devices=1, passes=PASSES, reps=2,
                     warmup=1)
    (pt,) = Runner().run(spec).points
    assert pt.devices == 1 and pt.gbps > 0
    assert pt.bytes_per_call == PASSES * ROWS * LANES * 4
    backend, mix = get_backend("sharded"), get_mix("all_reduce")
    x = _seeded(3)
    case = backend.make_case(spec, mix, x.shape, x.dtype, PASSES)
    acc = backend.bind_case(case, spec, mix, backend.prepare_buffer(spec, x))()
    total, ref = cb.reference_all_reduce(x, 1, PASSES)
    np.testing.assert_array_equal(total, np.asarray(x, np.float64))
    assert acc.shape == (1,)
    assert abs(float(acc[0]) - ref) <= TOL * ref


def test_bf16_reference_fails_the_tolerance():
    x = _seeded(5, (4 * 16, LANES))
    total, ref = cb.reference_all_reduce(x, 4, PASSES)
    low, low_acc = cb.reference_all_reduce(x, 4, PASSES,
                                           dt=ml_dtypes.bfloat16)
    gap = np.max(np.abs(low.astype(np.float64) - total) / total)
    assert gap > 100 * TOL
    assert abs(low_acc - ref) > TOL * ref


# ---------------------------------------------------------------------------
# 4 forced host devices (subprocess)
# ---------------------------------------------------------------------------

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.bench import BenchSpec, Runner
from repro.core import buffers, collective_bench as cb, timing

ROWS, LANES, PASSES = %d, %d, %d
x = jax.random.uniform(jax.random.key(11), (ROWS, LANES), jnp.float32,
                       1.0, 2.0)
# the Runner builds its working set from the seeded array, and each timed
# call's result is kept
buffers.working_set = lambda nbytes, dtype, value: x
outs, real_time_fn = [], timing.time_fn
def time_fn(fn, **kw):
    outs.append(np.asarray(fn()))
    return real_time_fn(fn, **kw)
timing.time_fn = time_fn

spec = BenchSpec(mixes=("all_reduce",), sizes=(ROWS * LANES * 4,),
                 backend="sharded", devices=4, passes=PASSES, reps=2,
                 warmup=1)
runner = Runner()
res = runner.run(spec)
(pt,) = res.points
rerun = runner.run(spec)
total, ref = cb.reference_all_reduce(x, 4, PASSES)

mesh = Mesh(np.array(jax.devices()), ("d",))
ranks = jax.jit(jax.shard_map(lambda v: cb._all_reduce(v, "d"), mesh=mesh,
                              in_specs=P("d", None), out_specs=P("d", None)))
copies = np.asarray(ranks(x), np.float64).reshape(4, ROWS // 4, LANES)
mesh22 = Mesh(np.array(jax.devices()).reshape(2, 2), ("a", "b"))
r = cb.bench_collective(mesh22, "b", "all_reduce", 2 * 8 * 128 * 4, reps=2)
print(json.dumps({
    "accs": outs[0].tolist(), "ref": ref,
    "out_rel_gap": float(np.max(np.abs(copies - total) / total)),
    "replica_gap": float(np.max(np.abs(copies - copies[0]))),
    "bytes_per_call": pt.bytes_per_call, "nbytes": pt.nbytes,
    "devices": pt.devices, "counters": res.meta["obs"]["counters"],
    "rerun": rerun.meta["obs"]["counters"],
    "bench_collective": [r.group_size, r.nbytes, r.algo_gbps, r.link_gbps],
}))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c",
                        SNIPPET % (ROWS, LANES, PASSES)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_runner_all_reduce_equals_reference_on_4_devices(four_devices):
    accs, ref = np.asarray(four_devices["accs"]), four_devices["ref"]
    assert accs.shape == (4,)
    assert np.all(np.abs(accs - ref) <= TOL * ref), (accs, ref)
    assert four_devices["out_rel_gap"] <= TOL


def test_every_rank_holds_the_same_sum(four_devices):
    assert len(set(four_devices["accs"])) == 1
    assert four_devices["replica_gap"] == 0.0


def test_bytes_per_call_is_passes_times_payload(four_devices):
    assert four_devices["devices"] == 4
    assert four_devices["bytes_per_call"] == \
        PASSES * four_devices["nbytes"] / 4


def test_case_build_is_counted_and_cached(four_devices):
    built = four_devices["counters"]
    assert built["collective_cases_built"] == 1
    assert built["passloop_chain_barrier"] == 1
    rerun = four_devices["rerun"]
    assert rerun["cache_hits"] == 1 and "cache_misses" not in rerun
    assert "collective_cases_built" not in rerun


def test_bench_collective_all_reduce_runs_the_runner_case(four_devices):
    n, payload, algo, link = four_devices["bench_collective"]
    assert n == 2 and payload == 8 * 128 * 4
    assert algo > 0 and link == pytest.approx(algo * 2 * (n - 1) / n)
