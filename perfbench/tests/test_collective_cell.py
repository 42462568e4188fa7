"""The ``osu_allreduce`` cell: on four forced host devices (a subprocess,
since the flag must be set before JAX starts) a tiny checkout runs it
correct, and the rest of a run with the timed path broken underneath comes
out not correct, once for each fault an exchange can have; the cell's
accounting against the program's registry; and the readers of its
per-layer metrics on a synthetic four-chip trace."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from conftest import BENCH, ROOT
from perfbench import collective_accounting, harness
from perfbench.trace_reduce import Reduced

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, tempfile, time
from pathlib import Path
sys.path[:0] = [%r, %r, %r]
import jax
from conftest import build_root
from perfbench import harness
from repro.core import collective_bench as cb, instruction_mix as im

bench = build_root(Path(tempfile.mkdtemp()))

def run():
    res = harness.run("osu_allreduce", 2**31 + 977, 0.3, False,
                      time.perf_counter(), bench_dir=bench,
                      chips_required=False)
    return {"correct": res["correct"], "checks": res["checks"],
            "failed": res["failed"], "count": jax.device_count(),
            "gbps": res["metrics"]["gbps"]["value"]}

def exchange_left_out(v, axis):
    return v

def one_rank_altered(v, axis):
    out = jax.lax.psum(v, axis)
    bump = (jax.lax.axis_index(axis) == 1).astype(v.dtype) * 1e-3
    return out.at[3, 5].add(bump)

def no_pass(sweep, passes, unroll, state, out0):
    return state, (out0,) * unroll

results = {"sound": run()}
for name, module, attr, fake in [
        ("exchange_left_out", cb, "_all_reduce", exchange_left_out),
        ("one_rank_altered", cb, "_all_reduce", one_rank_altered),
        ("no_pass", im, "_rotating_pass_loop", no_pass)]:
    real = getattr(module, attr)
    setattr(module, attr, fake)
    jax.clear_caches()
    try:
        results[name] = run()
    finally:
        setattr(module, attr, real)
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    code = SNIPPET % (str(ROOT), str(ROOT / "src"), str(BENCH / "tests"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cell_runs_correct_on_four_devices(four_devices):
    sound = four_devices["sound"]
    assert sound["count"] == 4
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    assert sound["checks"]["replica_gap"]["value"] == 0.0
    assert sound["gbps"] > 0


@pytest.mark.parametrize("fault", ["exchange_left_out", "one_rank_altered",
                                   "no_pass"])
def test_planted_fault_is_not_correct(four_devices, fault):
    res = four_devices[fault]
    assert not res["correct"], res["checks"]


def test_altered_rank_shows_in_the_extracted_exchange(four_devices):
    """One element of one rank's output moves neither accumulator: only
    the exchange taken out of the timed program shows it."""
    checks = four_devices["one_rank_altered"]["checks"]
    assert checks["acc_rel_gap"]["value"] <= checks["acc_rel_gap"]["limit"]
    assert checks["out_rel_gap"]["value"] > checks["out_rel_gap"]["limit"]
    assert checks["replica_gap"]["value"] > 0


@pytest.mark.parametrize("k", [1, 2, 4])
def test_accounting_equals_the_program_registry(k):
    from repro.bench.mixes import get_mix
    mix = get_mix("all_reduce")
    nbytes = 2**30
    assert collective_accounting.payload_bytes(nbytes, k) == \
        mix.bytes_per_pass(nbytes, k)
    assert collective_accounting.bus_bytes("all_reduce", nbytes, k) == \
        mix.bus_bytes_per_pass(nbytes, k)


# ---------------------------------------------------------------------------
# the per-layer readers on a synthetic trace of four chips
# ---------------------------------------------------------------------------

AR = ("%psum.10 = f32[524288,128]{1,0:T(8,128)} all-reduce(%get-tuple-"
      "element.66), channel_id=1, replica_groups={{0,1,2,3}}")
START = ("%all-reduce-start.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) "
         "all-reduce-start(%p), channel_id=2")
DONE = "%all-reduce-done.1 = f32[8,128]{1,0} all-reduce-done(%all-reduce-start.1)"
ADD = "%add.22 = f32[]{:T(128)} add(%get-tuple-element.65, %bitcast.7)"


def _ctx(devices, calls=1, exchanges=2, bus_bytes=1.5e9, peaks=True):
    red = Reduced(0.0, 100e6, devices, [])
    return harness.Context(
        cell=SimpleNamespace(bench_dir=BENCH),
        session=SimpleNamespace(work={"exchanges": exchanges,
                                      "bus_bytes": bus_bytes}),
        window=SimpleNamespace(traced_calls=calls), setup_s=0.0,
        peaks={"hbm_bytes_per_s": 1.0} if peaks else {}, trace=red)


def _chip(offset=0.0):
    """Two exchanges a call: one plain (10 ms), one async pair (from the
    start's start to the done's end, 20 ms), and 5 ms of other work."""
    return [(AR, offset, offset + 10e6), (ADD, offset + 10e6, offset + 15e6),
            (START, offset + 15e6, offset + 16e6),
            (DONE, offset + 30e6, offset + 35e6)]


@pytest.fixture
def on_v5e(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v5 lite")])


def _read(name, ctx):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_roofline_pairs_async_exchanges_and_averages_chips(on_v5e):
    ctx = _ctx([_chip(o) for o in (0.0, 1e6, 2e6, 3e6)])
    # 1.5e9 bytes at 200e9 B/s is 7.5 ms; the mean exchange takes 15 ms
    assert _read("allreduce_ici_roofline", ctx) == pytest.approx(50.0)
    # busy: 15 + 1 + 5 ms (the pair's 14 ms in flight run no operation),
    # of which the 5 ms add is outside the exchanges, on every chip
    assert _read("allreduce_outside_share", ctx) == pytest.approx(
        100 * 5 / 21)


def test_readers_report_nothing_for_another_exchange_count(on_v5e):
    chips = [_chip() for _ in range(4)]
    chips[2] = chips[2][:2]             # one chip lost its async pair
    for name in ("allreduce_ici_roofline", "allreduce_outside_share"):
        assert _read(name, _ctx(chips)) is None
        assert _read(name, _ctx([_chip()] * 4, calls=2)) is None


def test_readers_report_nothing_off_a_tpu():
    for name in ("allreduce_ici_roofline", "allreduce_outside_share"):
        assert _read(name, _ctx([_chip()] * 4, peaks=False)) is None
        assert _read(name, _ctx([])) is None


def test_unknown_device_kind_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v99")])
    with pytest.raises(KeyError, match="ici_peaks.json"):
        _read("allreduce_ici_roofline", _ctx([_chip()] * 4))
