"""The distributed (multi-process) execution layer: single-process parity of
the `distributed` backend vs `sharded`/`xla`, env-var autodetection, the
local launcher end-to-end (2 coordinated subprocesses, forced host devices),
gathered-result semantics (straggler merge, process meta), schema-v3
round-trips, and the v1/v2 golden back-compat promise.

Multi-process tests spawn subprocesses (conftest keeps this process at one
device by design); they share one launcher run via a module fixture to keep
the suite fast."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.bench import (BenchPoint, BenchResult, BenchSpec, BenchSpecError,
                         Runner, mix_names)
from repro.bench import distributed as dist

SRC = str(Path(__file__).resolve().parents[1] / "src")
DATA = Path(__file__).parent / "data"
TINY = dict(sizes=(16 * 2**10,), reps=2, warmup=1, passes=1)


def _clean_env(**extra):
    env = dict(os.environ, PYTHONPATH=SRC, **extra)
    for k in ("XLA_FLAGS", "REPRO_COORDINATOR", "REPRO_NUM_PROCESSES",
              "REPRO_PROCESS_ID"):
        env.pop(k, None)
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# single-process (in-process): the backend degenerates to sharded
# ---------------------------------------------------------------------------

def test_distributed_accounting_parity_vs_sharded_and_xla():
    """Accounting is registry-sourced, so xla == sharded == distributed for
    every oracle-runnable mix, by construction."""
    runner = Runner()
    assert mix_names("distributed") == mix_names("sharded") == mix_names("xla")
    for name in ("load_sum", "triad", "rw_2to1"):
        acct = {}
        for backend in ("xla", "sharded", "distributed"):
            spec = BenchSpec(mixes=(name,), backend=backend, **TINY)
            (pt,) = runner.run(spec).points
            assert pt.gbps > 0 and pt.mean_s > 0, (name, backend)
            acct[backend] = (pt.bytes_per_call, pt.flops_per_call)
        assert len(set(acct.values())) == 1, (name, acct)


def test_distributed_knob_rules_match_the_oracles():
    with pytest.raises(BenchSpecError):
        BenchSpec(mixes=("load_only",), backend="distributed", **TINY)
    with pytest.raises(BenchSpecError):
        Runner().run(BenchSpec(mixes=("copy",), backend="distributed",
                               streams=2, **TINY))
    with pytest.raises(BenchSpecError, match="devices=2"):
        Runner().run(BenchSpec(mixes=("load_sum",), backend="distributed",
                               devices=2, **TINY))   # 1 visible device here


def test_gather_result_is_identity_single_process():
    res = Runner().run(BenchSpec(mixes=("load_sum",), backend="distributed",
                                 **TINY))
    assert dist.gather_result(res) is res
    assert res.machine["process_count"] == 1
    assert res.machine["process_index"] == 0
    assert res.machine["local_device_count"] >= 1


# ---------------------------------------------------------------------------
# coordination plumbing (no jax.distributed needed)
# ---------------------------------------------------------------------------

def test_env_info_and_env_active(monkeypatch):
    for k in (dist.ENV_COORDINATOR + dist.ENV_NUM_PROCESSES
              + dist.ENV_PROCESS_ID):
        monkeypatch.delenv(k, raising=False)
    assert dist.env_info() == (None, None, None)
    assert not dist.env_active()
    monkeypatch.setenv("REPRO_COORDINATOR", "127.0.0.1:1234")
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "2")
    monkeypatch.setenv("REPRO_PROCESS_ID", "1")
    assert dist.env_info() == ("127.0.0.1:1234", 2, 1)
    assert dist.env_active()
    # JAX's own names are honored as fallback
    monkeypatch.delenv("REPRO_COORDINATOR")
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:9")
    assert dist.env_info()[0] == "10.0.0.1:9"


def test_ensure_initialized_noop_outside_launch(monkeypatch):
    for k in (dist.ENV_COORDINATOR + dist.ENV_NUM_PROCESSES
              + dist.ENV_PROCESS_ID):
        monkeypatch.delenv(k, raising=False)
    assert dist.ensure_initialized() is False
    # nproc set but no process id: a loud error beats a silent hang
    monkeypatch.setenv("REPRO_COORDINATOR", "127.0.0.1:1234")
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "2")
    with pytest.raises(RuntimeError, match="process id"):
        dist.ensure_initialized()


def test_launch_local_validates_args():
    with pytest.raises(ValueError, match="processes"):
        dist.launch_local(["true"], processes=0)
    with pytest.raises(ValueError, match="devices_per_process"):
        dist.launch_local(["true"], processes=1, devices_per_process=0)


def test_launch_local_propagates_worker_failure(tmp_path):
    rc = dist.launch_local(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        processes=2, timeout=60, stream_to=open(os.devnull, "w"))
    assert rc == 3


def test_launch_local_children_run_on_cpu():
    """The launcher simulates hosts on the CPU: its children never reach for
    the accelerator the parent may hold, whatever the parent's platform."""
    check = ("import os, sys; "
             "sys.exit(os.environ.get('JAX_PLATFORMS') != 'cpu')")
    rc = dist.launch_local([sys.executable, "-c", check], processes=2,
                           env=dict(os.environ, JAX_PLATFORMS="tpu"),
                           timeout=60, stream_to=open(os.devnull, "w"))
    assert rc == 0


# ---------------------------------------------------------------------------
# 2-process launcher end-to-end (subprocesses; one shared run)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gathered(tmp_path_factory):
    """One 2-process x 2-device launcher run: CLI `launch` -> workers run the
    distributed backend over the 4-device global mesh -> process 0 writes
    the gathered result."""
    out = tmp_path_factory.mktemp("dist") / "gathered.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.bench", "launch",
         "--processes", "2", "--devices-per-process", "2",
         "--timeout", "520", "--out", str(out),
         "--mixes", "load_sum,copy", "--sizes", "1M", "--reps", "2"],
        capture_output=True, text=True, env=_clean_env(), timeout=560)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    return json.loads(out.read_text()), r.stdout + r.stderr


def test_launcher_gathers_one_result_on_process0(gathered):
    d, log = gathered
    assert d["schema_version"] == 6
    assert d["machine"]["process_count"] == 2
    assert d["machine"]["process_index"] == 0
    assert d["machine"]["local_device_counts"] == [2, 2]
    assert d["machine"]["device_count"] == 4
    # all points on the full global mesh, positive throughput
    assert [p["mix"] for p in d["points"]] == ["load_sum", "copy"]
    assert all(p["devices"] == 4 and p["gbps"] > 0 for p in d["points"])
    # per-process timing rows kept for skew inspection; the merged point is
    # the straggler: its mean is the max across processes
    rows = d["meta"]["per_process_mean_s"]
    assert len(rows) == 2 and len(rows[0]) == len(d["points"])
    for i, p in enumerate(d["points"]):
        assert p["mean_s"] == pytest.approx(max(r[i] for r in rows))
        assert p["gbps"] == pytest.approx(
            p["bytes_per_call"] / p["mean_s"] / 1e9)
    # non-primary processes report instead of writing
    assert "[p1] # process 1/2 done" in log


def test_gathered_result_matches_sharded_accounting(gathered):
    """The acceptance criterion: a 2-process gathered run's per-point
    bytes/flops equals the single-process `sharded` backend at the same
    global device count (4), mix for mix — parity by construction."""
    d, _ = gathered
    snippet = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
from repro.bench import BenchSpec, Runner
res = Runner().run(BenchSpec(mixes=("load_sum", "copy"), sizes=(2**20,),
                             backend="sharded", devices=4, reps=2))
print(json.dumps([[p.mix, p.nbytes, p.bytes_per_call, p.flops_per_call]
                  for p in res.points]))
"""
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, env=_clean_env(), timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    sharded = json.loads(r.stdout.strip().splitlines()[-1])
    distributed = [[p["mix"], p["nbytes"], p["bytes_per_call"],
                    p["flops_per_call"]] for p in d["points"]]
    assert sharded == distributed


def test_gathered_result_roundtrips_as_v6(gathered):
    d, _ = gathered
    res = BenchResult.from_dict(d)
    assert res.schema_version == 6
    assert all(isinstance(p, BenchPoint) for p in res.points)
    # by_size resolves the requested size (1M here survives rounding intact)
    assert len(res.by_size(2**20)) == 2
    back = BenchResult.from_dict(json.loads(res.to_json()))
    assert back.points == res.points and back.machine == res.machine


def test_distributed_mesh_covers_every_process_or_raises():
    """devices < processes must fail loudly (a process with no shard can't
    represent the computation), and the round-robin device order spreads
    intermediate counts one-per-process."""
    snippet = r"""
from repro.bench import distributed as dist
dist.ensure_initialized()
import jax
from repro.bench import BenchSpec, BenchSpecError, Runner
from repro.bench.backends import get_backend
assert jax.process_count() == 2 and jax.device_count() == 4
devs = get_backend("distributed")._mesh_devices()
assert [d.process_index for d in devs] == [0, 1, 0, 1], devs
try:
    Runner().run(BenchSpec(mixes=("load_sum",), backend="distributed",
                           devices=1, sizes=(16 * 2**10,), reps=2,
                           warmup=1, passes=1))
except BenchSpecError as e:
    assert "no mesh shard" in str(e), e
else:
    raise AssertionError("devices=1 with 2 processes should be rejected")
# devices=2: one device per process via round-robin -> runs fine
res = Runner().run(BenchSpec(mixes=("load_sum",), backend="distributed",
                             devices=2, sizes=(16 * 2**10,), reps=2,
                             warmup=1, passes=1))
res = dist.gather_result(res)
assert res.points[0].devices == 2 and res.points[0].gbps > 0
print("COVERAGE_OK")
"""
    rc = dist.launch_local([sys.executable, "-c", snippet], processes=2,
                           devices_per_process=2, timeout=520,
                           stream_to=sys.stderr)
    assert rc == 0


# ---------------------------------------------------------------------------
# golden back-compat: v1/v2 files keep loading next to v3
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fname,ver", [("result_v1.json", 1),
                                       ("result_v2.json", 2)])
def test_pre_v3_goldens_still_load_with_defaults(fname, ver):
    res = BenchResult.from_json(DATA / fname)
    assert res.schema_version == ver
    assert all(p.nbytes_requested is None for p in res.points)
    # pre-v3 points only resolve by real size; no crash on requested lookup
    assert res.by_size(res.points[0].nbytes)
    d = json.loads(res.to_json())
    assert d["schema_version"] == ver


def test_v3_golden_records_process_topology():
    res = BenchResult.from_json(DATA / "result_v3.json")
    assert res.schema_version == 3
    assert res.machine["process_count"] == 2
    assert res.machine["local_device_counts"] == [2, 2]
    assert all(p.devices == 4 and p.nbytes_requested for p in res.points)
    assert len(res.meta["per_process_mean_s"]) == 2
