"""Every cell's set-up, window and check at a tiny size on the CPU, the
traffic drivers called through the harness with its look for a chip
skipped; and the command itself, which must refuse to run off a TPU."""
import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT, build_root
from perfbench import harness

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**31 + 977          # past 32 signed bits, as the driver's seeds are


def run_tiny(bench, workload, traced=False, seconds=0.3, seed=SEED):
    return harness.run(workload, seed, seconds, traced, time.perf_counter(),
                       bench_dir=bench, chips_required=False)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_on_cpu(tiny_bench, workload):
    res = run_tiny(tiny_bench, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    cell = harness.load_cell(workload, tiny_bench)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_is_correct_and_reads_spans(tiny_bench, workload):
    res = run_tiny(tiny_bench, workload, traced=True)
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0
    # a CPU trace has no TPU plane: device readers give nothing, not 0
    assert "membench_kernel_roofline" not in res["metrics"]
    if workload == "stream_runner":
        assert 0 < res["metrics"]["runner_build_share"]["value"] < 100


def test_same_seed_same_inputs(tiny_bench):
    from perfbench import data
    a = data.working_set(SEED, (64, 128), "float32")
    b = data.working_set(SEED, (64, 128), "float32")
    c = data.working_set(SEED + 1, (64, 128), "float32")
    assert (a == b).all() and not (a == c).all()
    assert float(a.min()) >= 1.0 and float(a.max()) < 2.0


def test_successor_is_one_cycle_missing_one_element():
    import numpy as np
    from perfbench import data
    succ = np.asarray(data.successor(2**33 + 5, (8, 128))).reshape(-1)
    n = succ.size
    seen, j = set(), 0
    while j not in seen:
        seen.add(j)
        j = int(succ[j])
    assert j == 0 and len(seen) == n - 1
    (left,) = set(range(n)) - seen
    assert succ[left] == left


def test_dummy_traffic_file_is_found_by_name(tmp_path):
    """A new traffic mix is a data file and a BENCHMARK.json entry."""
    bench = build_root(tmp_path)
    (bench / "traffic" / "dummy_copy_p2.json").write_text(json.dumps({
        "driver": "case", "backend": "pallas", "mix": "copy", "passes": 2,
        "limits": {"acc_rel_gap": 1e-5, "kernel_rel_gap": 0}}))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "dummy", "config": "membench_f32_1mib",
                             "traffic": "dummy_copy_p2", "chips": 1,
                             "why": "a test cell"})
    doc["end_to_end"][0]["workloads"].append("dummy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    res = run_tiny(bench, "dummy")
    assert res["correct"] and "gbps" in res["metrics"]


def test_split_metric_is_read_by_its_stem():
    """``idle_share.bw`` has no file of its own: ``idle_share.py`` reads it."""
    assert harness.reader_path(BENCH, "idle_share.bw") == \
        BENCH / "metrics" / "idle_share.py"
    assert harness.reader_path(BENCH, "gbps") == BENCH / "metrics" / "gbps.py"


def _command(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "stream_copy",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_command_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
