"""Shared fixtures: the benchmark rebuilt at a tiny size in a temporary
checkout, so that every cell runs end to end on the CPU in seconds.

Run with ``python -m pytest perfbench/tests -q`` from the repository root.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: every configuration's working set, at a size the Pallas interpreter
#: runs in about a second
TINY_SHAPE = [64, 128]
TINY_TRAFFIC = {"pallas_load_sum_p256": {"passes": 4},
                "runner_copy_triad_p4": {"reps": 2, "warmup": 1}}


def build_root(dest: Path, traffic_overrides: dict | None = None) -> Path:
    """A checkout at ``dest`` holding ``BENCHMARK.json`` and the benchmark
    with every configuration cut to a tiny shape; returns its bench dir."""
    bench = dest / "perfbench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for name in ("drivers", "reference", "metrics"):
        (bench / name).symlink_to(BENCH / name)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in doc["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["shape"] = TINY_SHAPE
        (dest / c["file"]).write_text(json.dumps(cfg))
    overrides = {**TINY_TRAFFIC, **(traffic_overrides or {})}
    for path in (BENCH / "traffic").glob("*.json"):
        traffic = json.loads(path.read_text())
        traffic.update(overrides.get(path.stem, {}))
        (bench / "traffic" / path.name).write_text(json.dumps(traffic))
    (dest / "BENCHMARK.json").write_text(json.dumps(doc))
    return bench


@pytest.fixture
def tiny_bench(tmp_path) -> Path:
    return build_root(tmp_path)
