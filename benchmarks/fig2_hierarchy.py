"""Fig 2/5/6 — memory-hierarchy throughput sweep under instruction mixes.

This *measures the host CPU* (its L1/L2/L3/DRAM) — the same experiment the
paper runs on A64FX/Altra/ThunderX2, proving the harness end-to-end.  The
script is one BenchSpec declaration; measurement goes through the bench
Runner, and the per-level table and mix-penalty ratios (the paper's FADD 69% /
NOP 88% / LOAD 99% analysis) are derived by core.analysis from the
schema-versioned BenchResult.
"""
from __future__ import annotations

import argparse
from pathlib import Path

from benchmarks.common import emit
from repro.bench import BenchSpec, Runner
from repro.core import analysis
from repro.core.buffers import hierarchy_grid
from repro.core.machine_model import detect_host

ART = Path(__file__).resolve().parents[1] / "artifacts"


def spec_for(quick: bool) -> BenchSpec:
    if quick:
        return BenchSpec(
            mixes=("load_sum", "copy", "fma_8"),
            sizes=hierarchy_grid(quick=True),
            reps=5, warmup=2, target_bytes=5e7)
    return BenchSpec(
        mixes=("load_sum", "copy", "fma_2", "fma_8", "fma_32"),
        sizes=hierarchy_grid(),
        reps=10, warmup=2, target_bytes=2e8)


def main(quick: bool = False):
    res = Runner().run(spec_for(quick))
    host = detect_host()
    model = analysis.build_machine_model(res, host)

    ART.mkdir(exist_ok=True)
    res.to_json(ART / "fig2_sweep.json")
    model.to_json(ART / "machine_model_host.json")

    for p in res.points:
        emit(f"fig2/{p.mix}/{p.nbytes}B", p.mean_s * 1e6,
             f"{p.gbps:.2f}GB/s")
    print()
    print(analysis.format_table(model.level_bw, model.mix_penalty))
    if model.ridge_flops_per_byte:
        print(f"\nmeasured ridge point: {model.ridge_flops_per_byte:.1f} flop/B")


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
