"""Fig 6 — bandwidth-bound vs issue-bound classification (repro.istream).

The paper's decode-width finding as a table: sweep the instruction-stream
knobs (unroll x interleave) over lean and store-mixed kernels on both
backends, extract each compiled case's HLO instruction profile, and label
every measured point bandwidth-bound or issue-bound with a confidence
margin.  Cache-resident sizes should trend issue-bound (the working set is
cheap to move, the issue path is the limiter); DRAM-resident sizes
bandwidth-bound.

Caption note: since the rotating-carry fix, carried-mix (copy / triad /
rw) unroll columns are **absolute GB/s** — the accounting auditor enforces
that unroll=u moves u x one sweep's declared traffic, and each table row's
``traffic`` column records that provenance (``audited``).  Only rows with
a documented waiver (e.g. chunked interleave>1) remain issue-axis shapes.

This script is a thin declaration over ``repro.istream.run_istream`` — the
sweep grid is the only thing decided here.  A fitted machine model
(``python -m repro.bench characterize --out model.json``) sharpens the
bandwidth side of the classification; without one the sweep
self-calibrates from its own fastest points.
"""
from __future__ import annotations

import argparse
from pathlib import Path

from benchmarks.common import emit
from repro.istream import run_istream

ART = Path(__file__).resolve().parents[1] / "artifacts"


def grid(quick: bool = False, smoke: bool = False) -> dict:
    if smoke:
        return dict(smoke=True)
    if quick:
        return dict(sizes=(1 << 16, 1 << 20, 1 << 23),
                    unrolls=(1, 2), interleaves=(1, 2), reps=3)
    return dict(sizes=(1 << 16, 1 << 20, 1 << 24, 1 << 26),
                unrolls=(1, 2, 4), interleaves=(1, 2, 4), reps=5)


def main(quick: bool = False, smoke: bool = False, out: str | None = None,
         model: str | None = None):
    kw = grid(quick, smoke)
    if model:
        from repro.characterize.fit import FittedMachineModel
        kw["model"] = FittedMachineModel.from_json(model)
    report = run_istream(**kw)
    for p in sorted(report.result.points,
                    key=lambda p: (p.backend, p.mix, p.nbytes,
                                   p.unroll, p.interleave)):
        info = p.istream or {}
        emit(f"fig6/{p.backend}/{p.mix}/u{p.unroll}i{p.interleave}/"
             f"{p.nbytes}B", p.mean_s * 1e6,
             f"{p.gbps:.2f}GB/s;{info.get('label', 'unclassified')}")
    print()
    print(report.table)

    if out:
        report.result.to_json(out)
        print(f"# saved {len(report.result.points)} classified points "
              f"(schema v{report.result.schema_version}) -> {out}")
    elif not smoke:
        ART.mkdir(exist_ok=True)
        report.result.to_json(ART / "fig6_istream.json")
    return report


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale grid — the CI smoke gate")
    ap.add_argument("--out", default=None,
                    help="write the classified result JSON here")
    ap.add_argument("--model", default=None,
                    help="FittedMachineModel JSON for bandwidth lookup")
    main(**vars(ap.parse_args()))
