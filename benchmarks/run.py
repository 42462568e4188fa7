"""Benchmark runner — one entry per paper table/figure.

``python -m benchmarks.run``         quick pass of every benchmark
``python -m benchmarks.run --full``  full sweep (slower)

Every figure script is a BenchSpec declaration executed by the shared
``repro.bench`` Runner (``python -m repro.bench`` is the standalone CLI; the
``bench`` entry here smoke-runs it).  Output: ``name,us_per_call,derived``
CSV lines (+ analysis tables).

fig4 and the collective bench run in subprocesses (they force multi-device
jax before init); everything else runs in-process.  A device belongs to one
process at a time, so the subprocess entries run first, while this process
has not touched JAX yet.  The script exits 1 when any entry failed.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

#: (name, module) of the entries that run in their own process
SUBPROCESS_ENTRIES = (("fig4", "benchmarks.fig4_scaling"),
                      ("collectives", "benchmarks.collective_bench_main"))


def _subproc(mod: str, quick: bool) -> bool:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = f"{ROOT}/src:{ROOT}"
    cmd = [sys.executable, "-m", mod] + (["--quick"] if quick else [])
    r = subprocess.run(cmd, env=env, cwd=ROOT, text=True, capture_output=True,
                       timeout=3600)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stdout.write(f"# {mod} FAILED\n{r.stderr[-2000:]}\n")
    return r.returncode == 0


def _bench(quick: bool):
    from repro.bench.cli import main as bench_main
    (ROOT / "artifacts").mkdir(exist_ok=True)
    rc = bench_main(["run", "--quick", "--out",
                     str(ROOT / "artifacts" / "bench_quick.json"), "--force"])
    if rc:
        raise RuntimeError(f"repro.bench run exited {rc}")


def _figure(module: str, **kw):
    def run(quick: bool):
        import importlib
        importlib.import_module(f"benchmarks.{module}").main(quick=quick, **kw)
    return run


def _roofline(quick: bool):
    from benchmarks import roofline_table
    roofline_table.main()


#: (name, title, fn(quick)) of the in-process entries, in output order
IN_PROCESS_ENTRIES = (
    ("bench", "bench: unified experiment API smoke (python -m repro.bench)",
     _bench),
    ("fig2", "fig2/5/6: hierarchy sweep x instruction mix (host measured)",
     _figure("fig2_hierarchy")),
    ("fig1", "fig1: addressing-mode / stream-count overhead",
     _figure("fig1_addressing")),
    ("fig3", "fig3: block-shape (registers-per-load) sweep",
     _figure("fig3_blockshape")),
    ("fig5", "fig5: R:W-ratio sweep, store-path attribution (rw family)",
     _figure("fig5_rw_ratio")),
    ("fig6", "fig6: instruction-stream classification "
             "(bandwidth- vs issue-bound)", _figure("fig6_istream")),
    ("fig7", "fig7: loaded-latency surface (bandwidth-latency curves)",
     _figure("fig7_loaded_latency")),
    ("table1", "table1: machine models (documented vs measured)",
     _figure("table1_machine")),
    ("roofline", "roofline: 40-cell dry-run table (reads artifacts/dryrun)",
     _roofline),
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: bench,fig1,fig2,fig3,fig4,fig5,fig6,"
                         "fig7,table1,collectives,roofline")
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    print("# Arm-membench (TPU port) benchmark suite")
    print("# name,us_per_call,derived")
    failed = []
    for name, mod in SUBPROCESS_ENTRIES:
        if want(name):
            print(f"\n## {name}: own process ({mod})")
            if not _subproc(mod, quick):
                failed.append(name)

    from repro.bench import compile_cache
    compile_cache.enable()
    for name, title, fn in IN_PROCESS_ENTRIES:
        if not want(name):
            continue
        print(f"\n## {title}")
        try:
            fn(quick)
        except Exception:       # report, and go on with the other entries
            traceback.print_exc()
            print(f"# {name} FAILED")
            failed.append(name)
    if failed:
        print(f"# failed entries: {','.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
