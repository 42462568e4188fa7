"""The readings the limits of a cell's check are set from: the program over
many seeds, and the control over a few, in one process.

    python3 perfbench/calibrate.py --workload stream_copy \
        --seeds 101-112 --control-seeds 201-203 --seconds 2

For each program seed the cell is set up, warmed and driven for
``--seconds`` as a run drives it, and its products are compared with the
reference.  For each control seed the reference itself, computed in
bfloat16 (the precision below the configurations' float32), takes the
program's place in the same comparison.  One JSON line per seed, then a
summary: per number, the largest program reading (the lower reading) and
the smallest control reading (the upper one).  Off a TPU it exits 2.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds(text: str) -> list[int]:
    """``1-12`` or ``5,9,40`` (or both, comma-separated)."""
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(workload: str, program_seeds, control_seeds, seconds: float,
             bench_dir=None, chips_required: bool = True, emit=print):
    """Emit one dict of readings per seed; return the summary: per
    number, the program's largest reading and the control's smallest."""
    from perfbench import harness
    bench_dir = bench_dir or harness.BENCH_DIR
    cell = harness.load_cell(workload, bench_dir)
    if chips_required:
        harness.require_chips(cell.chips)
    limits = cell.traffic["limits"]
    lower: dict[str, float] = {}
    upper: dict[str, float] = {}
    for impl, seed_list in (("program", program_seeds),
                            ("control", control_seeds)):
        for seed in seed_list:
            session = harness.setup_session(cell, seed)
            if impl == "program":
                session.warm()
                window = harness.drive(session, seconds)
                session.release()
                got = session.products(window.outs)
            else:
                got = session.reference(harness.CONTROL)
            numbers, failed = session.compare(
                got, session.reference(harness.REFERENCE), limits)
            emit({"workload": workload, "impl": impl, "seed": seed,
                  "numbers": numbers, "failed": failed})
            keep = lower if impl == "program" else upper
            pick = max if impl == "program" else min
            for k, v in numbers.items():
                keep[k] = pick(keep.get(k, v), v)
            del session, got
            gc.collect()
    return {"workload": workload, "lower": lower, "upper": upper,
            "limits": limits}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from perfbench import harness
    harness.start_process()
    t0 = time.perf_counter()
    try:
        summary = readings(args.workload, args.seeds, args.control_seeds,
                           args.seconds,
                           emit=lambda d: print(json.dumps(d), flush=True))
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    summary["seconds"] = time.perf_counter() - t0
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
