"""Run one cell of BENCHMARK.json once, on the chip, and print its result.

    python3 perfbench/run.py --workload stream_copy --seed 7 --seconds 20 \
        --trace 0

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
start of the window.  The last line of stdout is one JSON object; the last
lines of stderr give each number the check compared, beside its limit.
Off a TPU, or short of the chips the cell asks for, it prints no result and
exits 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from perfbench import harness
    harness.start_process()
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
