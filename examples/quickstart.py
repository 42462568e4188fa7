"""Quickstart: measure this machine's memory hierarchy and print the
per-level table.

    PYTHONPATH=src python examples/quickstart.py

The measurement is one declarative BenchSpec executed by the repro.bench
Runner — the same API behind ``python -m repro.bench run`` (see
src/repro/bench/README.md for the knob -> paper mapping).
"""
from repro.bench import BenchSpec, Runner
from repro.core import analysis
from repro.core.machine_model import detect_host


def main():
    print("== membench: hierarchy sweep (quick) ==")
    spec = BenchSpec(mixes=("load_sum", "fma_8"),
                     sizes=(32 * 2**10, 1 * 2**20, 16 * 2**20),
                     reps=4, warmup=2, target_bytes=3e7)
    res = Runner().run(spec)
    model = analysis.build_machine_model(res, detect_host())
    print(analysis.format_table(model.level_bw, model.mix_penalty))


if __name__ == "__main__":
    main()
