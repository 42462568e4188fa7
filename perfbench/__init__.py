"""The chip benchmark of this repository: one cell of ``BENCHMARK.json`` per
run of ``python3 perfbench/run.py``.  Everything that measures (traffic,
accounting, peaks, trace reduction, references) lives here, apart from the
program under test."""
