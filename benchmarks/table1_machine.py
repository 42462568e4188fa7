"""Table 1 — system specification table: documented peaks (paper systems +
TPU v5e target) vs what this harness measures on the host."""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.common import emit
from repro.core.machine_model import (A64FX, ALTRA, THUNDERX2, TPU_V5E,
                                      detect_host)

ART = Path(__file__).resolve().parents[1] / "artifacts"


def show(hw, measured=None):
    print(f"\n## {hw.name}")
    if hw.frequency_hz:
        print(f"  frequency: {hw.frequency_hz/1e9:.1f} GHz")
    if hw.peak_flops:
        print(f"  peak compute: {hw.peak_flops/1e12:.1f} TFLOP/s")
    for lvl in hw.levels:
        size = f"{lvl.size_bytes/2**10:.0f} KiB" if lvl.size_bytes and \
            lvl.size_bytes < 2**20 else \
            (f"{lvl.size_bytes/2**20:.0f} MiB" if lvl.size_bytes else "-")
        bw = f"{lvl.read_bw/1e9:.1f} GB/s" if lvl.read_bw else "undocumented"
        meas = ""
        if measured and lvl.name in measured:
            best = max(measured[lvl.name].values())
            meas = f"  measured(best mix): {best:.1f} GB/s"
        print(f"  {lvl.name:6s} size={size:>9s}  documented={bw}{meas}")
    if hw.link_bw:
        print(f"  interconnect: {hw.link_bw/1e9:.0f} GB/s per link")
    if hw.notes:
        print(f"  notes: {hw.notes}")


def main(quick: bool = False):
    measured = None
    mm_path = ART / "machine_model_host.json"
    if mm_path.exists():
        measured = json.loads(mm_path.read_text()).get("level_bw")
    for hw in (TPU_V5E, A64FX, ALTRA, THUNDERX2):
        show(hw)
    show(detect_host(), measured)
    emit("table1/systems", 0.0, "5 systems (3 paper + v5e target + host)")


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
