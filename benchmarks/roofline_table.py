"""§Roofline table generator — renders artifacts/dryrun/*.json as markdown.

One row per (arch x shape x mesh): the three roofline terms, dominant
bottleneck, MODEL_FLOPS/HLO_FLOPs usefulness ratio, HBM fit, and a one-line
'what would move the dominant term' note.
"""
from __future__ import annotations

import argparse
import glob
import json
from pathlib import Path

from benchmarks.common import emit

ART = Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
PROBE = Path(__file__).resolve().parents[1] / "artifacts" / "probe"

NOTES = {
    ("compute",): "raise arithmetic intensity: larger kv_block / fused kernels",
    ("memory",): "cut bytes: fp8/int8 weights, fused norms, better remat policy",
    ("collective",): "cut wire bytes: bf16 psum, a2a dispatch, overlap via LHS",
}


def load(variant: str = "baseline"):
    """Prefer probe records (correct loop accounting) for the roofline terms;
    merge the rolled dry-run's memory_analysis fields (fit proof)."""
    rows = []
    for f in sorted(glob.glob(str(ART / f"*__{variant}.json"))):
        d = json.loads(Path(f).read_text())
        p = PROBE / Path(f).name
        if p.exists():
            pd = json.loads(p.read_text())
            if pd.get("status") == "ok":
                keep = {k: d.get(k) for k in ("peak_device_bytes", "fits_hbm",
                                              "arg_bytes", "temp_bytes")}
                d = {**d, **pd, **{k: v for k, v in keep.items()
                                   if v is not None}}
        rows.append(d)
    return rows


def render(rows, show_skips=False):
    hdr = ("| arch | shape | mesh | t_comp (s) | t_mem (s) | t_coll (s) | "
           "dominant | useful_flops | peak GiB | fits |")
    sep = "|" + "---|" * 10
    out = [hdr, sep]
    for r in rows:
        mesh = "2x16x16" if r.get("multi_pod") else "16x16"
        if r["status"] == "skipped":
            if show_skips:
                out.append(f"| {r['arch']} | {r['shape']} | {mesh} | - | - | - "
                           f"| skipped | - | - | - |")
            continue
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | {mesh} | ERROR: "
                       f"{r['error'][:40]} | | | | | | |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {mesh} "
            f"| {r['t_compute_s']:.4f} | {r['t_memory_s']:.4f} "
            f"| {r['t_collective_s']:.4f} | **{r['dominant']}** "
            f"| {r.get('useful_flop_ratio', 0):.2f} "
            f"| {r['peak_device_bytes']/2**30:.2f} "
            f"| {'Y' if r.get('fits_hbm') else 'N'} |")
    return "\n".join(out)


def main(variant: str = "baseline", quick: bool = False):
    rows = load(variant)
    print(render(rows, show_skips=True))
    ok = [r for r in rows if r["status"] == "ok"]
    emit("roofline/cells", 0.0,
         f"{len(ok)} compiled cells, variant={variant}")


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--quick", action="store_true")
    main(**{k: v for k, v in vars(ap.parse_args()).items()})
