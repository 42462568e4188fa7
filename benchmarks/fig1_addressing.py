"""Fig 1 — addressing-mode overhead (post-increment vs manual multi-pointer).

Host analogue: contiguous single-stream reduction vs S interleaved strided
streams (stride = S x lane row).  On Arm the post-increment costs extra AGU
uOPs; on a cached host CPU the strided walk defeats the linear prefetcher the
same way — both are 'the address pattern, not the data volume, sets the rate'.

The strided kernel lives in core.instruction_mix (k_strided_sum); this script
is just the BenchSpec declaration (streams = C3 knob) plus the figure's emit
lines.  Relative throughput anchors on the streams=1 point per size via
BenchResult.baseline_relative — an explicit presence check, so a 0.0 first
measurement can no longer silently re-anchor the baseline.
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit
from repro.bench import BenchSpec, Runner
from repro.core.buffers import hierarchy_grid

STREAM_COUNTS = (1, 2, 4, 8)


def main(quick: bool = False, out: str | None = None):
    # shared grid constructor (core.buffers): the quick ladder, or a sparse
    # log grid across the full hierarchy span — per-script size lists are gone
    sizes = hierarchy_grid(quick=True) if quick else \
        hierarchy_grid(per_decade=2)
    base = BenchSpec(mixes=("load_sum",), sizes=sizes,
                     reps=5 if quick else 10, warmup=2,
                     target_bytes=5e7 if quick else 2e8)

    res = Runner().run_many(
        [base.replace(streams=s) for s in STREAM_COUNTS])

    rel = dict(res.baseline_relative(group_key=lambda p: p.nbytes,
                                     is_baseline=lambda p: p.streams == 1))
    for p in sorted(res.points, key=lambda p: (p.nbytes, p.streams)):
        emit(f"fig1/streams{p.streams}/{p.nbytes}B", p.mean_s * 1e6,
             f"{p.gbps:.2f}GB/s;rel={rel[p]:.3f}")
    if out:
        res.to_json(out)
        print(f"# saved {len(res.points)} points "
              f"(schema v{res.schema_version}) -> {out}")
    return res


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None, help="write result JSON here")
    main(**vars(ap.parse_args()))
