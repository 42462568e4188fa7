"""Fig 7 — loaded-latency surface (Mess-style bandwidth–latency curves).

The ``latency_chase`` probe measures per-step dependent-load latency; the
spec's ``load`` axis co-schedules bandwidth-generator streams next to it
(``bench/README.md``, "Loaded-latency surfaces").  Sweeping load at each
working-set size traces the memory system's bandwidth–latency curve: a flat
idle plateau, then latency taking off as the generators approach the
level's sustainable bandwidth.  The per-level knee fit
(``characterize.loaded.fit_loaded``) summarizes each curve into
(idle latency, knee load, knee generator GB/s) — the numbers a Mess-style
memory model feeds into a simulator.

This script is a thin declaration over
``repro.characterize.loaded.loaded_latency_sweep`` — the (sizes x loads)
grid is the only thing decided here.
"""
from __future__ import annotations

import argparse
from pathlib import Path

from benchmarks.common import emit
from repro.characterize.loaded import fit_loaded, loaded_latency_sweep

ART = Path(__file__).resolve().parents[1] / "artifacts"


def grid(quick: bool = False, smoke: bool = False) -> dict:
    if smoke:
        return dict(sizes=(128 * 2**10,), loads=(0, 1, 2), reps=3)
    if quick:
        return dict(sizes=(128 * 2**10, 4 * 2**20), loads=(0, 1, 2, 4),
                    reps=3)
    return dict(sizes=(128 * 2**10, 4 * 2**20, 64 * 2**20),
                loads=(0, 1, 2, 4, 8), reps=5)


def main(quick: bool = False, smoke: bool = False, out: str | None = None,
         backend: str = "xla"):
    kw = grid(quick, smoke)
    res = loaded_latency_sweep(kw.pop("sizes"), kw.pop("loads"),
                               backend=backend, **kw)
    fit = fit_loaded(res)
    if fit:
        res.meta["loaded_latency"]["fit"] = fit

    for p in sorted(res.points, key=lambda p: (p.nbytes, p.load)):
        emit(f"fig7/{p.backend}/{p.nbytes}B/load{p.load}", p.mean_s * 1e6,
             f"{p.latency_ns:.2f}ns;{p.gen_gbps:.2f}GB/s-generated")
    for name, knee in ((fit or {}).get("levels") or {}).items():
        print(f"# {name}: idle {knee['idle_latency_ns']:.1f} ns, knee at "
              f"load={knee['knee_load']} ({knee['knee_gen_gbps']:.2f} GB/s), "
              f"max {knee['max_latency_ns']:.1f} ns")

    if out:
        res.to_json(out)
        print(f"# saved {len(res.points)} points "
              f"(schema v{res.schema_version}) -> {out}")
    elif not smoke:
        ART.mkdir(exist_ok=True)
        res.to_json(ART / "fig7_loaded_latency.json")
    return res


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale grid — the CI smoke gate")
    ap.add_argument("--out", default=None,
                    help="write the schema-v5 result JSON here")
    ap.add_argument("--backend", default="xla", help="xla | pallas")
    main(**vars(ap.parse_args()))
