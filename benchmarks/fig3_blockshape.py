"""Fig 3 — registers-per-load-instruction (LD1D/LD2D/LD4D) => rows-per-block.

Host analogue: the reduction walks the buffer in blocks of R rows per step; R
is the LD1/2/4 'registers per instruction' analogue (the blocked kernel lives
in core.instruction_mix).  The script declares one BenchSpec per block shape
(block_rows = C4 knob) for the measured host table, then runs the *same*
specs through the Pallas backend in interpret mode and verifies the kernels
against the jnp oracle — one mix registry, two backends.
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit
from repro.audit import validate_ecm
from repro.bench import BenchSpec, BenchSpecError, Runner
from repro.characterize.fit import FittedMachineModel, LevelFit
from repro.core import buffers
from repro.istream import ProfileCache, analyze_case, fit_issue_rate


def main(quick: bool = False):
    nbytes = 4 * 2**20 if quick else 16 * 2**20
    rows_list = (8, 16, 32, 128) if quick else (8, 16, 32, 64, 128, 256, 512)
    base = BenchSpec(mixes=("load_sum",), sizes=(nbytes,),
                     reps=5 if quick else 10, warmup=2,
                     target_bytes=5e7 if quick else 2e8)

    runner = Runner()
    best = (None, 0.0)
    pairs = []          # (BenchPoint, InstructionProfile) across the sweep
    cache = ProfileCache()
    shape = buffers.working_set_shape(nbytes)
    for rows in rows_list:
        try:
            spec = base.replace(block_rows=rows)
            res = runner.run(spec)
        except BenchSpecError:     # rows not dividing this working set
            continue
        p = res.points[0]
        emit(f"fig3/rows{rows}/{p.nbytes}B", p.mean_s * 1e6,
             f"{p.gbps:.2f}GB/s")
        try:
            pairs.append((p, analyze_case(spec, "load_sum", shape, "float32",
                                          p.passes, runner=runner,
                                          cache=cache)))
        except Exception as e:     # prediction is a bonus, never blocks fig3
            print(f"# ecm: profile extraction failed at rows={rows}: {e}")
        if p.gbps > best[1]:
            best = (rows, p.gbps)
    print(f"# best block rows on this host: {best[0]} ({best[1]:.1f} GB/s)")

    # ECM predicted-vs-measured over the very sweep just timed: the sweep
    # self-calibrates a one-level model (best sustained transfer rate +
    # fitted issue rate) and the predictor must then reproduce each point's
    # time from its compiled profile alone.  The transfer term is calibrated
    # in OBSERVED compiled bytes/s, not declared GB/s — the blocked host
    # reduction materializes per-partial sums (the audit's documented
    # xla/load_sum blocked waiver), so declared-byte bandwidth would
    # understate what the memory path actually sustained.
    if pairs:
        def _obs_bw(p, prof):
            per_pass = (prof.per_iter["loads"] + prof.per_iter["stores"]) \
                / max(prof.unroll, 1) * 4
            return per_pass * p.passes / p.mean_s
        model = FittedMachineModel(
            name="fig3-self-calibrated",
            levels=(LevelFit(
                name="mem", capacity_bytes=None, capacity_ci=None,
                bandwidth={"load_sum": {
                    "gbps": max(_obs_bw(p, pr) for p, pr in pairs) / 1e9,
                    "ci": None, "n": len(pairs)}}),),
            issue={"rate_elems_per_s": fit_issue_rate(pairs)})
        val = validate_ecm(pairs, model)
        for r in val["rows"]:
            emit(f"fig3/ecm/rows{r['knobs']['block_rows']}",
                 r["predicted_s"] * 1e6,
                 f"meas={r['measured_s'] * 1e6:.1f}us "
                 f"err={r['rel_err'] * 100:+.1f}% {r['bound']}-bound")
        print(f"# ecm predicted-vs-measured over {val['n']} block shapes: "
              f"median |rel err| {val['median_abs_rel_err'] * 100:.1f}%, "
              f"max {val['max_abs_rel_err'] * 100:.1f}%")

    # Pallas path: same spec shape on the pallas backend, numerics vs oracle
    # (interpret mode validates structure, not time)
    from repro.kernels.membench import ops as mb_ops
    from repro.kernels.membench.ref import reference
    small = base.replace(sizes=(64 * 2**10,), backend="pallas", passes=1,
                         reps=2, warmup=1)
    xs = buffers.working_set(64 * 2**10)
    for rows in (8, 32, 128):
        runner.run(small.replace(block_rows=rows))      # runs through Runner
        out = float(mb_ops.make_kernel("load_sum", block_rows=rows)(xs))
        ref = float(reference("load_sum", xs))
        assert abs(out - ref) < 1e-2, (rows, out, ref)
    print("# pallas block-shape kernels verified vs oracle (interpret mode)")


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    main(**vars(ap.parse_args()))
