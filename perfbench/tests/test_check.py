"""The check that decides ``correct`` must be able to fail.

- The control: the reference, computed in bfloat16 (the precision below
  the configurations' float32), put in the program's place, fails at least
  one number of every cell, while the program passes every number.
- Planted faults: the rest of a run, with the timed path broken underneath,
  comes out not correct, once for each fault a cell can have (a step that
  returns its state unchanged, half of the data left out, an answer
  altered where it is produced).  One chip: no exchange to leave out.
- The pass loop's own kernel broken while the program's separate kernel
  entry stays sound: the check runs the kernel taken out of the timed
  program, so it sees the fault that the accumulator cannot.
"""
import json
import math
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT
from perfbench import calibrate, harness

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(tiny_bench, workload):
    rows = []
    summary = calibrate.readings(workload, [5, 2**31 + 3], [7, 8, 9], 0.2,
                                 bench_dir=tiny_bench, chips_required=False,
                                 emit=rows.append)
    limits = summary["limits"]
    for row in rows:
        over = [k for k, v in row["numbers"].items() if v > limits[k]]
        if row["impl"] == "program":
            assert not over and row["failed"] == 0, row
        else:
            assert over, row


def run_broken(bench, workload):
    return harness.run(workload, 11, 0.2, False, time.perf_counter(),
                       bench_dir=bench, chips_required=False)


def _ops():
    from repro.kernels.membench import ops
    return ops


def _im():
    from repro.core import instruction_mix
    return instruction_mix


def _membench():
    from repro.kernels.membench import membench
    return membench


def unchanged_state(mp, workload):
    """The pass loop runs no pass: it returns its initial state."""
    if workload == "membench_chase":
        mp.setattr(_im(), "k_chase", lambda perm, passes, unroll=1:
                   jnp.float32(0))
        return
    im = _im()
    mp.setattr(im, "_pass_loop", lambda step, passes, unroll, init: init)
    mp.setattr(im, "_rotating_pass_loop",
               lambda sweep, passes, unroll, state, out0:
               (state, (out0,) * unroll))


def half_left_out(mp, workload):
    """The kernel covers only the first half of the rows."""
    if workload == "membench_chase":
        real = _im().k_chase
        mp.setattr(_im(), "k_chase", lambda perm, passes, unroll=1:
                   real(perm[: perm.shape[0] // 2], passes, unroll))
        return
    ops = _ops()
    real = ops.membench_call

    def half(x, **kw):
        h = x.shape[0] // 2
        kw = {k: (v[:h] if k == "y" else v) for k, v in kw.items()}
        kw["block_rows"] = math.gcd(kw.get("block_rows", 128), h)
        out = real(x[:h], **kw)
        if getattr(out, "ndim", 0) == 2:
            return jnp.concatenate([out, jnp.zeros_like(out)])
        return out
    mp.setattr(ops, "membench_call", half)


def answer_altered(mp, workload):
    """The kernel itself writes a wrong answer: one element of every copy
    tile, or each tile's share of a sum."""
    if workload == "membench_chase":
        real = _im().k_chase
        mp.setattr(_im(), "k_chase", lambda perm, passes, unroll=1:
                   real(perm, passes, unroll) + 1)
        return
    mb = _membench()
    real_copy, real_body = mb._copy_kernel, mb._mix_body

    def copy(interleave, x_ref, o_ref):
        real_copy(interleave, x_ref, o_ref)
        o_ref[1:2, 3:4] = o_ref[1:2, 3:4] + 1.0
    mp.setattr(mb, "_copy_kernel", copy)
    mp.setattr(mb, "_mix_body", lambda *a, **kw: real_body(*a, **kw) * 1.001)


#: the cells whose timed paths the faults below know how to break; a cell
#: on another path brings its own faults
FAULT_CELLS = ["stream_copy", "membench_load_sum", "stream_runner",
               "membench_chase"]


@pytest.mark.parametrize("fault", [unchanged_state, half_left_out,
                                   answer_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", FAULT_CELLS)
def test_planted_fault_is_not_correct(tiny_bench, monkeypatch, workload,
                                      fault):
    jax.clear_caches()
    fault(monkeypatch, workload)
    try:
        res = run_broken(tiny_bench, workload)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]


def test_runner_bytes_altered_is_not_correct(tiny_bench, monkeypatch):
    """The Runner's declared bytes per call, altered where it is made."""
    from repro.bench import mixes
    real = mixes.MixDef.bytes_per_pass
    monkeypatch.setattr(mixes.MixDef, "bytes_per_pass",
                        lambda self, nbytes: real(self, nbytes) * 0.5)
    res = run_broken(tiny_bench, "stream_runner")
    assert not res["correct"]
    assert res["checks"]["bytes_rel_gap"]["value"] == pytest.approx(0.5)


def _row_dropped_copy(block_rows, interpret):
    """A Pallas copy that writes zeros in place of row 1 of every tile."""
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref):
        v = x_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        o_ref[...] = jnp.where(row == 1, jnp.zeros_like(v), v)

    @jax.jit
    def fn(x):
        tile = pl.BlockSpec((block_rows, x.shape[1]), lambda i: (i, 0))
        return pl.pallas_call(
            body, grid=(x.shape[0] // block_rows,), in_specs=[tile],
            out_specs=tile, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret)(x)
    return fn


@pytest.mark.parametrize("workload,prefix", [("stream_copy", ""),
                                             ("stream_runner", "copy_")])
def test_timed_kernel_alone_altered_is_not_correct(tiny_bench, monkeypatch,
                                                   workload, prefix):
    """The pass loop is built on a copy that drops a row of every tile,
    while ``ops.make_kernel`` stays sound.  The accumulator reads the first
    and the last element, which stay right; the kernel taken out of the
    timed program shows the dropped rows."""
    ops = _ops()
    real_timed = ops.make_timed_kernel

    def timed(mix, **kw):
        if mix != "copy":
            return real_timed(mix, **kw)
        with pytest.MonkeyPatch.context() as inner:
            inner.setattr(ops, "make_kernel", lambda mix, **k:
                          _row_dropped_copy(k["block_rows"], k["interpret"]))
            return real_timed(mix, **kw)

    jax.clear_caches()
    monkeypatch.setattr(ops, "make_timed_kernel", timed)
    try:
        res = run_broken(tiny_bench, workload)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    checks = res["checks"]
    assert checks[f"{prefix}acc_rel_gap"]["value"] <= \
        checks[f"{prefix}acc_rel_gap"]["limit"]
    assert checks[f"{prefix}kernel_rel_gap"]["value"] == 1.0
    assert not res["correct"]
