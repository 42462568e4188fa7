"""The R:W-ratio mix family (store-path attribution): property-based
accounting parity across backends, numerical-correctness oracles for EVERY
registered mix (a mis-ordered load/store fails loudly instead of silently
benchmarking the wrong traffic), the ``summarize(levels=...)`` view, the
golden-file schema round-trips, and deterministic mix listing."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.bench import (BenchResult, BenchSpec, BenchSpecError, MAX_RW,
                         RW_RATIOS, Runner, get_backend, get_mix, mix_names,
                         registry, rw_name, rw_ratio)

DATA = Path(__file__).parent / "data"
TINY = dict(sizes=(16 * 2**10,), reps=2, warmup=1, passes=1)

#: shared across property examples so repeated (R, W) draws hit the
#: compiled-case cache instead of re-tracing
RUNNER = Runner()


# ---------------------------------------------------------------------------
# the family: one shared accounting formula, open-ended like fma
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=MAX_RW),
       st.integers(min_value=1, max_value=MAX_RW))
def test_rw_accounting_formula(reads, writes):
    """bytes = (R+W) * nbytes, flops = 2(R-1) * n — derived from (R, W) by
    the ONE shared formula, for any family member."""
    m = rw_ratio(reads, writes)
    nbytes, n = 4096, 1024
    assert m.bytes_per_pass(nbytes) == (reads + writes) * nbytes
    assert m.flops_per_pass(n) == 2 * (reads - 1) * n
    assert m.rw == (reads, writes)
    assert get_mix(rw_name(reads, writes)) == m        # open-ended lookup


def test_rw_family_generalizes_copy_and_triad():
    """The formula reproduces the fixed mixes it generalizes."""
    nbytes, n = 65536, 16384
    assert (rw_ratio(1, 1).bytes_per_pass(nbytes)
            == get_mix("copy").bytes_per_pass(nbytes))
    assert (rw_ratio(2, 1).bytes_per_pass(nbytes)
            == get_mix("triad").bytes_per_pass(nbytes))
    assert (rw_ratio(2, 1).flops_per_pass(n)
            == get_mix("triad").flops_per_pass(n))


def test_rw_registry_and_rejects():
    reg = registry()
    for r, w in RW_RATIOS:
        assert rw_name(r, w) in reg
    assert "rw_5to2" not in reg            # canonical ladder only
    assert get_mix("rw_5to2").rw == (5, 2)  # ...but resolvable, like fma_3
    for bad in ("rw_0to1", "rw_1to0", f"rw_{MAX_RW + 1}to1", "rw_zzto1",
                "rw_1to", "rw_", "rw_01to1", "rw_1to02"):
        with pytest.raises(KeyError):
            get_mix(bad)
    with pytest.raises(ValueError):
        rw_ratio(0, 1)
    with pytest.raises(ValueError):
        rw_ratio(1, MAX_RW + 1)


def test_rw_threads_spec_validation():
    """The family flows through BenchSpec validation on every backend; bad
    family parameters surface as BenchSpecError before any timing."""
    for backend in ("xla", "pallas", "sharded"):
        s = BenchSpec(mixes=("rw_3to1",), backend=backend, **TINY)
        assert s.mixes == ("rw_3to1",)
    with pytest.raises(BenchSpecError):
        BenchSpec(mixes=("rw_0to1",), **TINY)
    with pytest.raises(BenchSpecError):
        BenchSpec(mixes=(f"rw_{MAX_RW + 1}to1",), **TINY)
    with pytest.raises(BenchSpecError):    # oracle knob rules still apply
        Runner().run(BenchSpec(mixes=("rw_2to1",), streams=2, **TINY))


# ---------------------------------------------------------------------------
# property-based cross-backend parity (the paper's oracle-vs-embodiment check)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=1, max_value=MAX_RW),
       st.integers(min_value=1, max_value=MAX_RW))
def test_rw_parity_xla_vs_pallas_and_recorded_traffic(reads, writes):
    """For random (R, W), the xla and pallas embodiments report identical
    bytes/flops per call, and the per-point traffic the Runner records at
    devices=1 is exactly formula x passes (registry-derived accounting — the
    numpy-oracle tests below are the kernel-level check that the buffers
    really move that traffic)."""
    name = rw_name(reads, writes)
    acct = {}
    for backend in ("xla", "pallas"):
        spec = BenchSpec(mixes=(name,), backend=backend, **TINY)
        (pt,) = RUNNER.run(spec).points
        assert pt.gbps > 0 and pt.devices == 1, (name, backend)
        assert pt.bytes_per_call == (reads + writes) * pt.nbytes * pt.passes
        assert pt.flops_per_call == (2 * (reads - 1) * (pt.nbytes // 4)
                                     * pt.passes)
        acct[backend] = (pt.bytes_per_call, pt.flops_per_call)
    assert acct["xla"] == acct["pallas"], (name, acct)


def test_rw_parity_sharded_inherits_xla_accounting():
    """The sharded backend runs the xla oracle per shard (PR 2), so the
    family's accounting carries over by construction at devices=1."""
    name = rw_name(2, 1)
    acct = {}
    for backend in ("xla", "sharded"):
        spec = BenchSpec(mixes=(name,), backend=backend, **TINY)
        (pt,) = RUNNER.run(spec).points
        acct[backend] = (pt.bytes_per_call, pt.flops_per_call)
    assert acct["xla"] == acct["sharded"]


# ---------------------------------------------------------------------------
# numerical-correctness oracles: EVERY registered mix vs a numpy reference
# ---------------------------------------------------------------------------

PASSES = 3


def _buffer():
    rng = np.random.default_rng(0)
    x = rng.uniform(0.5, 1.5, size=(32, 128)).astype(np.float32)
    return x.astype(np.float64), jnp.asarray(x)


def _fma_chain(x64, depth):
    v = x64.copy()
    for _ in range(depth):
        v = v * np.float64(np.float32(1.0000001)) + 1e-9
    return v


def _rw_combined(x64, reads):
    from repro.bench.mixes import RW_COMBINE_COEF
    factor = 1.0 + RW_COMBINE_COEF * sum(0.5 ** r for r in range(1, reads))
    return x64 * factor


def _xla_reference(name, x64, p):
    """What the xla oracle kernels compute (perturbation terms are ~1e-30
    relative and vanish in float32)."""
    m = get_mix(name)
    if name == "load_sum":
        return p * x64.sum()
    if name == "copy":
        return p * x64[0, 0] + x64[-1, -1]
    if name == "triad":
        return p * 1.75 * x64[0, 0] + 1.75 * x64[-1, -1]
    if name == "mxu":
        return p * x64[0, 0]
    if m.chase:
        # a full permutation-cycle walk always returns to its start index 0,
        # so the accumulated final-position fold is exactly zero — any other
        # value means the cycle structure (or the walk) is broken
        return 0.0
    if m.fma_depth:
        return p * _fma_chain(x64, m.fma_depth).sum()
    if m.rw is not None:
        v = _rw_combined(x64, m.rw[0])
        return p * v[0, 0] + m.rw[1] * v[-1, -1]
    raise KeyError(name)


def _pallas_reference(name, x64, p, block_rows):
    """What the pallas timed kernels accumulate (block-accumulator grid for
    the load family; array outputs are loop-carried — folded in at their
    first element each pass, plus the final carry's last element, the same
    consumption convention as the xla ``k_copy``/``k_rw`` oracles)."""
    m = get_mix(name)
    lead = x64[::block_rows, 0].sum()          # one lane per visited block
    if name == "load_only":
        return p * lead
    if name == "load_sum":
        return p * x64.sum()
    if name == "copy":
        return p * x64[0, 0] + x64[-1, -1]
    if name == "triad":
        return p * 1.75 * x64[0, 0] + 1.75 * x64[-1, -1]
    if name == "mxu":
        return p * lead                        # blk @ eye accumulates [0, 0]
    if m.chase:
        return 0.0                             # tile-local cycles end at 0
    if m.fma_depth:
        return p * _fma_chain(x64, m.fma_depth).sum()
    if m.rw is not None:
        v = _rw_combined(x64, m.rw[0])
        return p * m.rw[1] * v[0, 0] + m.rw[1] * v[-1, -1]
    raise KeyError(name)


@pytest.mark.parametrize("name", mix_names("xla"))
def test_numeric_parity_xla(name):
    """Each xla kernel's output matches its numpy model — a mis-ordered
    load/store in a future kernel edit fails here, not in a benchmark."""
    x64, x = _buffer()
    spec = BenchSpec(mixes=(name,), backend="xla", sizes=(16 * 2**10,),
                     reps=2, warmup=1, passes=PASSES)
    fn = get_backend("xla").build(spec, get_mix(name), x, PASSES)
    got = float(fn())
    want = _xla_reference(name, x64, PASSES)
    assert got == pytest.approx(want, rel=1e-4), (name, got, want)


@pytest.mark.parametrize("name", mix_names("pallas"))
def test_numeric_parity_pallas(name):
    x64, x = _buffer()
    spec = BenchSpec(mixes=(name,), backend="pallas", block_rows=8,
                     sizes=(16 * 2**10,), reps=2, warmup=1, passes=PASSES)
    fn = get_backend("pallas").build(spec, get_mix(name), x, PASSES)
    got = float(fn())
    want = _pallas_reference(name, x64, PASSES, block_rows=8)
    assert got == pytest.approx(want, rel=1e-4), (name, got, want)


def test_numeric_parity_covers_every_registered_mix():
    """Nothing in the registry escapes the oracle check: every registered mix
    is runnable (and therefore checked above) on xla or pallas."""
    assert set(mix_names()) == set(mix_names("xla")) | set(mix_names("pallas"))


# ---------------------------------------------------------------------------
# summarize(levels=...) — per-level attribution as a result view
# ---------------------------------------------------------------------------

def _mk_result(points):
    from repro.bench.result import BenchPoint
    pts = []
    for mix, nbytes, gbps in points:
        pts.append(BenchPoint(
            nbytes=nbytes, mix=mix, dtype="float32", backend="xla", passes=1,
            streams=1, block_rows=None, reps=1, bytes_per_call=float(nbytes),
            flops_per_call=0.0, mean_s=1e-3, std_s=0.0, min_s=1e-3,
            gbps=gbps, gflops=0.0))
    return BenchResult(points=pts)


def test_summarize_bands_means_and_rel():
    res = _mk_result([("load_sum", 16 * 2**10, 40.0),
                      ("load_sum", 16 * 2**10, 60.0),   # averaged: 50
                      ("copy", 16 * 2**10, 25.0),
                      ("load_sum", 8 * 2**20, 10.0),
                      ("copy", 8 * 2**20, 5.0)])
    levels = (("L1", 64 * 2**10), ("DRAM", None))
    s = res.summarize(levels=levels)
    assert list(s) == ["L1", "DRAM"]
    assert s["L1"]["load_sum"]["gbps"] == pytest.approx(50.0)
    assert s["L1"]["load_sum"]["n"] == 2
    assert s["L1"]["load_sum"]["rel"] == pytest.approx(1.0)
    assert s["L1"]["copy"]["rel"] == pytest.approx(0.5)
    assert s["L1"]["copy"]["band"] == (4096.0, 32768.0)
    assert s["DRAM"]["load_sum"]["gbps"] == pytest.approx(10.0)
    assert s["DRAM"]["copy"]["rel"] == pytest.approx(0.5)
    # unbounded band edge is None (JSON-serializable), NOT float("inf"):
    # a summary stashed into meta must survive to_json as spec-compliant JSON
    assert s["DRAM"]["copy"]["band"][1] is None


def test_summarize_accepts_memlevel_objects_and_default_band():
    from repro.core.machine_model import MemLevel
    res = _mk_result([("copy", 16 * 2**10, 8.0)])
    s = res.summarize(levels=(MemLevel("L1d", 64 * 2**10, None),
                              MemLevel("DRAM", None, None)))
    assert s == res.summarize(levels=(("L1d", 64 * 2**10), ("DRAM", None)))
    # levels=None: one unbounded band
    assert res.summarize()["all"]["copy"]["gbps"] == pytest.approx(8.0)
    # empty bands are omitted, not emitted as {}
    tiny = res.summarize(levels=(("L0", 8 * 2**10),))
    assert tiny == {}


def test_summarize_matches_legacy_attribute_levels():
    """core.analysis.attribute_levels is now a thin view over summarize —
    both derive the identical table."""
    from repro.core import analysis
    from repro.core.machine_model import HardwareSpec, MemLevel
    hw = HardwareSpec(name="t", peak_flops=0.0,
                      levels=(MemLevel("L1", 64 * 2**10, None),
                              MemLevel("DRAM", None, None)))
    res = _mk_result([("load_sum", 16 * 2**10, 40.0),
                      ("copy", 16 * 2**10, 20.0),
                      ("load_sum", 8 * 2**20, 10.0)])
    table = analysis.attribute_levels(res, hw)
    s = res.summarize(levels=hw.levels)
    assert table == {lvl: {m: c["gbps"] for m, c in mixes.items()}
                     for lvl, mixes in s.items()}


# ---------------------------------------------------------------------------
# golden-file round-trips: the back-compat promise, locked in fixtures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fname,ver,devices", [
    ("result_v1.json", 1, 1),     # v1: no devices field -> default 1
    ("result_v2.json", 2, 2),
    ("result_v3.json", 3, 4),     # v3: gathered 2-process distributed run
])
def test_golden_result_roundtrip(fname, ver, devices):
    path = DATA / fname
    res = BenchResult.from_json(path)
    assert res.schema_version == ver
    assert res.points and all(p.devices == devices for p in res.points)
    # summarize works on both schema generations
    s = res.summarize(levels=(("L1", 64 * 2**10), ("DRAM", None)))
    assert set(s) == {"L1", "DRAM"}
    for mixes in s.values():
        assert all(c["gbps"] > 0 for c in mixes.values())
    # re-serialization preserves schema_version and round-trips the points
    d = res.to_dict()
    assert d["schema_version"] == ver
    back = BenchResult.from_dict(json.loads(json.dumps(d)))
    assert back.points == res.points
    assert back.spec == res.spec and back.schema_version == ver


def test_golden_v2_points_carry_rw_accounting():
    res = BenchResult.from_json(DATA / "result_v2.json")
    for p in res.points:
        m = get_mix(p.mix)
        assert m.rw is not None
        assert p.bytes_per_call == m.bytes_per_pass(p.nbytes) * p.passes
        assert p.flops_per_call == m.flops_per_pass(p.nbytes // 4) * p.passes


# ---------------------------------------------------------------------------
# deterministic listing + CLI surface
# ---------------------------------------------------------------------------

def test_mix_names_deterministic_order():
    """Families list by their parameter (fma by depth, rw by R:W ratio, then
    name), everything else alphabetically — independent of registration
    order, so CLI list-mixes output is stable."""
    names = mix_names()
    assert names == ["copy", "fma_1", "fma_2", "fma_4", "fma_8", "fma_16",
                     "fma_32", "fma_64", "latency_chase", "load_only",
                     "load_sum", "mxu", "rw_1to2", "rw_1to1", "rw_2to1",
                     "rw_3to1", "rw_4to1", "triad"]
    assert mix_names("pallas") == names
    assert "load_only" not in mix_names("xla")
    assert mix_names("sharded") == mix_names("xla")


def test_cli_run_mix_flag_and_list_mixes_family(tmp_path, capsys):
    from repro.bench import cli
    out = tmp_path / "rw.json"
    rc = cli.main(["run", "--mix", "rw_3to1", "--sizes", "16K", "--reps", "2",
                   "--backend", "xla", "--out", str(out)])
    assert rc == 0
    d = json.loads(out.read_text())
    assert [p["mix"] for p in d["points"]] == ["rw_3to1"]
    assert d["points"][0]["bytes_per_call"] == \
        4 * d["points"][0]["nbytes"] * d["points"][0]["passes"]
    assert cli.main(["list-mixes"]) == 0
    cap = capsys.readouterr()
    # the family is listed ratio-ordered, with the open-endedness noted
    assert cap.out.index("rw_1to2") < cap.out.index("rw_1to1") \
        < cap.out.index("rw_2to1") < cap.out.index("rw_4to1")
    assert "rw_RtoW" in cap.out


def test_cli_compare_rw_accounting_agrees(capsys):
    from repro.bench import cli
    rc = cli.main(["compare", "--mix", "rw_2to1", "--sizes", "16K",
                   "--reps", "2"])
    assert rc == 0                      # nonzero would mean a mismatch
    cap = capsys.readouterr()
    assert "rw_2to1" in cap.out and "mismatch" not in cap.out


def test_fig5_quick_sizes_sit_inside_attribution_bands():
    """Quick-mode sizes derive from the detected hierarchy so every point
    attributes to exactly one level — fixed power-of-two sizes would land ON
    band edges (a 32K buffer is outside a 32K L1's (4K, 16K) band)."""
    from benchmarks.fig5_rw_ratio import quick_sizes
    from repro.bench.result import level_band
    from repro.core.machine_model import MemLevel
    levels = (MemLevel("L1", 32 * 2**10, None),
              MemLevel("L2", 256 * 2**10, None),
              MemLevel("L3", 8 * 2**20, None),
              MemLevel("DRAM", None, None))
    sizes = quick_sizes(levels)
    assert len(sizes) == len(levels)
    prev = 2 * 2**10
    for lvl, size in zip(levels, sizes):
        lo, hi = level_band(lvl.size_bytes, prev)
        assert lo < size < hi, (lvl.name, size, lo, hi)
        if lvl.size_bytes:
            prev = lvl.size_bytes
    # cacheless topology still yields a multi-size sweep
    assert len(quick_sizes((MemLevel("DRAM", None, None),))) >= 3
    # a big last-level cache must not push the DRAM size below its band
    # floor (the capped-size regression): 2x the floor is always in-band
    big = (MemLevel("L3", 64 * 2**20, None), MemLevel("DRAM", None, None))
    dram_size = quick_sizes(big)[-1]
    dram_lo, _ = level_band(None, big[0].size_bytes)
    assert dram_size > dram_lo


def test_fig5_smoke_emits_ratio_table(capsys):
    from benchmarks import fig5_rw_ratio
    summary = fig5_rw_ratio.main(smoke=True)
    cap = capsys.readouterr()
    assert "fig5/rw_2to1/" in cap.out
    assert "R:W" in cap.out and "1:1" in cap.out and "3:1" in cap.out
    assert set(summary) == {"all"}
    assert {"rw_1to1", "rw_2to1", "rw_3to1"} <= set(summary["all"])
