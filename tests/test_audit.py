"""repro.audit — the static accounting verifier (declared mix formulas vs
element-weighted compiled-HLO traffic) and the ECM-style analytic predictor.

Covers: the registry-wide base-knob audit as a pytest-collected lint (every
mix x backend must reconcile, un-waived), corrupted-formula detection (exit
2 naming the mix/backend/knob triple, at both library and CLI level), the
deviceless golden-fixture path, the pinned DCE regression (pre-fix pallas
copy lowering whose timed loop was empty), the UnknownOpcodeWarning bucket,
property-based audits over random rw_RtoW pairs, ECM bound classification /
validation, and the autotune ECM prefilter selecting the same winner as the
exhaustive timed sweep."""
import dataclasses
import json
import math
import types
from pathlib import Path

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.audit import (EXIT_OK, EXIT_VIOLATION, audit_case, audit_goldens,
                         audit_hlo, audit_registry, ecm_filter_rows,
                         ecm_predict, expected_counts, lint_mix,
                         predict_block_rows, random_rw_pairs, validate_ecm,
                         waiver_reason, write_goldens)
from repro.audit import verify as audit_verify
from repro.bench.cli import main as bench_main
from repro.bench.mixes import get_mix, mix_names, rw_name
from repro.bench.spec import BenchSpec
from repro.characterize.fit import FittedMachineModel, LevelFit
from repro.istream import ProfileCache
from repro.istream.extract import UnknownOpcodeWarning, extract_profile

HLO_DIR = Path(__file__).parent / "data" / "hlo"
SHAPE = (64, 128)
NBYTES = 64 * 128 * 4
PASSES = 4
BACKENDS = ("xla", "pallas")

#: one compiled-case cache for the whole module — repeated audits of the
#: same (mix, backend, knobs) coordinate re-lower nothing
CACHE = ProfileCache()


@pytest.fixture(scope="module")
def base_report():
    """Full registry x both backends at base knobs — the audit lint."""
    return audit_registry(backends=BACKENDS, knob_grid=[{}], shape=SHAPE,
                          passes=PASSES, cache=CACHE)


# ---------------------------------------------------------------------------
# registry-wide lint: every mix x backend reconciles, checked (not waived)
# ---------------------------------------------------------------------------

ALL_CASES = sorted({(b, m) for b in BACKENDS for m in mix_names(b)})


@pytest.mark.parametrize("backend,mix", ALL_CASES,
                         ids=[f"{b}-{m}" for b, m in ALL_CASES])
def test_registry_base_accounting(base_report, backend, mix):
    cases = [c for c in base_report.cases
             if c.backend == backend and c.mix == mix]
    if not cases:
        pytest.skip(f"{mix} does not support {backend}")
    for c in cases:
        if c.waived:   # only the documented caveats may be waived, loudly
            assert c.waived_reason, f"{c.where()} waived without a reason"
            assert waiver_reason(get_mix(mix), backend, {}), \
                f"{c.where()} waived outside the documented policy"
            continue
        assert c.ok, f"{c.where()}: " + "; ".join(
            f"{k.name}: {k.detail}" for k in c.failures)


def test_sharded_backend_audits_clean():
    """The mesh oracle wraps the xla kernels per shard — its compiled
    traffic must reconcile against the same declared formulas, including
    the smoke grid's unroll axis (the rotating-carry pass loop rides
    through the shard wrapper unchanged)."""
    rep = audit_registry(backends=("sharded",), mixes=("copy",),
                         smoke=True, cache=CACHE)
    assert len(rep.cases) == 3
    for case in rep.cases:
        assert case.backend == "sharded" and case.ok and not case.waived, \
            rep.table()


def test_base_report_clean_and_serializable(base_report, tmp_path):
    assert base_report.ok
    assert base_report.exit_code() == EXIT_OK
    assert not base_report.skipped
    d = base_report.to_dict()
    assert d["schema"] == "repro.audit/v1"
    out = tmp_path / "audit.json"
    base_report.to_json(out)
    back = json.loads(out.read_text())
    assert len(back["cases"]) == len(base_report.cases)
    # the rendered table names every case
    table = base_report.table()
    for c in base_report.cases:
        assert c.where() in table


# ---------------------------------------------------------------------------
# corrupted accounting formulas must fail, naming the offending triple
# ---------------------------------------------------------------------------

def _corrupt(monkeypatch, name, **fields):
    bad = dataclasses.replace(get_mix(name), **fields)
    real = audit_verify.get_mix
    monkeypatch.setattr(audit_verify, "get_mix",
                        lambda n: bad if n == name else real(n))


def test_corrupted_reads_formula_fails(monkeypatch):
    _corrupt(monkeypatch, "copy", reads_per_elem=2.0)
    rep = audit_registry(backends=("xla",), mixes=("copy",), smoke=True,
                         cache=CACHE)
    assert rep.exit_code() == EXIT_VIOLATION
    assert rep.violations
    for case in rep.violations:
        assert case.where().startswith("xla/copy")
        assert any(c.name == "loads" for c in case.failures)


def test_corrupted_flops_formula_fails(monkeypatch):
    _corrupt(monkeypatch, "triad", flops_per_elem=7.0)
    rep = audit_registry(backends=("xla",), mixes=("triad",), smoke=True,
                         cache=CACHE)
    assert rep.exit_code() == EXIT_VIOLATION
    assert any(c.name in ("arith", "lint:triad") for case in rep.violations
               for c in case.failures)


def test_cli_audit_goldens_exit0(capsys):
    assert bench_main(["audit", "--goldens", str(HLO_DIR)]) == EXIT_OK
    assert "0 violations" in capsys.readouterr().out


def test_cli_audit_corrupted_exit2_names_case(monkeypatch, capsys):
    _corrupt(monkeypatch, "copy", writes_per_elem=3.0)
    rc = bench_main(["audit", "--goldens", str(HLO_DIR)])
    captured = capsys.readouterr()
    assert rc == EXIT_VIOLATION
    assert "accounting violation" in captured.err
    assert "copy" in captured.err


def test_cli_audit_json(capsys):
    assert bench_main(["audit", "--goldens", str(HLO_DIR), "--json"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["schema"] == "repro.audit/v1"
    assert len(d["cases"]) == 26
    assert d["summary"]["waived"] == 0


# ---------------------------------------------------------------------------
# deviceless golden fixtures
# ---------------------------------------------------------------------------

def test_goldens_manifest_covers_both_backends():
    manifest = json.loads((HLO_DIR / "manifest.json").read_text())
    pairs = {(c["backend"], c["mix"]) for c in manifest["cases"]}
    for mix in ("load_sum", "copy", "triad", "rw_2to1", "fma_8"):
        assert ("xla", mix) in pairs and ("pallas", mix) in pairs


def test_goldens_manifest_covers_carried_unroll():
    """The deviceless CI path pins the rotating-carry lowering: every
    carried-mix family head has unroll-2 and unroll-4 fixtures on both
    backends, each with its own passes (>= 2 trips)."""
    manifest = json.loads((HLO_DIR / "manifest.json").read_text())
    triples = {(c["backend"], c["mix"], c.get("unroll", 1))
               for c in manifest["cases"]}
    for mix in ("copy", "triad", "rw_2to1"):
        for u in (2, 4):
            for backend in BACKENDS:
                assert (backend, mix, u) in triples
    for c in manifest["cases"]:
        if c.get("unroll", 1) > 1:
            assert c["passes"] // c["unroll"] >= 2


def test_goldens_audit_clean():
    rep = audit_goldens(HLO_DIR)
    assert rep.ok and rep.exit_code() == EXIT_OK
    assert len(rep.cases) == 26
    assert not rep.waived


def test_dce_fixture_fails_loudly():
    """Pinned regression: the pre-fix pallas copy lowering (outputs not
    loop-carried) dead-code-eliminates the whole timed sweep — the audit
    must call that out as 'dce', not report tiny-but-plausible traffic."""
    hlo = (HLO_DIR / "dce_pallas_copy.txt").read_text()
    case = audit_hlo(hlo, "copy", "pallas", SHAPE, passes=PASSES)
    assert not case.ok
    names = [c.name for c in case.failures]
    assert "dce" in names
    assert "eliminated" in next(c.detail for c in case.failures
                                if c.name == "dce")


def test_dead_sweep_fixture_fails_loudly():
    """Pinned regression: the pre-fix unroll=4 xla copy lowering, where
    only the LAST unrolled sweep's outputs were loop state — XLA narrowed
    the three interior sweeps to one element each and the trip moved ~1/4
    of the declared traffic.  The audit must fail (exit 2) naming the
    backend/mix[knobs] triple, never waive it."""
    hlo = (HLO_DIR / "dead_sweep_xla_copy_u4.txt").read_text()
    case = audit_hlo(hlo, "copy", "xla", SHAPE, passes=8, unroll=4,
                     knobs={"unroll": 4})
    assert not case.ok and not case.waived
    assert case.where() == "xla/copy[unroll=4]"
    names = {c.name for c in case.failures}
    assert names & {"dce", "loads", "stores"}, names
    rep = audit_verify.AuditReport(cases=[case])
    assert rep.exit_code() == EXIT_VIOLATION
    assert "xla/copy[unroll=4]" in rep.table()


def test_write_goldens_roundtrip(tmp_path):
    manifest = write_goldens(tmp_path, shape=(16, 128), passes=2)
    assert (tmp_path / "manifest.json").exists()
    for case in manifest["cases"]:
        assert (tmp_path / case["file"]).exists()
    rep = audit_goldens(tmp_path)
    assert rep.ok, rep.table()


# ---------------------------------------------------------------------------
# unknown opcodes stay loud (the istream extraction contract audit rides on)
# ---------------------------------------------------------------------------

BOGUS_HLO = """\
HloModule bogus

ENTRY %main (p0: f32[64,128]) -> f32[64,128] {
  %p0 = f32[64,128]{1,0} parameter(0)
  ROOT %weird.1 = f32[64,128]{1,0} frobnicate(%p0)
}
"""


def test_unknown_opcode_warns_and_buckets():
    with pytest.warns(UnknownOpcodeWarning, match="frobnicate"):
        raw = extract_profile(BOGUS_HLO, expected_trips=1)
    assert raw["per_iter"]["unknown"].get("frobnicate") == 64 * 128


# ---------------------------------------------------------------------------
# property: random members of the open-ended rw_RtoW family reconcile
# ---------------------------------------------------------------------------

def test_random_rw_pairs_deterministic():
    assert random_rw_pairs(4, seed=7) == random_rw_pairs(4, seed=7)
    assert all(p.startswith("rw_") for p in random_rw_pairs(4))


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=4))
def test_rw_family_accounting_property(r, w):
    name = rw_name(r, w)
    spec = BenchSpec(mixes=(name,), sizes=(NBYTES,), backend="xla",
                     passes=PASSES, reps=2, warmup=0)
    case = audit_case(spec, name, SHAPE, "float32", PASSES, cache=CACHE)
    assert case.ok, f"{case.where()}: " + "; ".join(
        f"{c.name}: {c.detail}" for c in case.failures)


# ---------------------------------------------------------------------------
# unroll soundness: carried mixes ENFORCED at unroll>1 (waiver retired)
# ---------------------------------------------------------------------------

UNROLL_CASES = [(b, m, u) for b in BACKENDS
                for m in ("copy", "triad", "rw_2to1")
                for u in (2, 4)]


@pytest.mark.parametrize("backend,mix,unroll", UNROLL_CASES,
                         ids=[f"{b}-{m}-u{u}" for b, m, u in UNROLL_CASES])
def test_carried_unroll_enforced_and_scales(backend, mix, unroll):
    """The tentpole acceptance check: carried mixes at unroll>1 carry a
    full compiled-traffic expectation (no waiver) and the rotating-carry
    lowering keeps every sweep live — per-TRIP loads/stores cover u x one
    sweep's declared stream traffic, and the audit passes."""
    from repro.istream.analyze import analyze_case
    assert waiver_reason(get_mix(mix), backend, {"unroll": unroll}) is None
    p = max(PASSES, 2 * unroll)
    spec = BenchSpec(mixes=(mix,), sizes=(NBYTES,), backend=backend,
                     passes=p, unroll=unroll, reps=2, warmup=0)
    case = audit_case(spec, mix, SHAPE, "float32", p, cache=CACHE)
    assert not case.waived
    assert case.ok, f"{case.where()}: " + "; ".join(
        f"{c.name}: {c.detail}" for c in case.failures)
    prof = analyze_case(spec, mix, SHAPE, "float32", p, cache=CACHE)
    m = get_mix(mix)
    n = SHAPE[0] * SHAPE[1]
    tol = unroll * (64 + 0.03 * n)
    assert prof.per_iter["loads"] >= unroll * m.reads_per_elem * n - tol
    assert prof.per_iter["stores"] >= unroll * m.writes_per_elem * n - tol


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=1, max_value=3),
       st.integers(min_value=1, max_value=3),
       st.sampled_from([2, 4]))
def test_rw_unroll_linear_scaling_property(r, w, u):
    """Property over the open-ended rw_RtoW family: on xla the compiled
    per-trip loads/stores at unroll=u are ~u x the unroll=1 counts (the
    pre-fix lowering scaled them by ~1, not u)."""
    from repro.istream.analyze import analyze_case
    name = rw_name(r, w)
    base = analyze_case(
        BenchSpec(mixes=(name,), sizes=(NBYTES,), backend="xla",
                  passes=PASSES, reps=2, warmup=0),
        name, SHAPE, "float32", PASSES, cache=CACHE)
    p = max(PASSES, 2 * u)
    prof = analyze_case(
        BenchSpec(mixes=(name,), sizes=(NBYTES,), backend="xla",
                  passes=p, unroll=u, reps=2, warmup=0),
        name, SHAPE, "float32", p, cache=CACHE)
    for key in ("loads", "stores"):
        exp = u * base.per_iter[key]
        assert abs(prof.per_iter[key] - exp) <= u * 64 + 0.03 * exp, \
            (name, key, prof.per_iter[key], exp)


def test_scalar_unroll_was_never_exempt():
    """Regression pin for the over-broad waiver condition (it swept
    scalar-accumulator mixes on pallas into the carried-mix waiver):
    scalar mixes at unroll>1 carry a full expectation on both backends."""
    for backend in BACKENDS:
        for name in ("load_sum", "fma_8"):
            for u in (2, 4):
                assert waiver_reason(get_mix(name), backend,
                                     {"unroll": u}) is None
                assert expected_counts(get_mix(name), backend, 8192.0,
                                       {"unroll": u}) is not None


def test_smoke_grid_covers_unroll_axis():
    """The CI fast-fail gate audits the unroll AND load axes, not just base
    knobs."""
    from repro.audit.verify import default_knob_grid
    assert default_knob_grid(smoke=True) == [{}, {"unroll": 2},
                                             {"unroll": 4}, {"load": 1}]


# ---------------------------------------------------------------------------
# waiver policy: documented, named, never a silent pass
# ---------------------------------------------------------------------------


def test_waiver_reason_base_knobs_none():
    for backend in BACKENDS:
        for name in ("copy", "triad", "rw_2to1", "fma_8"):
            assert waiver_reason(get_mix(name), backend, {}) is None


def test_expected_counts_derive_from_declared_fields():
    """The whole corruption-detection mechanism: expectations come from the
    DECLARED registry fields, so editing a formula moves the expectation
    away from the (unchanged) compiled traffic."""
    good = expected_counts(get_mix("copy"), "xla", 8192)
    bad = expected_counts(dataclasses.replace(get_mix("copy"),
                                              reads_per_elem=2.0),
                          "xla", 8192)
    assert bad["loads"] == 2 * good["loads"]


def test_lint_mix_flags_inconsistent_rw():
    bad = dataclasses.replace(get_mix("rw_2to1"), flops_per_elem=999.0)
    assert any(not ok for _, ok, _ in lint_mix(bad))
    assert all(ok for _, ok, _ in lint_mix(get_mix("rw_2to1")))


# ---------------------------------------------------------------------------
# ECM analytic predictor
# ---------------------------------------------------------------------------

def _model(rate=1e9, l1_gbps=100.0, dram_gbps=10.0, l1_cap=100_000):
    return FittedMachineModel(
        name="synthetic",
        levels=(LevelFit(name="L1", capacity_bytes=l1_cap, capacity_ci=None,
                         bandwidth={"load_sum": {"gbps": l1_gbps, "ci": None,
                                                 "n": 1}}),
                LevelFit(name="DRAM", capacity_bytes=None, capacity_ci=None,
                         bandwidth={"load_sum": {"gbps": dram_gbps,
                                                 "ci": None, "n": 1}})),
        issue={"rate_elems_per_s": rate})


def _profile(loads=8192.0, stores=0.0, arith=8192.0, move=0.0,
             mix="load_sum", nbytes=NBYTES):
    from repro.istream.analyze import InstructionProfile
    return InstructionProfile(mix=mix, backend="xla", shape=SHAPE,
                              dtype="float32", nbytes=nbytes, unroll=1,
                              interleave=1,
                              per_iter={"loads": loads, "stores": stores,
                                        "arith": arith, "move": move},
                              critical_path=1.0, trips=PASSES, passes=PASSES,
                              loop="while.1")


def test_ecm_core_vs_data_bound():
    prof = _profile()
    slow_core = ecm_predict(prof, _model(rate=1e9))
    assert slow_core.bound == "core"
    assert slow_core.t_pred_s == pytest.approx(16384 / 1e9)
    fast_core = ecm_predict(prof, _model(rate=1e13))
    assert fast_core.bound == "data"
    # fits L1 (32 KiB < 100 KB): only the L1 term on the transfer path
    assert list(fast_core.level_times) == ["L1"]
    assert fast_core.t_pred_s == pytest.approx(32768 / 100e9)
    assert fast_core.gbps == pytest.approx(
        fast_core.declared_bytes / fast_core.t_pred_s / 1e9)


def test_ecm_level_path_extends_past_capacity():
    big = _profile(loads=65536.0, arith=65536.0, nbytes=262144)
    pred = ecm_predict(big, _model(rate=1e13))
    assert set(pred.level_times) == {"L1", "DRAM"}


def test_validate_ecm_zero_error_on_self():
    model = _model(rate=1e9)
    prof = _profile()
    pred_call_s = ecm_predict(prof, model).t_pred_s * PASSES
    point = types.SimpleNamespace(mix="load_sum", backend="xla",
                                  nbytes=NBYTES, passes=PASSES,
                                  mean_s=pred_call_s, unroll=1,
                                  block_rows=None,
                                  gbps=4 * NBYTES / pred_call_s / 1e9)
    out = validate_ecm([(point, prof)], model)
    assert out["n"] == 1
    assert out["median_abs_rel_err"] == pytest.approx(0.0, abs=1e-12)
    assert out["rows"][0]["bound"] == "core"


def test_validate_ecm_skips_unmeasured():
    model = _model()
    point = types.SimpleNamespace(mix="load_sum", backend="xla",
                                  nbytes=NBYTES, passes=PASSES, mean_s=0.0,
                                  unroll=1, gbps=0.0)
    out = validate_ecm([(point, None), (point, _profile())], model)
    assert out["n"] == 0 and out["median_abs_rel_err"] is None


# ---------------------------------------------------------------------------
# block-shape prefilter: same winner as the exhaustive timed sweep
# ---------------------------------------------------------------------------

class _FakeRunner:
    """Deterministic 'timing': throughput peaked at block_rows=64."""

    def __init__(self):
        self.timed_rows = []

    def run(self, spec):
        rows = spec.block_rows or 128
        self.timed_rows.append(rows)
        gbps = 100.0 - abs(math.log2(rows) - 6.0) * 10.0
        return types.SimpleNamespace(
            points=[types.SimpleNamespace(gbps=gbps)])


def test_prefilter_ranking_prefers_fewer_blocks_in_core_regime():
    pred = predict_block_rows(NBYTES, _model(rate=1e9), (8, 16, 32, 64))
    assert pred[64] > pred[32] > pred[16] > pred[8]
    kept, _ = ecm_filter_rows(NBYTES, _model(rate=1e9), (8, 16, 32, 64),
                              keep=2)
    assert kept == (32, 64)


def test_autotune_ecm_prefilter_matches_exhaustive():
    from repro.core.autotune import sweep_block_shapes
    model = _model(rate=1e9)
    exhaustive = sweep_block_shapes(NBYTES, runner=_FakeRunner())
    pruned_runner = _FakeRunner()
    pruned = sweep_block_shapes(NBYTES, model=model, ecm_keep=3,
                                runner=pruned_runner)
    assert pruned.best_rows == exhaustive.best_rows == 64
    assert pruned.ecm is not None
    assert set(pruned.ecm["kept"]) == set(pruned_runner.timed_rows)
    assert pruned.ecm["pruned"]      # the saving is recorded, not silent
    assert len(pruned_runner.timed_rows) < len(exhaustive.table)
    for rows in pruned.ecm["pruned"]:
        assert rows not in pruned_runner.timed_rows
        assert rows in pruned.ecm["predicted_gbps"]


# ---------------------------------------------------------------------------
# autotune unroll objective: ranks audited GB/s, immune to phantom traffic
# ---------------------------------------------------------------------------

class _UnrollRunner:
    """Injected timing for the unroll leg: a machine where unroll does not
    help (mild decode penalty, GB/s slightly decreasing in u).
    ``phantom=True`` reproduces the pre-fix measurement shape — only ~1/u
    of the declared traffic executed, so the declared-bytes normalization
    reported ~u x the true GB/s."""

    def __init__(self, phantom: bool = False):
        self.phantom = phantom

    def run(self, spec):
        u = spec.unroll or 1
        gbps = 100.0 / (1.0 + 0.02 * (u - 1))
        if self.phantom and u > 1:
            gbps *= u
        return types.SimpleNamespace(
            points=[types.SimpleNamespace(gbps=gbps)])


def test_autotune_unroll_objective_sound_not_phantom():
    """Regression for the tuner leg of the dead-sweep bug: with sound
    measurements the objective picks the genuinely best unroll, while
    pre-fix-shaped throughput (x u phantom) would flip the winner to the
    largest candidate."""
    from repro.core.autotune import CANDIDATE_UNROLLS, sweep_block_shapes
    sound = sweep_block_shapes(NBYTES, mix="copy", tune_unroll=True,
                               runner=_UnrollRunner())
    assert sound.best_unroll == 1
    assert sound.unroll_audit == {u: None for u in CANDIDATE_UNROLLS}
    phantom = sweep_block_shapes(NBYTES, mix="copy", tune_unroll=True,
                                 runner=_UnrollRunner(phantom=True))
    assert phantom.best_unroll == max(CANDIDATE_UNROLLS)
    assert phantom.best_unroll != sound.best_unroll


def test_autotune_unroll_objective_excludes_waived(monkeypatch):
    """A candidate whose (mix, unroll) combination carries an accounting
    waiver is timed and reported but never wins — even when its un-audited
    GB/s looks best (the pre-fix phantom shape)."""
    from repro.core.autotune import sweep_block_shapes
    monkeypatch.setattr(
        audit_verify, "waiver_reason",
        lambda mix, backend, knobs=None:
        "carried-mix unroll (simulated)"
        if (knobs or {}).get("unroll", 1) > 1 else None)
    r = sweep_block_shapes(NBYTES, mix="copy", tune_unroll=True,
                           runner=_UnrollRunner(phantom=True))
    assert r.best_unroll == 1
    assert all(r.unroll_audit[u] for u in r.unroll_audit if u > 1)
