"""The instruction-stream knobs (unroll / interleave) through the bench
stack: spec validation with actionable gate errors, property-based
accounting parity across backends (the PR-3 discipline applied to the new
axes), numeric equality of the interleaved kernel variants against their
plain counterparts, the compiled-case cache-key no-alias guarantee, the
``summarize(key=...)`` grouped view, and the schema-v4 golden round-trip."""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.bench import (BenchResult, BenchSpec, BenchSpecError, Runner,
                         get_backend)
from repro.bench.backends import _NON_CASE_FIELDS, case_knobs
from repro.bench.spec import knob_names

DATA = Path(__file__).parent / "data"
TINY = dict(sizes=(16 * 2**10,), reps=2, warmup=1)

#: shared so repeated knob draws hit the compiled-case cache
RUNNER = Runner()


# ---------------------------------------------------------------------------
# spec validation + the improved BenchSpecError surface
# ---------------------------------------------------------------------------

def test_spec_knob_validation():
    s = BenchSpec(unroll=4, interleave=2, passes=8, **TINY)
    assert s.unroll == 4 and s.interleave == 2
    with pytest.raises(BenchSpecError):
        BenchSpec(unroll=0, **TINY)
    with pytest.raises(BenchSpecError):
        BenchSpec(interleave=0, **TINY)
    # explicit passes must divide into whole unrolled bodies
    with pytest.raises(BenchSpecError, match="multiple of unroll"):
        BenchSpec(unroll=3, passes=8, **TINY)
    # auto passes (None) is fine — the Runner rounds up
    BenchSpec(unroll=3, passes=None, **TINY)


def test_unknown_knob_error_lists_valid_fields():
    """from_dict on an unknown field names every valid knob — the error is
    the documentation."""
    d = BenchSpec(**TINY).to_dict()
    d["unrol"] = 2      # typo'd knob
    with pytest.raises(BenchSpecError) as ei:
        BenchSpec.from_dict(d)
    msg = str(ei.value)
    assert "valid fields" in msg
    for name in ("unroll", "interleave", "mixes", "backend"):
        assert name in msg


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_gate_error_names_backend_and_valid_knobs(backend):
    """A knob rejected by a backend gate says WHICH backend's validate
    raised, WHICH rule fired, and lists the valid spec knobs."""
    spec = BenchSpec(mixes=("fma_8",), backend=backend, interleave=2,
                     **TINY)
    with pytest.raises(BenchSpecError) as ei:
        get_backend(backend).validate(spec)
    msg = str(ei.value)
    assert f"{backend}.validate" in msg
    assert "gate:" in msg
    assert "valid spec knobs" in msg
    assert "unroll" in msg and "interleave" in msg


def test_gate_interleave_xor_streams_and_block_rows():
    for kw in (dict(streams=2), dict(block_rows=8)):
        spec = BenchSpec(mixes=("load_sum",), interleave=2, **TINY, **kw)
        with pytest.raises(BenchSpecError, match="gate:"):
            get_backend("xla").validate(spec)


def test_run_mix_rejects_non_interleavable():
    from repro.core.instruction_mix import run_mix
    x = jnp.ones((16, 128), jnp.float32)
    with pytest.raises(KeyError, match="interleav"):
        run_mix("fma_8", x, 1, interleave=2)


# ---------------------------------------------------------------------------
# property-based accounting parity (the PR-3 rw discipline, new axes)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.sampled_from([1, 2, 4]), st.sampled_from([1, 2]),
       st.sampled_from(["copy", "rw_2to1", "load_sum"]))
def test_knob_parity_xla_vs_pallas(unroll, interleave, mix):
    """For any (unroll, interleave, mix) combination both backends report
    IDENTICAL bytes/flops per call, and the recorded traffic is exactly
    formula x passes — the knobs change the instruction stream, never the
    accounting."""
    from repro.bench import get_mix
    acct = {}
    for backend in ("xla", "pallas"):
        spec = BenchSpec(mixes=(mix,), backend=backend, unroll=unroll,
                         interleave=interleave, passes=4, **TINY)
        (pt,) = RUNNER.run(spec).points
        m = get_mix(mix)
        assert pt.gbps > 0, (backend, unroll, interleave, mix)
        assert pt.unroll == unroll and pt.interleave == interleave
        assert pt.bytes_per_call == m.bytes_per_pass(pt.nbytes) * pt.passes
        assert pt.flops_per_call == (m.flops_per_pass(pt.nbytes // 4)
                                     * pt.passes)
        acct[backend] = (pt.bytes_per_call, pt.flops_per_call, pt.passes)
    assert acct["xla"] == acct["pallas"], (mix, unroll, interleave, acct)


def test_passes_round_up_to_unroll():
    """Auto-picked passes round UP to whole unrolled bodies (never down to
    0), and the recorded accounting uses the rounded value."""
    spec = BenchSpec(mixes=("copy",), unroll=3, passes=None,
                     target_bytes=1e5, **TINY)
    (pt,) = RUNNER.run(spec).points
    assert pt.passes % 3 == 0 and pt.passes >= 3


# ---------------------------------------------------------------------------
# numeric equality: interleaved variants compute the same values
# ---------------------------------------------------------------------------

def _buf(rows=32):
    rng = np.random.default_rng(7)
    return jnp.asarray(rng.uniform(0.5, 1.5, (rows, 128)).astype(np.float32))


def test_interleaved_kernels_match_plain():
    from repro.core import instruction_mix as im
    x = _buf()
    np.testing.assert_allclose(
        im.k_load_sum_istream(x, 4, 1, 4), im.k_load_sum(x, 4), rtol=1e-5)
    np.testing.assert_array_equal(
        im.k_copy_istream(x, 4, 1, 2), im.k_copy(x, 4))
    streams = im.rw_streams(x, 2)
    np.testing.assert_allclose(
        im.k_rw_istream(streams, (x,), 2, 1, 2),
        im.k_rw(streams, (x,), 2), rtol=1e-5)


def test_unroll_preserves_values():
    """Scalar-accumulator mixes compute identical values at any unroll.
    Carried mixes differ ONLY by the rotating-carry consumption term —
    the final trip holds u live output slots and each slot's last element
    is folded in, so copy at unroll=u adds exactly (u-1) extra copies of
    the stream's last element versus unroll=1 (the streams themselves are
    unchanged; this pins the consumption convention)."""
    from repro.core import instruction_mix as im
    x = _buf()
    np.testing.assert_allclose(im.k_load_sum(x, 4, unroll=2),
                               im.k_load_sum(x, 4), rtol=1e-5)
    last = float(np.asarray(x)[-1, -1])
    np.testing.assert_allclose(im.k_copy(x, 4, unroll=4),
                               im.k_copy(x, 4) + 3 * last, rtol=1e-5)


# ---------------------------------------------------------------------------
# compiled-case cache key: knob-differing cases never alias
# ---------------------------------------------------------------------------

def test_cache_key_derives_from_full_knob_dict():
    """Forward-compat proof: every BenchSpec field is either explicitly
    excluded as measurement-only or lands in the cache key — a future knob
    that changes compilation can NOT silently alias a stale case."""
    spec = BenchSpec(**TINY)
    knob_cols = {name for name, _ in case_knobs(spec)}
    for f in dataclasses.fields(spec):
        assert (f.name in _NON_CASE_FIELDS) != (f.name in knob_cols), \
            f"field {f.name} neither excluded nor keyed"
    # the new knobs are key columns
    assert {"unroll", "interleave"} <= knob_cols
    # excluded fields are genuinely measurement-only (shape/traffic fields
    # like sizes/dtype appear in the key through other columns)
    assert "reps" in _NON_CASE_FIELDS and "warmup" in _NON_CASE_FIELDS


@pytest.mark.parametrize("knob", [dict(unroll=2), dict(interleave=2)])
def test_cache_no_alias_regression(knob):
    """Two specs differing ONLY in a new knob compile two distinct cases:
    the second run must be a cache MISS, and the two points must differ in
    their recorded knob column."""
    r = Runner()
    base = BenchSpec(mixes=("copy",), passes=4, **TINY)
    r.run(base)
    misses = r.cache_misses
    r.run(base.replace(**knob))
    assert r.cache_misses == misses + 1, f"{knob} aliased a cached case"
    r.run(base.replace(**knob))          # identical knobs re-hit
    assert r.cache_misses == misses + 1


def test_case_keys_distinct_across_knob_grid():
    """Direct key-level check across the whole grid — no two (unroll,
    interleave) combinations share a compiled-case cache key."""
    backend = get_backend("xla")
    from repro.bench import get_mix
    mix = get_mix("copy")
    keys = set()
    for u in (1, 2, 4):
        for i in (1, 2, 4):
            spec = BenchSpec(mixes=("copy",), unroll=u, interleave=i,
                             passes=4, **TINY)
            keys.add(backend.case_key(spec, mix, (32, 128), "float32", 4))
    assert len(keys) == 9


# ---------------------------------------------------------------------------
# summarize grouped by the new axes + schema-v4 golden round-trip
# ---------------------------------------------------------------------------

def test_summarize_key_groups_by_istream_axes():
    specs = [BenchSpec(mixes=("copy",), unroll=u, interleave=i, passes=4,
                       **TINY)
             for u in (1, 2) for i in (1, 2)]
    res = RUNNER.run_many(specs)
    s = res.summarize(key=lambda p: f"{p.mix}/u{p.unroll}x{p.interleave}")
    cells = s["all"]
    assert set(cells) == {"copy/u1x1", "copy/u1x2", "copy/u2x1",
                          "copy/u2x2"}
    assert all(c["n"] == 1 and c["gbps"] > 0 for c in cells.values())
    # string keys survive the meta/JSON stash
    res.meta["by_knobs"] = s
    back = BenchResult.from_dict(json.loads(res.to_json()))
    assert set(back.meta["by_knobs"]["all"]) == set(cells)
    # default grouping is unchanged: one 'copy' cell
    assert set(res.summarize()["all"]) == {"copy"}


def test_golden_v4_roundtrip():
    """The schema-v4 fixture: points carry unroll/interleave and a full
    istream classification dict; the file round-trips bit-identically
    through from_dict/to_dict."""
    res = BenchResult.from_json(DATA / "result_v4.json")
    assert res.schema_version == 4
    assert res.points
    knobs = {(p.unroll, p.interleave) for p in res.points}
    assert len(knobs) > 1                   # a real knob sweep
    labels = set()
    for p in res.points:
        assert p.istream is not None
        assert p.istream["label"] in ("bandwidth-bound", "issue-bound")
        assert p.istream["per_iter"]["loads"] > 0
        labels.add(p.istream["label"])
    assert labels == {"bandwidth-bound", "issue-bound"}
    back = BenchResult.from_dict(json.loads(res.to_json()))
    assert back.points == res.points and back.schema_version == 4


@pytest.mark.parametrize("fname,ver", [
    ("result_v1.json", 1), ("result_v2.json", 2), ("result_v3.json", 3),
])
def test_golden_older_schemas_default_istream_knobs(fname, ver):
    """v1-v3 files load with the v4 defaults: unroll=interleave=1,
    istream=None — the back-compat promise for the new columns."""
    res = BenchResult.from_json(DATA / fname)
    assert res.schema_version == ver
    for p in res.points:
        assert p.unroll == 1 and p.interleave == 1 and p.istream is None


def test_knob_names_exposes_full_surface():
    names = knob_names()
    assert "unroll" in names and "interleave" in names
    assert names == tuple(sorted(names))
