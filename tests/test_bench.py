"""repro.bench: spec validation + JSON round-trip, mix-registry parity across
backends (identical bytes/flops accounting from the shared registry), Runner
smoke in interpret mode, CLI surface, and the relative-baseline fix."""
import json

import pytest

from repro.bench import (BenchSpec, BenchSpecError, BenchResult, Runner,
                         get_mix, mix_names, quick_spec, registry)
from repro.bench.result import BenchPoint, SCHEMA_VERSION

TINY = dict(sizes=(16 * 2**10,), reps=2, warmup=1, passes=1)


# ---------------------------------------------------------------------------
# BenchSpec validation + serialization
# ---------------------------------------------------------------------------

def test_spec_defaults_valid():
    s = BenchSpec()
    assert s.backend == "xla" and s.mixes == ("load_sum",)


@pytest.mark.parametrize("kw", [
    dict(backend="cuda"),
    dict(mixes=("nope",)),
    dict(mixes=()),
    dict(mixes=("load_only",)),            # pallas-only mix on xla backend
    dict(sizes=(0,)),
    dict(sizes=()),
    dict(streams=0),
    dict(devices=0),
    dict(devices=2),                       # xla is single-device
    dict(devices=2, backend="pallas"),     # pallas is single-device
    dict(block_rows=12),                   # not a multiple of 8
    dict(reps=0),
    dict(passes=0),
    dict(target_bytes=0),
    dict(dtype="floatzz"),
])
def test_spec_rejects(kw):
    with pytest.raises(BenchSpecError):
        BenchSpec(**kw)


def test_spec_accepts_load_only_on_pallas():
    s = BenchSpec(mixes=("load_only",), backend="pallas")
    assert s.mixes == ("load_only",)


def test_spec_json_roundtrip(tmp_path):
    s = BenchSpec(mixes=("load_sum", "fma_4"), sizes=(2**14, 2**20),
                  backend="pallas", block_rows=32, streams=2, reps=3,
                  tags=("unit",))
    p = tmp_path / "spec.json"
    s.to_json(p)
    back = BenchSpec.from_json(p)
    assert back == s
    # lists coming from hand-written JSON coerce to tuples
    d = json.loads(s.to_json())
    assert BenchSpec.from_dict(d) == s


def test_spec_rejects_unknown_fields_and_newer_version():
    with pytest.raises(BenchSpecError):
        BenchSpec.from_dict({"mixes": ["load_sum"], "bogus": 1})
    with pytest.raises(BenchSpecError):
        BenchSpec.from_dict({"spec_version": 99})


def test_spec_replace_is_frozen():
    s = BenchSpec()
    with pytest.raises(Exception):
        s.backend = "pallas"
    assert s.replace(backend="pallas").backend == "pallas"


# ---------------------------------------------------------------------------
# mix registry — declared once, consumed by both backends
# ---------------------------------------------------------------------------

def test_registry_parity_accounting():
    """Every dual-backend mix runs through the Runner on a tiny buffer on BOTH
    backends and reports byte-identical bytes/flops accounting."""
    runner = Runner()
    for name in mix_names():
        m = get_mix(name)
        per_backend = {}
        for backend in m.backends:
            spec = BenchSpec(mixes=(name,), backend=backend, **TINY)
            res = runner.run(spec)
            (pt,) = res.points
            assert pt.gbps > 0 and pt.mean_s > 0, (name, backend)
            per_backend[backend] = (pt.bytes_per_call, pt.flops_per_call)
        assert len(set(per_backend.values())) == 1, (name, per_backend)


def test_registry_accounting_values():
    n = 1024
    nbytes = 4 * n
    assert get_mix("load_sum").bytes_per_pass(nbytes) == nbytes
    assert get_mix("load_sum").flops_per_pass(n) == n
    assert get_mix("copy").bytes_per_pass(nbytes) == 2 * nbytes
    assert get_mix("triad").bytes_per_pass(nbytes) == 3 * nbytes
    assert get_mix("triad").flops_per_pass(n) == 2 * n
    assert get_mix("fma_8").flops_per_pass(n) == 16 * n
    assert get_mix("mxu").flops_per_pass(n) == 2 * 128 * n
    assert get_mix("load_only").backends == ("pallas",)


def test_legacy_views_delegate_to_registry():
    from repro.core import instruction_mix
    from repro.core.buffers import working_set
    from repro.kernels.membench import ops as mb_ops
    legacy = instruction_mix.mixes()
    reg = registry()
    for name, m in legacy.items():
        if name in reg:
            assert m == reg[name], name
    x = working_set(32 * 1024)
    assert mb_ops.work_per_call("copy", x) == (2 * x.size * 4, 0.0)


# ---------------------------------------------------------------------------
# Runner smoke + versioned results
# ---------------------------------------------------------------------------

def test_runner_smoke_and_result_roundtrip(tmp_path):
    spec = BenchSpec(mixes=("load_sum", "copy"), sizes=(16 * 2**10, 64 * 2**10),
                     reps=2, warmup=1, target_bytes=1e6)
    res = Runner().run(spec)
    assert len(res.points) == 4
    assert res.schema_version == SCHEMA_VERSION
    assert res.spec["backend"] == "xla"
    assert res.machine["jax"] and res.machine["device_platform"]
    for p in res.points:
        assert p.backend == "xla" and p.gbps > 0 and p.passes >= 1
    path = tmp_path / "res.json"
    res.to_json(path)
    back = BenchResult.from_json(path)
    assert back.points == res.points
    assert back.spec == res.spec


def test_runner_pallas_interpret_smoke():
    spec = BenchSpec(mixes=("load_only", "load_sum"), backend="pallas",
                     block_rows=8, streams=2, **TINY)
    res = Runner().run(spec)
    assert [p.mix for p in res.points] == ["load_only", "load_sum"]
    assert all(p.streams == 2 and p.block_rows == 8 for p in res.points)


def test_runner_auto_passes():
    from repro.bench.runner import pick_passes
    assert pick_passes(1024, 1e6) == 976
    assert pick_passes(10**9, 1e6) == 1
    spec = BenchSpec(mixes=("load_sum",), sizes=(16 * 2**10,), reps=2,
                     warmup=1, target_bytes=1e6)
    (pt,) = Runner().run(spec).points
    assert pt.passes == pick_passes(pt.nbytes, 1e6)


def test_xla_backend_rejects_unsupported_knobs():
    with pytest.raises(BenchSpecError):
        Runner().run(BenchSpec(mixes=("copy",), streams=2, **TINY))
    with pytest.raises(BenchSpecError):
        Runner().run(BenchSpec(mixes=("copy",), block_rows=8, **TINY))


def test_baseline_relative_zero_anchor():
    """A 0.0 first measurement must STAY the baseline (rel=nan), not silently
    re-anchor on the next point — the fig1 `base = base or gbps` bug."""
    def pt(streams, gbps):
        return BenchPoint(nbytes=1024, mix="load_sum", dtype="float32",
                          backend="xla", passes=1, streams=streams,
                          block_rows=None, reps=1, bytes_per_call=1024.0,
                          flops_per_call=0.0, mean_s=1e-3, std_s=0.0,
                          min_s=1e-3, gbps=gbps, gflops=0.0)
    res = BenchResult(points=[pt(1, 0.0), pt(2, 5.0), pt(4, 10.0)])
    rels = res.baseline_relative(group_key=lambda p: p.nbytes,
                                 is_baseline=lambda p: p.streams == 1)
    import math
    assert all(math.isnan(r) for _, r in rels)   # anchored on the 0.0 point
    res2 = BenchResult(points=[pt(1, 5.0), pt(2, 10.0)])
    rels2 = dict(res2.baseline_relative(group_key=lambda p: p.nbytes,
                                        is_baseline=lambda p: p.streams == 1))
    assert rels2[pt(2, 10.0)] == pytest.approx(2.0)


def test_time_fn_warmup_zero():
    """warmup=0 must not crash (the UnboundLocalError on `out`): the first
    timed rep simply pays compilation."""
    import jax.numpy as jnp
    from repro.core import timing
    t = timing.time_fn(lambda: jnp.zeros(8), reps=2, warmup=0,
                       bytes_per_call=1.0)
    assert len(t.times_s) == 2 and t.mean_s > 0


def test_spec_warmup_zero_end_to_end():
    """BenchSpec validation allows warmup=0, so the Runner must run it."""
    spec = BenchSpec(mixes=("load_sum",), sizes=(16 * 2**10,), reps=2,
                     warmup=0, passes=1)
    (pt,) = Runner().run(spec).points
    assert pt.mean_s > 0 and pt.gbps > 0


@pytest.mark.parametrize("backend", ["xla", "pallas", "sharded"])
def test_sweep_releases_buffers(monkeypatch, backend):
    """A size sweep holds ONE working set at a time — earlier sizes' buffers
    are collectible while later sizes are being timed, not retained for the
    whole run (as the build-everything-up-front case list used to do), and
    the compiled-case cache never pins one either."""
    import gc
    import weakref
    from repro.bench.backends import get_backend
    from repro.core import buffers, timing
    refs = []
    real_ws = buffers.working_set

    def spy_ws(nbytes, **kw):
        x = real_ws(nbytes, **kw)
        refs.append(weakref.ref(x))
        return x

    # also track placed copies (sharded swaps the host buffer for a mesh one)
    be = get_backend(backend)
    real_prep = be.prepare_buffer

    def spy_prep(spec, x):
        y = real_prep(spec, x)
        refs.append(weakref.ref(y))
        return y

    peak = 0
    real_tf = timing.time_fn

    def spy_tf(fn, *a, **kw):
        nonlocal peak
        gc.collect()
        alive = {id(r()) for r in refs if r() is not None}
        peak = max(peak, len(alive))
        return real_tf(fn, *a, **kw)

    monkeypatch.setattr(buffers, "working_set", spy_ws)
    monkeypatch.setattr(be, "prepare_buffer", spy_prep)
    monkeypatch.setattr(timing, "time_fn", spy_tf)
    sizes = (16 * 2**10, 64 * 2**10, 256 * 2**10, 1 * 2**20)
    runner = Runner()
    runner.run(BenchSpec(mixes=("load_sum", "copy"), backend=backend,
                         sizes=sizes, reps=2, warmup=1, passes=1))
    assert len(refs) >= len(sizes)
    assert peak == 1, f"{peak} working sets live at once on {backend}"
    assert runner._cases            # cached cases outlive the buffers
    gc.collect()
    assert all(r() is None for r in refs)


def test_compiled_case_cache_hits():
    """Re-running a spec (or sweeping an unrelated knob) re-times cached
    kernels instead of re-tracing them."""
    r = Runner()
    base = BenchSpec(mixes=("load_sum",), **TINY)
    r.run(base)
    assert (r.cache_hits, r.cache_misses) == (0, 1)
    r.run(base)
    assert (r.cache_hits, r.cache_misses) == (1, 1)
    r.run_many([base, base.replace(streams=2)])   # streams=2 is a new case
    assert (r.cache_hits, r.cache_misses) == (2, 2)
    fresh = Runner()                               # cache is per-instance
    fresh.run(base)
    assert (fresh.cache_hits, fresh.cache_misses) == (0, 1)


def test_runner_compare_filters_mixes():
    out = Runner().compare(BenchSpec(mixes=("load_sum",), **TINY))
    assert set(out) == {"xla", "pallas"}
    for res in out.values():
        assert res.points[0].mix == "load_sum"


def test_runner_compare_filters_knob_conflicts():
    """streams=2 keeps load_sum on xla and drops copy instead of aborting."""
    spec = BenchSpec(mixes=("load_sum", "copy"), backend="pallas", streams=2,
                     sizes=(128 * 2**10,), reps=2, warmup=1, passes=1)
    out = Runner().compare(spec)
    assert [p.mix for p in out["xla"].points] == ["load_sum"]
    assert [p.mix for p in out["pallas"].points] == ["load_sum", "copy"]


def test_run_many_envelope_records_all_specs():
    base = BenchSpec(mixes=("load_sum",), **TINY)
    res = Runner().run_many([base.replace(streams=s) for s in (1, 2)])
    assert "many" in res.spec and len(res.spec["many"]) == 2
    assert {p.streams for p in res.points} == {1, 2}
    single = Runner().run_many([base])
    assert "many" not in single.spec   # one spec: plain envelope


def test_run_many_unions_meta_across_specs():
    """The merged envelope must describe ALL merged points — sizes/mixes are
    the union across specs, not results[0]'s lists."""
    a = BenchSpec(mixes=("load_sum",), **TINY)
    b = a.replace(mixes=("copy",), sizes=(64 * 2**10,))
    res = Runner().run_many([a, b])
    assert res.meta["sizes"] == [16 * 2**10, 64 * 2**10]
    assert res.meta["mixes"] == ["load_sum", "copy"]
    assert {p.mix for p in res.points} == {"load_sum", "copy"}
    # uniform dtype/reps stay scalar (the common knob sweep)
    assert res.meta["dtype"] == "float32" and res.meta["reps"] == a.reps


def test_run_many_unions_dtype_and_reps_when_specs_disagree():
    """results[0]'s scalar dtype/reps silently misdescribed a merge of
    disagreeing specs — they now union to first-seen-ordered lists."""
    a = BenchSpec(mixes=("load_sum",), **TINY)
    b = a.replace(dtype="bfloat16", reps=3)
    res = Runner().run_many([a, b])
    assert res.meta["dtype"] == ["float32", "bfloat16"]
    assert res.meta["reps"] == [a.reps, 3]
    # each point still carries its own knobs
    assert {p.dtype for p in res.points} == {"float32", "bfloat16"}
    assert {p.reps for p in res.points} == {a.reps, 3}


def test_by_size_resolves_requested_and_real_sizes():
    """working_set_shape rounds 50_000 B to whole (8, 128) f32 tiles;
    by_size(spec size) used to return [] for any rounded size."""
    spec = BenchSpec(mixes=("load_sum",), sizes=(50_000,), reps=2, warmup=1,
                     passes=1)
    res = Runner().run(spec)
    (p,) = res.points
    assert p.nbytes != 50_000 and p.nbytes_requested == 50_000
    assert res.by_size(50_000) == [p] == res.by_size(p.nbytes)
    # the envelope's sizes list (requested) now always resolves
    assert all(res.by_size(s) for s in res.meta["sizes"])


def test_summarize_band_and_meta_are_json_spec_compliant():
    """An unbounded band edge must serialize as null, not Infinity — JSON
    parsers outside Python reject non-finite literals."""
    res = Runner().run(BenchSpec(mixes=("load_sum",), **TINY))
    # an 8K L1 puts the 16K point in the unbounded DRAM band (lo = 16K)
    res.meta["summary"] = res.summarize(levels=(("L1", 8 * 2**10),
                                                ("DRAM", None)))
    summary = res.meta["summary"]
    assert summary["DRAM"]["load_sum"]["band"] == (16 * 2**10, None)
    # belt and suspenders: even a raw inf/nan stashed into meta serializes
    # as null rather than emitting non-JSON "Infinity"/"NaN" literals
    res.meta["raw"] = {"inf": float("inf"), "nan": float("nan")}
    text = res.to_json()
    assert "Infinity" not in text and "NaN" not in text
    back = json.loads(text)
    assert back["meta"]["summary"]["DRAM"]["load_sum"]["band"][1] is None
    assert back["meta"]["raw"] == {"inf": None, "nan": None}


def test_compare_records_skipped():
    """compare must not drop mixes/backends silently: every skipped
    (backend, mix) pair lands in meta['skipped'] with its reason."""
    spec = BenchSpec(mixes=("load_sum", "copy"), backend="pallas", streams=2,
                     sizes=(128 * 2**10,), reps=2, warmup=1, passes=1)
    out = Runner().compare(spec)
    sk = out["xla"].meta["skipped"]
    assert [m for m, _ in sk["xla"]] == ["copy"]       # streams>1 on copy
    assert "streams" in sk["xla"][0][1]
    assert all(res.meta["skipped"] == sk for res in out.values())


def test_compare_raises_when_nothing_runnable():
    """A comparison where every backend is skipped raises with the skip map
    instead of returning an empty dict."""
    spec = BenchSpec(mixes=("load_only",), backend="pallas", **TINY)
    with pytest.raises(BenchSpecError, match="load_only"):
        Runner().compare(spec, backends=("xla",))


def test_cli_compare_prints_skipped(capsys):
    from repro.bench import cli
    rc = cli.main(["compare", "--mixes", "load_sum,copy", "--streams", "2",
                   "--sizes", "128K", "--reps", "2"])
    assert rc == 0
    cap = capsys.readouterr()
    assert "# skipped xla/copy:" in cap.out


def test_spec_devices_roundtrip_and_v1_backcompat():
    s = BenchSpec(mixes=("load_sum",), backend="sharded", devices=1, **TINY)
    d = json.loads(s.to_json())
    assert d["spec_version"] == 5 and d["devices"] == 1
    assert "interpret" not in d
    assert BenchSpec.from_dict(d) == s
    old = {k: v for k, v in d.items()
           if k not in ("devices", "unroll", "interleave")}  # a v1 spec file
    old["spec_version"] = 1
    old["interpret"] = True     # retired in v5: the platform decides
    assert BenchSpec.from_dict(old) == s
    assert BenchSpec.from_dict(old).devices == 1
    assert BenchSpec.from_dict(old).unroll == 1
    assert BenchSpec.from_dict(old).interleave == 1


def test_result_v1_backcompat_defaults_devices():
    pt = dict(nbytes=1024, mix="load_sum", dtype="float32", backend="xla",
              passes=1, streams=1, block_rows=None, reps=1,
              bytes_per_call=1024.0, flops_per_call=0.0, mean_s=1e-3,
              std_s=0.0, min_s=1e-3, gbps=1.0, gflops=0.0)
    res = BenchResult.from_dict({"schema_version": 1, "points": [pt]})
    assert res.points[0].devices == 1
    assert res.schema_version == 1


def test_custom_backend_registration_usable():
    from repro.bench.backends import _BACKENDS, register_backend
    import jax.numpy as jnp

    class EchoBackend:
        name = "echo-test"

        def supports(self, mix):
            return mix.name == "load_sum"

        def validate(self, spec):
            pass

        def build(self, spec, mix, x, passes):
            return lambda: jnp.sum(x)

    register_backend(EchoBackend())
    try:
        spec = BenchSpec(mixes=("load_sum",), backend="echo-test", **TINY)
        (pt,) = Runner().run(spec).points
        assert pt.backend == "echo-test" and pt.mean_s > 0
        with pytest.raises(BenchSpecError):   # support set still enforced
            BenchSpec(mixes=("copy",), backend="echo-test", **TINY)
    finally:
        _BACKENDS.pop("echo-test", None)


def test_fma_family_open_ended():
    """Any fma_k depth is a valid mix with synthesized accounting (the
    registry lists only the canonical ladder)."""
    m = get_mix("fma_3")
    assert m.flops_per_elem == 6.0 and m.fma_depth == 3
    assert "fma_3" not in registry()
    with pytest.raises(KeyError):
        get_mix("fma_zz")
    (pt,) = Runner().run(BenchSpec(mixes=("fma_3",), **TINY)).points
    assert pt.flops_per_call == 6.0 * (pt.nbytes / 4)


def test_pallas_explicit_block_rows_never_clamped():
    """An explicit block_rows that doesn't fit the buffer errors (on both
    backends) rather than being silently adjusted and mis-recorded."""
    with pytest.raises(BenchSpecError):
        Runner().run(BenchSpec(mixes=("load_sum",), backend="pallas",
                               block_rows=512, **TINY))


def test_legacy_mixes_restricts_fma_depths():
    from repro.core.instruction_mix import mixes
    got = sorted(mixes(fma_depths=(2,)))
    assert got == ["copy", "fma_2", "load_sum", "mxu", "triad"]


# ---------------------------------------------------------------------------
# legacy sweep wrapper + CLI
# ---------------------------------------------------------------------------

def test_legacy_run_sweep_routes_through_runner():
    from repro.core import sweep
    res = sweep.run_sweep(sizes=[16 * 2**10], mix_names=["load_sum"], reps=2,
                          target_bytes=1e6)
    assert isinstance(res, sweep.SweepResult)
    assert res.points[0].mix == "load_sum" and res.points[0].gbps > 0
    assert res.meta["mixes"] == ["load_sum"]


def test_cli_run_and_list(tmp_path, capsys):
    from repro.bench import cli
    out = tmp_path / "r.json"
    rc = cli.main(["run", "--quick", "--sizes", "16K", "--reps", "2",
                   "--out", str(out)])
    assert rc == 0
    d = json.loads(out.read_text())
    assert d["schema_version"] == SCHEMA_VERSION and d["points"]
    assert cli.main(["list-mixes"]) == 0
    cap = capsys.readouterr()
    assert "load_only" in cap.out and "triad" in cap.out


def test_cli_compare(capsys):
    from repro.bench import cli
    rc = cli.main(["compare", "--mixes", "load_sum", "--sizes", "16K",
                   "--reps", "2"])
    assert rc == 0
    cap = capsys.readouterr()
    assert "load_sum" in cap.out and "mismatch" not in cap.out
