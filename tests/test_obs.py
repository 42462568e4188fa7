"""repro.obs: span tracer, metrics registry, run ledger, regression gate.

The expensive part (one real traced Runner run) happens once in a module
fixture; everything trace-shaped asserts against those events, everything
ledger-shaped against that result.  CLI behaviors (overwrite refusal,
history/diff exit codes) go through ``cli.main`` in-process.
"""
import dataclasses
import json
import math

import pytest

from repro.bench import BenchSpec, Runner
from repro.bench.result import REP_SAMPLE_LIMIT, BenchResult
from repro.obs import ledger, metrics, trace
from repro.obs.trace import (Tracer, merge_process_traces, span_coverage,
                             validate_chrome)


@pytest.fixture(autouse=True)
def _tracer_reset():
    """CLI --trace enables the global tracer; never leak that into the
    next test (the zero-overhead test asserts it is OFF)."""
    yield
    trace.configure(enabled=False, clear=True)


# ---------------------------------------------------------------------------
# tracer unit behavior (private Tracer instances — no global state)
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_balance():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner2", cat="x", knob=3):
            pass
    evs = tr.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["args"]["depth"] == 0
    assert by_name["inner"]["args"]["depth"] == 1
    assert by_name["inner2"]["args"]["knob"] == 3
    # children close before the parent -> appear first, contained inside
    o, i = by_name["outer"], by_name["inner"]
    assert i["ts"] >= o["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]


def test_span_balanced_under_exception():
    tr = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("boom"):
                raise RuntimeError("body failed")
    evs = tr.events()
    assert [e["name"] for e in evs] == ["boom", "outer"]
    assert evs[0]["args"]["error"] == "RuntimeError"
    assert evs[1]["args"]["error"] == "RuntimeError"
    # the stack is balanced: a new span starts at depth 0 again
    with tr.span("after"):
        pass
    assert tr.events()[-1]["args"]["depth"] == 0


def test_disabled_tracer_is_allocation_free_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b", cat="x", big=list(range(10)))
    assert s1 is s2                     # the shared _NULL_SPAN singleton
    with s1:
        pass
    tr.event("e")
    assert tr.events() == []


def test_timed_path_never_touches_spans_when_disabled(monkeypatch):
    """The zero-overhead guarantee: with tracing off, ``time_fn`` must run
    the original untraced loop, and a backend's compiled case must call
    straight through its ``case.dispatch`` wrapper — a span() call anywhere
    in either would raise here."""
    import jax.numpy as jnp

    from repro.bench.backends import get_backend
    from repro.bench.mixes import get_mix
    from repro.core import timing

    def explode(*a, **k):
        raise AssertionError("span() called on the disabled timed path")

    assert not trace.get_tracer().enabled
    monkeypatch.setattr(Tracer, "span", explode)
    monkeypatch.setattr(Tracer, "event", explode)
    x = jnp.ones((8, 8))
    t = timing.time_fn(lambda: x + 1, reps=3, warmup=1, bytes_per_call=1.0)
    assert len(t.times_s) == 3
    spec = BenchSpec(mixes=("load_sum",), sizes=(64 * 2**10,), passes=2)
    mix = get_mix("load_sum")
    backend = get_backend("xla")
    buf = jnp.ones((128, 128), jnp.float32)
    case = backend.make_case(spec, mix, buf.shape, buf.dtype, 2)
    fn = backend.bind_case(case, spec, mix, buf)
    t = timing.time_fn(fn, reps=3, warmup=1, bytes_per_call=1.0)
    assert len(t.times_s) == 3


def test_spans_land_on_the_profiler_trace(tmp_path):
    """With the tracer on under a JAX profiler trace, the spans of a bound
    pallas and xla case and of one ``Runner.run`` are written to the host
    line that holds the caller's own ``TraceAnnotation``, inside it, with
    their args as metadata: the device trace's clock names them."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.bench.backends import get_backend
    from repro.bench.mixes import get_mix

    mix = get_mix("load_sum")
    buf = jnp.ones((128, 128), jnp.float32)
    calls = []
    for name in ("pallas", "xla"):
        spec = BenchSpec(mixes=("load_sum",), sizes=(64 * 2**10,),
                         backend=name, passes=2)
        backend = get_backend(name)
        case = backend.make_case(spec, mix, buf.shape, buf.dtype, 2)
        calls.append(backend.bind_case(case, spec, mix, buf))
    for fn in calls:
        jax.block_until_ready(fn())         # compiled before the trace
    run_spec = BenchSpec(mixes=("copy",), sizes=(64 * 2**10,), passes=2,
                         reps=2, warmup=1)
    trace.configure(enabled=True, clear=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.window"):
            for fn in calls:
                jax.block_until_ready(fn())
            Runner().run(run_spec)
    finally:
        jax.profiler.stop_trace()
        trace.configure(enabled=False)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    lines = [list(line.events)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    (line,) = [evs for evs in lines
               if any(ev.name == "test.window" for ev in evs)]
    (lo, hi) = [(ev.start_ns, ev.end_ns) for ev in line
                if ev.name == "test.window"][0]
    spans = {}
    for ev in line:
        if ev.name in ("case.dispatch", "runner.run", "buffers.build"):
            assert lo <= ev.start_ns <= ev.end_ns <= hi, ev.name
            stats = dict(ev.stats) if ev.name == "case.dispatch" else {}
            spans.setdefault(ev.name, []).append(
                (ev.start_ns, ev.end_ns, stats))
    assert len(spans["runner.run"]) == len(spans["buffers.build"]) == 1
    # two direct calls + the Runner's warm-up and reps, each dispatched
    dispatch = spans["case.dispatch"]
    assert len(dispatch) == 2 + run_spec.warmup + run_spec.reps
    assert {d[2]["backend"] for d in dispatch} == {"pallas", "xla"}
    (run_lo, run_hi, _), = spans["runner.run"]
    assert sum(run_lo <= s <= e <= run_hi for s, e, _ in dispatch) == 3


def test_timing_samples_bounded():
    from repro.core.timing import TimingResult
    t = TimingResult(times_s=[float(i + 1) for i in range(100)])
    assert t.samples(10) == tuple(float(i + 1) for i in range(90, 100))
    assert len(t.samples()) == 100
    # the (mean, std, min) triple still covers ALL reps
    assert t.mean_s == pytest.approx(50.5)


def test_merge_process_traces_restamps_and_orders():
    def ev(name, ts, pid):
        return {"name": name, "cat": "c", "ph": "X", "ts": ts, "dur": 1.0,
                "pid": pid, "tid": 1, "args": {"depth": 0}}
    # per-process streams with colliding OS pids and interleaved timestamps
    p0 = [ev("a", 0.0, 9999), ev("b", 5.0, 9999)]
    p1 = [ev("c", 1.0, 9999), ev("d", 5.0, 9999)]
    merged = merge_process_traces([p0, p1])
    assert [e["pid"] for e in merged] == [0, 1, 0, 1]
    assert [e["name"] for e in merged] == ["a", "c", "b", "d"]
    assert [e["ts"] for e in merged] == sorted(e["ts"] for e in merged)
    # inputs are not mutated (the gather reuses local event lists)
    assert p0[0]["pid"] == 9999


def test_validate_chrome_catches_malformed_events():
    ok = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
                           "pid": 1, "tid": 1}]}
    assert validate_chrome(ok) == []
    assert validate_chrome({}) == ["traceEvents missing or not a list"]
    assert validate_chrome({"traceEvents": [{"ph": "X"}]})
    bad_dur = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                                "pid": 1, "tid": 1}]}
    assert any("dur" in p for p in validate_chrome(bad_dur))
    bad_ph = {"traceEvents": [{"name": "a", "ph": "?", "ts": 0.0,
                               "pid": 1, "tid": 1}]}
    assert any("phase" in p for p in validate_chrome(bad_ph))


def test_trace_write_formats(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("s"):
        tr.event("e")
    chrome = tr.write(tmp_path / "t.json")
    doc = json.loads(chrome.read_text())
    assert validate_chrome(doc) == []
    assert doc["metadata"]["trace_format"] == trace.TRACE_FORMAT
    lines = tr.write(tmp_path / "t.jsonl").read_text().splitlines()
    head = json.loads(lines[0])
    assert head["trace_format"] == trace.TRACE_FORMAT
    assert [json.loads(ln)["name"] for ln in lines[1:]] == ["e", "s"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_scope_delta_and_merge():
    reg = metrics.MetricsRegistry()
    reg.inc("pre", 5)
    with reg.scope() as scope:
        reg.inc("hits")
        reg.inc("hits")
        reg.gauge_max("peak", 10)
        reg.gauge_max("peak", 4)        # high-water: ignored
        delta = scope.delta()
    assert delta == {"counters": {"hits": 2}, "gauges": {"peak": 10}}
    assert reg.snapshot()["counters"]["pre"] == 5
    merged = metrics.merge_obs([
        {"counters": {"a": 1}, "gauges": {"g": 5}, "runner": {"x": 1}},
        {"counters": {"a": 2, "b": 1}, "gauges": {"g": 3},
         "runner": {"x": 4}},
    ])
    assert merged == {"counters": {"a": 3, "b": 1}, "gauges": {"g": 5},
                      "runner": {"x": 4}}


# ---------------------------------------------------------------------------
# one real traced run — trace/result/obs agreement, the ledger's input
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    tr = trace.configure(enabled=True, clear=True)
    try:
        res = Runner().run(BenchSpec(
            mixes=("copy", "load_sum"), sizes=(64 * 2**10, 256 * 2**10),
            passes=4, reps=3, warmup=1))
        events = tr.events()
    finally:
        trace.configure(enabled=False, clear=True)
    return events, res


def test_traced_run_chrome_valid_and_covered(traced_run):
    events, _ = traced_run
    doc = {"traceEvents": events, "metadata": {}}
    assert validate_chrome(doc) == []
    # the acceptance bar: phase spans account for >= 95% of runner.run
    assert span_coverage(events) >= 0.95
    names = {e["name"] for e in events}
    assert {"runner.run", "runner.plan", "runner.size", "buffers.build",
            "runner.case", "timing.warmup", "timing.rep", "case.build",
            "cache", "case.dispatch", "buffers.release"} <= names


def test_obs_counters_match_trace_events(traced_run):
    events, res = traced_run
    obs = res.meta["obs"]
    cache = [e for e in events if e["name"] == "cache"]
    hits = sum(e["args"]["outcome"] == "hit" for e in cache)
    misses = sum(e["args"]["outcome"] == "miss" for e in cache)
    assert obs["counters"].get("cache_hits", 0) == hits
    assert obs["counters"].get("cache_misses", 0) == misses == 4
    builds = sum(e["name"] == "buffers.build" for e in events)
    releases = sum(e["name"] == "buffers.release" for e in events)
    assert obs["counters"]["buffers_built"] == builds == 2
    assert obs["counters"]["buffers_released"] == releases == 2
    assert obs["gauges"]["peak_working_set_bytes"] == 256 * 2**10
    assert obs["runner"] == {"cache_hits": 0, "cache_misses": 4}


def test_rep_samples_on_points_roundtrip(traced_run):
    _, res = traced_run
    for p in res.points:
        assert p.rep_times_s is not None
        assert len(p.rep_times_s) == min(p.reps, REP_SAMPLE_LIMIT)
        assert all(t > 0 for t in p.rep_times_s)
    back = BenchResult.from_dict(json.loads(res.to_json()))
    assert back.points == res.points
    assert back.meta["obs"] == res.meta["obs"]


def test_points_with_rep_samples_stay_hashable(traced_run):
    """rep_times_s must canonicalize to a tuple on EVERY construction path
    (runner, from_dict, literal list): the frozen point is grouped in dicts
    by baseline_relative, and a list field breaks __hash__ — caught live by
    fig1 --quick, pinned here."""
    _, res = traced_run
    for p in res.points:
        assert isinstance(p.rep_times_s, tuple)
        hash(p)
    back = BenchResult.from_dict(json.loads(res.to_json()))
    assert all(isinstance(p.rep_times_s, tuple) for p in back.points)
    listy = dataclasses.replace(res.points[0], rep_times_s=[1e-3, 2e-3])
    assert listy.rep_times_s == (1e-3, 2e-3) and hash(listy) is not None
    rel = dict(res.baseline_relative(group_key=lambda p: p.nbytes))
    assert len(rel) == len(res.points)


# ---------------------------------------------------------------------------
# ledger + regression gate
# ---------------------------------------------------------------------------

def test_ledger_roundtrip_and_refs(traced_run, tmp_path):
    _, res = traced_run
    root = tmp_path / "hist"
    path, rec = ledger.append_record(res, cmd="run", root=root)
    assert path == root / "ledger.jsonl"
    assert (root / "VERSION").read_text().strip() == str(
        ledger.LEDGER_VERSION)
    assert rec["schema_version"] == res.schema_version
    assert len(rec["curves"]) == 4          # 2 mixes x 2 sizes
    for cell in rec["curves"]:
        assert cell["gbps"] > 0 and cell["n"] == 3
        assert cell["log_sigma"] >= 0
    records = ledger.read_ledger(root)
    assert records == [rec]
    # every accepted reference form resolves to the same record
    assert ledger.resolve_ref(-1, root=root) == rec
    assert ledger.resolve_ref("latest", root=root) == rec
    assert ledger.resolve_ref(rec["spec_digest"][:6], root=root) == rec
    out = tmp_path / "res.json"
    res.to_json(out)
    from_file = ledger.resolve_ref(str(out), root=root)
    assert [c["gbps"] for c in from_file["curves"]] == \
        [c["gbps"] for c in rec["curves"]]
    with pytest.raises(ValueError, match="cannot resolve"):
        ledger.resolve_ref("zzzz", root=root)
    with pytest.raises(ValueError, match="out of range"):
        ledger.resolve_ref(5, root=root)


def test_ledger_refuses_newer_version(tmp_path):
    root = tmp_path / "hist"
    root.mkdir()
    (root / "VERSION").write_text(f"{ledger.LEDGER_VERSION + 1}\n")
    with pytest.raises(ValueError, match="newer than supported"):
        ledger.append_record({"ledger_version": ledger.LEDGER_VERSION},
                             root=root)


def test_diff_self_is_identical_exit_0(traced_run, tmp_path):
    _, res = traced_run
    _, rec = ledger.append_record(res, root=tmp_path / "h")
    report = ledger.diff_records(rec, rec)
    assert report.identical and report.exit_code() == 0
    assert not report.regressions and not report.improvements


def test_diff_flags_real_drop_exit_2(traced_run, tmp_path):
    _, res = traced_run
    _, rec = ledger.append_record(res, root=tmp_path / "h")
    # pin the noise term: the traced fixture run uses 3 reps, whose measured
    # scatter can legitimately absorb even a 2x step (that behavior has its
    # own test below) — here the subject is the verdict/exit-code plumbing
    for cell in rec["curves"]:
        cell["log_sigma"] = 0.02
    slower = json.loads(json.dumps(rec))
    for cell in slower["curves"]:
        cell["gbps"] /= 1.5
    report = ledger.diff_records(rec, slower)
    assert report.exit_code() == 2
    assert len(report.regressions) == len(rec["curves"])
    for row in report.rows:
        assert row["verdict"] == "regression"
        assert row["ratio"] == pytest.approx(1 / 1.5)
    # the reverse direction is an improvement, not a regression
    back = ledger.diff_records(slower, rec)
    assert back.exit_code() == 0
    assert len(back.improvements) == len(rec["curves"])


def test_diff_noise_floor_absorbs_small_wobble(traced_run, tmp_path):
    _, res = traced_run
    _, rec = ledger.append_record(res, root=tmp_path / "h")
    wobble = json.loads(json.dumps(rec))
    for cell in wobble["curves"]:
        cell["gbps"] *= 0.97            # -3%: inside the 5% tolerance floor
    report = ledger.diff_records(rec, wobble, tolerance=0.05)
    assert report.exit_code() == 0 and not report.regressions
    # ... but a tight-tolerance, huge-sigma cell still needs z*sigma cleared
    noisy = json.loads(json.dumps(rec))
    for cell in noisy["curves"]:
        cell["gbps"] /= 1.10
        cell["log_sigma"] = 1.0         # per-rep scatter dwarfs the 10% drop
    report = ledger.diff_records(rec, noisy, tolerance=0.01)
    assert report.exit_code() == 0


def test_diff_reports_missing_and_added_cells(traced_run, tmp_path):
    _, res = traced_run
    _, rec = ledger.append_record(res, root=tmp_path / "h")
    shrunk = json.loads(json.dumps(rec))
    moved = shrunk["curves"].pop()
    extra = dict(moved, nbytes=moved["nbytes"] * 2)
    shrunk["curves"].append(extra)
    report = ledger.diff_records(rec, shrunk)
    assert len(report.missing) == 1 and len(report.added) == 1
    assert report.exit_code() == 0      # coverage drift is visible, not fatal


def test_cell_stats_sigma_from_samples(traced_run):
    _, res = traced_run
    rec = ledger.record_from_result(res)
    # log_sigma must come from the retained per-rep samples via the
    # MAD-robust scale (per point, then RMS across a cell's points):
    from collections import defaultdict
    import statistics
    by_key = defaultdict(list)
    for p in res.points:
        by_key[tuple(getattr(p, k, None) for k in ledger.CELL_KEY)].append(p)
    for cell in rec["curves"]:
        pts = by_key[tuple(cell[k] for k in ledger.CELL_KEY)]
        var = 0.0
        for p in pts:
            logs = [math.log(t) for t in p.rep_times_s]
            med = statistics.median(logs)
            mad = statistics.median(abs(x - med) for x in logs)
            var += (1.4826 * mad) ** 2
        want = math.sqrt(var / len(pts))
        assert cell["log_sigma"] == pytest.approx(want)


def test_cell_stats_sigma_robust_to_cold_rep():
    """A single 5x cold first rep must not deaden the gate: the MAD scale
    stays near the tight cluster's spread, not the outlier's."""
    from repro.bench.result import BenchPoint
    base = dict(mix="copy", nbytes=2**16, dtype="float32", backend="xla",
                passes=4, streams=1, block_rows=None, reps=5,
                bytes_per_call=2 * 2**16, flops_per_call=0,
                mean_s=1.2e-3, std_s=1e-3, min_s=6e-4, gbps=10.0, gflops=0.0)
    p = BenchPoint(**base, rep_times_s=(3.0e-3, 6.0e-4, 6.1e-4, 5.9e-4, 6.0e-4))
    cell = ledger._cell_stats([p])
    assert cell["log_sigma"] < 0.05   # plain std would be ~0.7
    # and with that sigma, a 1.5x drop at n=5 is well above the noise gate
    from repro.characterize.detect import significant_step
    assert significant_step(math.log(10.0), 5, math.log(10.0 / 1.5), 5,
                            sigma=cell["log_sigma"], z=3.0, min_drop=0.05)


# ---------------------------------------------------------------------------
# CLI: overwrite refusal, history, diff
# ---------------------------------------------------------------------------

def test_cli_refuses_silent_overwrite(tmp_path, capsys):
    from repro.bench.cli import main
    out = tmp_path / "r.json"
    out.write_text("{}")            # pre-existing artifact
    rc = main(["run", "--quick", "--mixes", "copy", "--sizes", "64K",
               "--reps", "2", "--out", str(out)])
    assert rc == 2
    assert "refusing to overwrite" in capsys.readouterr().err
    assert out.read_text() == "{}"          # untouched


def test_cli_force_overwrites_and_traces(tmp_path, capsys, monkeypatch):
    from repro.bench.cli import main
    monkeypatch.setenv(ledger.LEDGER_ENV, str(tmp_path / "hist"))
    out, tpath = tmp_path / "r.json", tmp_path / "t.json"
    out.write_text("{}")
    rc = main(["run", "--quick", "--mixes", "copy", "--sizes", "64K",
               "--reps", "2", "--out", str(out), "--force",
               "--trace", str(tpath)])
    assert rc == 0
    capsys.readouterr()
    res = json.loads(out.read_text())
    assert res["schema_version"] == 6 and res["meta"]["obs"]
    doc = json.loads(tpath.read_text())
    assert validate_chrome(doc) == []
    assert span_coverage(doc["traceEvents"]) >= 0.95
    # the run auto-appended a ledger record pointing at both artifacts
    [rec] = ledger.read_ledger()
    assert rec["cmd"] == "run"
    assert rec["out"] == str(out) and rec["trace"] == str(tpath)


def test_cli_history_and_diff_exit_codes(traced_run, tmp_path, capsys):
    from repro.bench.cli import main
    _, res = traced_run
    root = str(tmp_path / "hist")
    rfile = tmp_path / "res.json"
    res.to_json(rfile)
    assert main(["history", "--history-root", root]) == 0
    assert "empty ledger" in capsys.readouterr().out
    assert main(["history", "--add", str(rfile),
                 "--history-root", root]) == 0
    out = capsys.readouterr().out
    assert "ledger +=" in out and "copy,load_sum" in out
    # self-diff: exit 0
    assert main(["diff", "--baseline", "-1", "--history-root", root]) == 0
    capsys.readouterr()
    # perturbed baseline: every cell regresses, exit 2 (sigma pinned small —
    # the noise-absorption behavior is unit-tested elsewhere)
    rec = ledger.read_ledger(root)[0]
    for cell in rec["curves"]:
        cell["log_sigma"] = 0.02
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(rec))
    fast = json.loads(json.dumps(rec))
    for cell in fast["curves"]:
        cell["gbps"] *= 2.0
    fastp = tmp_path / "fast.json"
    fastp.write_text(json.dumps(fast))
    rc = main(["diff", "--baseline", str(fastp), "--current", str(cur),
               "--history-root", root])
    assert rc == 2
    captured = capsys.readouterr()
    assert "regression" in captured.out and "regression" in captured.err
    # unresolvable ref -> the CLI's uniform error exit, not a traceback
    assert main(["diff", "--baseline", "zzzz",
                 "--history-root", root]) == 2


def test_cli_no_ledger_skips_append(tmp_path, capsys, monkeypatch):
    from repro.bench.cli import main
    monkeypatch.setenv(ledger.LEDGER_ENV, str(tmp_path / "hist"))
    rc = main(["run", "--quick", "--mixes", "copy", "--sizes", "64K",
               "--reps", "2", "--no-ledger"])
    assert rc == 0
    capsys.readouterr()
    assert ledger.read_ledger() == []
