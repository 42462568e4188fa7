"""The general harness: set up one cell of ``BENCHMARK.json``, warm it, drive
its timed call back to back for ``--seconds``, check what it produced
against the plain reference, and print the result line.

Everything that belongs to one configuration, traffic mix or metric sits in
files of its own, found by name:

- ``configs/<config>.json``: the sizes and type of the working set;
- ``traffic/<traffic>.json``: the parameters of the traffic mix, naming
  its ``driver`` and the limits of the numbers its check compares;
- ``drivers/<driver>.py``: ``setup(config, traffic, seed)``, which returns
  a session with ``warm()``, ``call()``, ``work``, ``release()``,
  ``products(outs)``, ``reference(precision)`` and ``compare(...)``; a
  session whose ``work`` has ``kernel_calls`` runs that many Pallas kernels
  a call, and a traced run counts them;
- ``reference/<mix>.py``: the plain reference of one mix;
- ``metrics/<metric>.py``: ``read(ctx)``, the value of one metric or None
  where the run has nothing for it to read; ``<metric>.<part>`` is read by
  ``metrics/<metric>.py`` where it has no file of its own.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def start_process() -> None:
    """Before JAX is imported: the program on the path, and JAX's persistent
    compilation cache at the fixed ``.jax_cache/`` of this checkout, so that
    only a cell's first run there compiles.  Eviction stays off: it keeps
    an access-time file beside each entry, and a cell's programs are few."""
    root = BENCH_DIR.parent
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    # the TPU runtime's logs stay off the host's shared /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(root / "src")]
    from repro.bench import compile_cache
    compile_cache.enable()


#: the precision the reference is computed in, and the one below the
#: configuration's float32 that the control uses
REFERENCE, CONTROL = "float64", "bfloat16"


def scalar_type(precision: str):
    """The NumPy scalar type of ``precision`` (host arithmetic in it)."""
    import ml_dtypes
    import numpy as np
    return {"float64": np.float64, "float32": np.float32,
            "bfloat16": ml_dtypes.bfloat16}[precision]


def rel_gap(got, want) -> float:
    """The largest gap between an output and the reference's, element by
    element, as a share of the reference element: 0 for a bit-exact
    output.  Arrays are compared where they are (on the device)."""
    import jax.numpy as jnp
    if getattr(want, "ndim", 0):
        return float(jnp.max(jnp.abs(got - want) / jnp.abs(want)))
    return abs(float(got) - want) / abs(want)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path


def _load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload`` in ``BENCHMARK.json`` beside
    ``bench_dir``, with its configuration and traffic files."""
    root = bench_dir.parent
    doc = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in doc["configs"]}
    e2e = [m for m in doc["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in doc["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=workload, chips=int(w["chips"]),
                config=_load_json(root / configs[w["config"]]["file"]),
                traffic=_load_json(bench_dir / "traffic"
                                   / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def setup_session(cell: Cell, seed: int):
    driver = load_module(cell.bench_dir / "drivers"
                         / f"{cell.traffic['driver']}.py")
    return driver.setup(cell.config, cell.traffic, seed, cell.bench_dir)


def reference_module(bench_dir: Path, mix: str):
    return load_module(bench_dir / "reference" / f"{mix}.py")


def peaks(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _load_json(bench_dir / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def require_chips(chips: int) -> list:
    """The devices of the cell; raises NoChip off a TPU or short of chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


class CompileCounter:
    """Counts JAX traces, lowerings and backend compiles from its
    monitoring events (the discipline of ``chip_smoke.CompileClock``)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in self.EVENTS:
            self.count += 1


@dataclass
class Window:
    """The timed calls of one run: host-clock start and end of each, and
    what each returned."""
    starts: list[float] = field(default_factory=list)
    ends: list[float] = field(default_factory=list)
    outs: list = field(default_factory=list)
    traced_calls: int = 0

    @property
    def calls(self) -> int:
        return len(self.ends)

    @property
    def span_s(self) -> float:
        """From the first call's start to the last call's end."""
        return self.ends[-1] - self.starts[0]

    def summary(self) -> dict:
        """The calls and their wall times, in ms: least, median, 95th
        percentile, most.  A run that reads far off shows here whether
        every call was slower or a few stalled."""
        import statistics
        ms = sorted((e - s) * 1e3 for s, e in zip(self.starts, self.ends))
        p95 = ms[min(len(ms) - 1, int(0.95 * len(ms)))]
        return {"calls": len(ms), "span_s": self.span_s,
                "call_ms": [ms[0], statistics.median(ms), p95, ms[-1]]}


def _timed(session, window: Window, annotate=None) -> None:
    if annotate is None:
        t0 = time.perf_counter()
        out = session.call()
    else:
        with annotate("perfbench.call"):
            t0 = time.perf_counter()
            out = session.call()
    window.starts.append(t0)
    window.ends.append(time.perf_counter())
    window.outs.append(out)


def drive(session, seconds: float, trace_dir: Path | None = None,
          trace_seconds: float = 0.0) -> Window:
    """Call the session back to back until ``seconds`` have passed.  With a
    ``trace_dir``, the first ``trace_seconds`` of the window (one call at
    least) run under the profiler inside a ``perfbench.window`` span, with
    ``repro.obs`` spans on, and the rest untraced."""
    window = Window()
    t_start = time.perf_counter()
    if trace_dir is not None:
        import jax
        from repro.obs import trace as obs_trace
        obs_trace.configure(enabled=True, clear=True)
        # host spans are kept, Python's own function calls are not: the
        # Python tracer slows the host enough to widen every idle gap
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("perfbench.window"):
                t_traced = time.perf_counter()
                while True:
                    _timed(session, window, jax.profiler.TraceAnnotation)
                    if window.ends[-1] - t_traced >= min(trace_seconds,
                                                         seconds):
                        break
        finally:
            jax.profiler.stop_trace()
            obs_trace.configure(enabled=False)
        window.traced_calls = window.calls
    while not window.ends or window.ends[-1] - t_start < seconds:
        _timed(session, window)
    return window


@dataclass
class Context:
    """What a metric's reader may read."""
    cell: Cell
    session: object
    window: Window
    setup_s: float
    peaks: dict
    trace: object = None         # trace_reduce.Reduced of the traced runs
    spans: list | None = None    # repro.obs events of the traced window


def reader_path(bench_dir: Path, name: str) -> Path:
    """``metrics/<name>.py``, else ``metrics/<stem>.py`` for a name
    ``<stem>.<part>`` (one quantity split by the metric it moves)."""
    own = bench_dir / "metrics" / f"{name}.py"
    return own if own.exists() else (bench_dir / "metrics"
                                     / f"{name.split('.')[0]}.py")


def read_metrics(metrics: list[dict], ctx: Context) -> dict:
    out = {}
    for m in metrics:
        reader = load_module(reader_path(ctx.cell.bench_dir, m["name"]))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_block(devices, trace=None) -> dict:
    import jax
    stats = [d.memory_stats() or {} for d in devices]
    block = {"platform": devices[0].platform,
             "kind": devices[0].device_kind,
             "count": len(jax.devices()),
             "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                      for s in stats)}
    if trace is not None:
        block["busy_s"] = trace.busy_s()
        block["window_s"] = trace.window_s
    return block


def kernel_event_gap(red, calls: int, per_call: int) -> int:
    """How far the Pallas kernel's events in the trace are from
    ``per_call`` for each of the traced ``calls``: fewer is work left out,
    more is work the accounting does not see."""
    from perfbench.trace_reduce import PALLAS_KERNEL
    return abs(calls * per_call - len(red.matching(PALLAS_KERNEL)))


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_process: float, bench_dir: Path = BENCH_DIR,
        chips_required: bool = True) -> dict:
    """One run of one cell; returns the result object (its last key is
    ``checks``).  ``chips_required=False`` lets a test drive the run on
    whatever JAX has."""
    from perfbench import trace_reduce
    cell = load_cell(workload, bench_dir)
    import jax
    marks = [time.perf_counter()]
    devices = require_chips(cell.chips) if chips_required else jax.devices()
    devices = devices[:cell.chips]
    counter = CompileCounter()
    marks.append(time.perf_counter())
    session = setup_session(cell, seed)
    marks.append(time.perf_counter())
    session.warm()
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t_process
    # where set-up went: imports, TPU start-up, inputs and build, warm-up
    setup_split = dict(zip(("imports", "devices", "session", "warm"),
                           (b - a for a, b in zip([t_process] + marks,
                                                  marks))))

    trace_dir = None
    if traced:
        trace_dir = bench_dir.parent / ".perfbench_trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles_before = counter.count
    window = drive(session, seconds, trace_dir,
                   float(cell.traffic.get("trace_seconds", 2.0)))
    compiles = counter.count - compiles_before
    red = spans = None
    if traced:
        from repro.obs import trace as obs_trace
        red = trace_reduce.reduce(trace_reduce.trace_file(trace_dir))
        if chips_required and len(red.devices) < len(devices):
            raise RuntimeError(f"the trace holds {len(red.devices)} TPU "
                               f"planes for {len(devices)} chips")
        spans = obs_trace.get_tracer().events()
    device = device_block(devices, red)

    session.release()
    limits = cell.traffic["limits"]
    numbers, failed = session.compare(session.products(window.outs),
                                      session.reference(REFERENCE), limits)
    if red is not None and red.devices and "kernel_calls" in session.work:
        numbers["kernel_event_gap"] = kernel_event_gap(
            red, window.traced_calls, session.work["kernel_calls"])
        limits = {**limits, "kernel_event_gap": 0}
    # each number compared, beside its limit: it passes at or under it
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    ctx = Context(cell=cell, session=session, window=window,
                  setup_s=setup_s, peaks=peaks(device["kind"], bench_dir)
                  if device["platform"] == "tpu" else {},
                  trace=red, spans=spans)
    metrics = read_metrics(cell.per_layer if traced else cell.end_to_end, ctx)
    result = {"correct": correct, "attempted": session.attempted(window),
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                               "idle_gaps": trace_reduce.idle_gaps(red)}
    result["setup_split_s"] = setup_split
    result["window"] = window.summary()
    result["compiles_in_window"] = compiles
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """The result line last on stdout; each number compared beside its
    limit as the last lines of stderr."""
    print(f"set-up: {json.dumps(result['setup_split_s'])}", file=sys.stderr)
    print(f"window: {json.dumps(result['window'])}", file=sys.stderr)
    print(f"compiles in the measured window: {result['compiles_in_window']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
