"""Multi-process coordination for the ``distributed`` backend (paper Fig 4
at multi-host scale).

Three concerns live here, deliberately separated from the backend itself
(``bench.backends.DistributedBackend`` — kernels and mesh placement):

* **initialization** — ``ensure_initialized()`` wraps
  ``jax.distributed.initialize`` with env-var autodetection
  (``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``,
  falling back to JAX's own ``JAX_COORDINATOR_ADDRESS`` etc.), enabling the
  gloo CPU-collective implementation first so forced-host-device simulation
  works on a laptop/CI exactly like a real multi-host mesh.  It MUST run
  before anything initializes the jax backend (i.e. before ``jax.devices()``
  is first called) — the CLI and the figure scripts call it up front.
* **gathering** — ``gather_result()`` allgathers every process's per-point
  timings (``multihost_utils.process_allgather``) and merges them into ONE
  BenchResult: each merged point takes the *slowest* process's timing triple
  (aggregate bandwidth = global bytes / the wall time of the straggler), the
  per-process means land in ``meta["per_process_mean_s"]`` for skew
  inspection, and the machine meta records ``process_count`` and the
  per-host device counts (result schema v3).
* **launching** — ``launch_local()`` spawns N coordinated local processes
  with ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` each, so a
  single machine simulates an N-host mesh with N*K global devices; this is
  the CI-testable path behind ``python -m repro.bench launch`` and
  ``scripts/launch_distributed.py``.  On a real cluster you skip the
  launcher: start one process per host with the env vars set and the same
  ``run --backend distributed`` command.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time

from repro.obs import metrics, trace

#: env vars read by ``env_info`` (REPRO_* first, then JAX's own names)
ENV_COORDINATOR = ("REPRO_COORDINATOR", "JAX_COORDINATOR_ADDRESS")
ENV_NUM_PROCESSES = ("REPRO_NUM_PROCESSES", "JAX_NUM_PROCESSES")
ENV_PROCESS_ID = ("REPRO_PROCESS_ID", "JAX_PROCESS_ID")

_initialized = False


def _env(names, cast=str):
    for n in names:
        v = os.environ.get(n)
        if v not in (None, ""):
            return cast(v)
    return None


def env_info() -> tuple[str | None, int | None, int | None]:
    """(coordinator_address, num_processes, process_id) from the environment;
    None where unset.  The launcher sets the REPRO_* triple on every child."""
    return (_env(ENV_COORDINATOR),
            _env(ENV_NUM_PROCESSES, int),
            _env(ENV_PROCESS_ID, int))


def env_active() -> bool:
    """True when this process was started under a multi-process launcher."""
    coord, nproc, _ = env_info()
    return coord is not None and (nproc or 1) > 1


def is_initialized() -> bool:
    if _initialized:
        return True
    try:    # already initialized by someone else (e.g. a framework harness)
        from jax._src import distributed as _dist
        return _dist.global_state.client is not None
    except Exception:
        return False


def initialize(coordinator_address: str, num_processes: int,
               process_id: int) -> None:
    """``jax.distributed.initialize`` + the CPU-collectives knob.

    JAX's CPU backend refuses multi-process computations unless a
    cross-process collective implementation is selected; gloo ships in
    jaxlib, so forced-host-device simulation works out of the box.  Must
    run before the jax backend initializes.
    """
    global _initialized
    import jax
    if os.environ.get("JAX_PLATFORMS", "cpu").startswith("cpu") or \
            "xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True


def ensure_initialized() -> bool:
    """Autodetect the coordination env and initialize once; no-op (False)
    outside a multi-process launch, True when running distributed."""
    if is_initialized():
        return True
    coord, nproc, pid = env_info()
    if coord is None or not nproc or nproc < 2:
        return False
    if pid is None:
        raise RuntimeError(
            f"{ENV_NUM_PROCESSES[0]}={nproc} but no process id; set "
            f"{ENV_PROCESS_ID[0]} (the launcher does this per child)")
    initialize(coord, nproc, pid)
    return True


def process_count() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def is_primary() -> bool:
    """True on the process that should print/save gathered results."""
    return process_index() == 0


#: the canonical Fig-4 device-count ladder
DEVICE_LADDER = (1, 2, 4, 8, 16, 32, 64)


def covering_device_counts(ladder=DEVICE_LADDER) -> tuple[int, ...]:
    """The ladder values usable as a distributed mesh size here: every
    process must own >= 1 shard (so counts below the process count drop
    out) and the count can't exceed the global device total.  When no
    ladder value qualifies (e.g. 3 hosts x 1 device), the full global mesh
    always covers, so it is the fallback."""
    import jax
    counts = tuple(k for k in ladder
                   if jax.process_count() <= k <= jax.device_count())
    return counts or (jax.device_count(),)


# ---------------------------------------------------------------------------
# gathering
# ---------------------------------------------------------------------------

def gather_result(res):
    """Merge every process's copy of ``res`` into one global BenchResult.

    Every process runs the identical SPMD measurement loop, so the point
    lists line up index-for-index; only the timings differ (per-process
    clock skew around each global serialization point).  The merged point
    takes the timing triple of the process with the largest mean — aggregate
    bandwidth is global bytes over the straggler's wall time — and gbps /
    gflops are recomputed from it.  Per-process means are kept in
    ``meta["per_process_mean_s"]`` (process-indexed rows, point-indexed
    columns) and the machine meta grows ``process_count`` plus per-host
    ``local_device_counts``.  Identity (and the input object) on a
    single-process run.
    """
    import jax
    if jax.process_count() == 1:
        return res
    import dataclasses

    import numpy as np
    from jax.experimental import multihost_utils

    # one allgather for all points: rows tagged with the sender's process
    # index so merge order never depends on allgather's device ordering
    local = np.array([[float(jax.process_index()),
                       float(jax.local_device_count())]
                      + [s for p in res.points
                         for s in (p.mean_s, p.std_s, p.min_s)]])
    rows = multihost_utils.process_allgather(local).reshape(
        jax.process_count(), -1)
    rows = rows[np.argsort(rows[:, 0])]          # process-index order
    stats = rows[:, 2:].reshape(jax.process_count(), len(res.points), 3)

    merged = []
    for i, p in enumerate(res.points):
        slowest = int(np.argmax(stats[:, i, 0]))
        mean_s, std_s, min_s = (float(v) for v in stats[slowest, i])
        merged.append(dataclasses.replace(
            p, mean_s=mean_s, std_s=std_s, min_s=min_s,
            gbps=p.bytes_per_call / mean_s / 1e9 if mean_s else 0.0,
            gflops=p.flops_per_call / mean_s / 1e9 if mean_s else 0.0))
    res.points = merged
    res.meta["per_process_mean_s"] = stats[:, :, 0].tolist()
    res.machine["process_count"] = jax.process_count()
    res.machine["local_device_counts"] = [int(r[1]) for r in rows]
    _gather_traces()
    return res


def _gather_traces() -> None:
    """Allgather every process's span-trace events and install the merged
    stream (pids re-stamped to mesh process indices) on ALL processes —
    process 0 then writes ONE trace showing probe and generator shards,
    stragglers included.  A no-op while tracing is disabled (nothing is
    gathered, zero cost).  Events are self-describing variable-length JSON,
    so each allgathered row carries its own 8-byte length header and pads
    to the global max — row *order* from the collective is irrelevant."""
    import jax
    tr = trace.get_tracer()
    if not tr.enabled or jax.process_count() == 1:
        return
    import json

    import numpy as np
    from jax.experimental import multihost_utils

    events = tr.events()
    for e in events:        # stamp mesh identity before the OS pid is lost
        e["pid"] = jax.process_index()
    payload = np.frombuffer(json.dumps(events).encode(), dtype=np.uint8)
    sizes = multihost_utils.process_allgather(
        np.array([payload.size], dtype=np.int64))
    cap = int(np.max(sizes))
    row = np.zeros(cap + 8, dtype=np.uint8)
    row[:8] = np.frombuffer(np.array([payload.size], "<i8").tobytes(),
                            np.uint8)
    row[8:8 + payload.size] = payload
    gathered = multihost_utils.process_allgather(row)
    per_proc: dict[int, list[dict]] = {}
    for r in np.asarray(gathered).reshape(-1, cap + 8):
        n = int(np.frombuffer(bytes(r[:8]), "<i8")[0])
        evs = json.loads(bytes(r[8:8 + n]).decode())
        if evs:
            per_proc[evs[0]["pid"]] = evs
    streams = [per_proc.get(i, []) for i in range(jax.process_count())]
    tr.replace_events(trace.merge_process_traces(streams))


# ---------------------------------------------------------------------------
# local launcher (single-machine multi-process simulation)
# ---------------------------------------------------------------------------

def pick_free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pump(proc, prefix, sink):
    for line in proc.stdout:
        sink.write(f"{prefix}{line}")
        sink.flush()


def launch_local(cmd: list[str], processes: int,
                 devices_per_process: int = 1,
                 coordinator_port: int | None = None,
                 env: dict | None = None, timeout: float | None = None,
                 stream_to=None) -> int:
    """Spawn ``cmd`` as ``processes`` coordinated local processes.

    Each child gets the REPRO_* coordination triple, ``JAX_PLATFORMS=cpu``
    (this simulates several hosts on the CPU; a child that reached for the
    accelerator would contend with its siblings for one chip) and
    ``--xla_force_host_platform_device_count=devices_per_process`` appended
    to ``XLA_FLAGS`` (appended last, so it wins over any count the command
    sets for its single-process path) — the global mesh the children see has
    ``processes * devices_per_process`` devices.  Child stdout/stderr are
    streamed line-by-line with a ``[pK]`` prefix.  Returns the max child
    return code; on the first failure — *whichever* child fails first — the
    stragglers are killed rather than left waiting at a coordination
    barrier, and a ``timeout`` (seconds, for the whole launch) likewise
    kills everything and reports nonzero instead of raising.
    """
    if processes < 1:
        raise ValueError(f"processes must be >= 1: {processes}")
    if devices_per_process < 1:
        raise ValueError(
            f"devices_per_process must be >= 1: {devices_per_process}")
    port = coordinator_port or pick_free_port()
    base = dict(env if env is not None else os.environ)
    xla_flags = (base.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                 f"device_count={devices_per_process}").strip()
    sink = stream_to or sys.stderr
    procs, pumps = [], []
    deadline = None if timeout is None else time.monotonic() + timeout
    rc = 0
    tr = trace.get_tracer()
    launch_span = tr.span("launch.local", cat="launch", processes=processes,
                          devices_per_process=devices_per_process)
    launch_span.__enter__()
    try:
        # spawn INSIDE the cleanup scope: a Popen failure partway through
        # (EMFILE, OOM) must not leak already-started children blocked at
        # the coordination barrier
        for i in range(processes):
            child_env = dict(base,
                             JAX_PLATFORMS="cpu",
                             XLA_FLAGS=xla_flags,
                             REPRO_COORDINATOR=f"127.0.0.1:{port}",
                             REPRO_NUM_PROCESSES=str(processes),
                             REPRO_PROCESS_ID=str(i))
            p = subprocess.Popen(cmd, env=child_env, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append(p)
            t = threading.Thread(target=_pump, args=(p, f"[p{i}] ", sink),
                                 daemon=True)
            t.start()
            pumps.append(t)
        # poll ALL children (a sequential wait would hang on a live earlier
        # child blocked at a collective barrier while a later one lies dead)
        pending = set(procs)
        while pending:
            for p in list(pending):
                code = p.poll()
                if code is not None:
                    pending.discard(p)
                    if code:    # negative = killed by signal, still a failure
                        rc = max(rc, code if code > 0 else 1)
            if rc:          # a dead peer wedges the others at a barrier
                break
            if deadline is not None and time.monotonic() > deadline:
                sink.write(f"# launch_local: timeout after {timeout}s, "
                           f"killing {len(pending)} process(es)\n")
                tr.event("launch.timeout", cat="launch", timeout_s=timeout,
                         pending=len(pending))
                rc = 1
                break
            if pending:
                time.sleep(0.05)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
                rc = max(rc, 1)
                metrics.REGISTRY.inc("straggler_kills")
                tr.event("launch.straggler_kill", cat="launch",
                         process=procs.index(p), rc=rc)
        launch_span.__exit__(None, None, None)
    for t in pumps:
        t.join(timeout=5)
    return rc
