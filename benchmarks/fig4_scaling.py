"""Fig 4 — multi-device scaling + STREAM-triad comparison.

MUST run as its own process: forces 8 host devices before jax init.  On TPU
hardware the same code produces the real per-chip HBM scaling curve (the
paper's CMG saturation study); on host the 8 'devices' share one socket so the
curve saturating early IS the expected result (shared-bandwidth NUMA analogue).

Everything here is a BenchSpec through ``repro.bench``: the scaling curve is
the ``sharded`` backend swept over the ``devices`` knob (one spec per device
count, merged by ``run_many``), with per-count speedup read off
``BenchResult.baseline_relative``; the triad reference (the paper compares
against STREAM on A64FX) is the registry's ``triad`` mix as a one-size spec.

``--distributed`` takes the same sweep multi-process: the script respawns
itself as ``--processes`` coordinated workers (repro.bench.distributed's
launcher, forced host devices per process), each running the identical
sweep on the ``distributed`` backend over the **global** mesh; process 0
gathers and emits.  ``processes x devices-per-process`` simulated hosts
reproduce the paper's scaling study past one machine — on a real cluster,
start one worker per host with the REPRO_* env set instead of respawning.
"""
import os
import sys

#: set in workers by the launcher (or on the hosts of a real cluster, where
#: JAX's own env names are equally valid — see repro.bench.distributed);
#: when active, jax.distributed (not XLA_FLAGS below) decides the topology.
#: The coordinator address alone marks a worker — keying on a process COUNT
#: would send a --processes 1 child back into the launcher branch, an
#: infinite respawn chain.  Checked without importing repro so it runs
#: before any jax setup.
_UNDER_LAUNCHER = any(
    os.environ.get(k) for k in ("REPRO_COORDINATOR", "JAX_COORDINATOR_ADDRESS",
                                "REPRO_NUM_PROCESSES", "JAX_NUM_PROCESSES"))

if __name__ == "__main__" and not _UNDER_LAUNCHER:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse           # noqa: E402

from benchmarks.common import emit                       # noqa: E402


def run_curve(backend: str, per_dev: int, counts, reps: int):
    """The devices sweep + emit lines (shared by both modes).  Under a
    multi-process run, only process 0 emits (it holds the gathered result);
    the sweep itself is identical SPMD work on every process."""
    from repro.bench import BenchSpec, Runner
    from repro.bench import distributed as dist
    runner = Runner()
    specs = [BenchSpec(mixes=("load_sum",), sizes=(per_dev * k,),
                       backend=backend, devices=k, passes=4,
                       reps=reps, warmup=2)
             for k in counts]
    res = dist.gather_result(runner.run_many(specs))

    # STREAM triad reference (the paper compares against STREAM on A64FX):
    # plain xla single-process (the historical baseline); distributed mode
    # keeps all processes in the computation on the smallest covering mesh.
    # NB every process must reach this point — the measurement is SPMD; only
    # the emission below is gated on process 0.
    t_backend, t_devs = (("xla", 1) if backend == "sharded"
                         else (backend, min(counts)))
    # sized per device like the sweep, so the rows always shard evenly
    spec = BenchSpec(mixes=("triad",), sizes=(per_dev * t_devs,), reps=reps,
                     warmup=2, backend=t_backend, devices=t_devs,
                     target_bytes=5e7)
    t = dist.gather_result(runner.run(spec)).points[0]

    if not dist.is_primary():
        return
    tag = "fig4_dist" if backend == "distributed" else "fig4"
    pc = res.machine.get("process_count", 1)
    for p, speedup in res.baseline_relative(group_key=lambda p: p.mix):
        emit(f"{tag}/devices{p.devices}", p.mean_s * 1e6,
             f"{p.gbps:.2f}GB/s;speedup={speedup:.2f}x;processes={pc}")
    emit(f"{tag}/stream_triad_{t_devs}dev", t.mean_s * 1e6,
         f"{t.gbps:.2f}GB/s")


def main(quick: bool = False, smoke: bool = False, distributed: bool = False,
         processes: int = 2, devices_per_process: int = 2) -> int:
    per_dev = 2 * 2**20 if quick else 16 * 2**20
    if smoke:
        per_dev = 256 * 2**10
    reps = 2 if smoke else (4 if quick else 8)

    if distributed and not _UNDER_LAUNCHER:
        # launcher role: respawn this script as N coordinated workers; their
        # global mesh has processes * devices_per_process devices
        if processes < 2:
            print("error: --distributed needs --processes >= 2 "
                  "(use the plain sharded mode for one process)",
                  file=sys.stderr)
            return 2
        from repro.bench.distributed import launch_local
        argv = [sys.executable, "-m", "benchmarks.fig4_scaling",
                "--distributed", "--processes", str(processes),
                "--devices-per-process", str(devices_per_process)]
        argv += ["--quick"] if quick else []
        argv += ["--smoke"] if smoke else []
        return launch_local(argv, processes=processes,
                            devices_per_process=devices_per_process,
                            stream_to=sys.stdout)

    if distributed:                     # worker role (spawned above)
        from repro.bench import distributed as dist
        dist.ensure_initialized()
        # the mesh must give every process a shard; the shared helper also
        # falls back to the full global mesh when no ladder value qualifies
        run_curve("distributed", per_dev, dist.covering_device_counts(),
                  reps)
        return 0

    import jax
    from repro.bench.distributed import DEVICE_LADDER
    run_curve("sharded", per_dev,
              tuple(k for k in DEVICE_LADDER if k <= jax.device_count()),
              reps)
    return 0


if __name__ == "__main__":
    from repro.bench import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes / 2 reps (CI gate)")
    ap.add_argument("--distributed", action="store_true",
                    help="multi-process mode: respawns itself via the "
                         "repro.bench launcher (simulated multi-host)")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--devices-per-process", dest="devices_per_process",
                    type=int, default=2)
    sys.exit(main(**vars(ap.parse_args())))
