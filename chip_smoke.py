"""Smoke test of the membench Runner on TPU: the quickest proof that the
benchmark still starts on the chip and gives correct answers there.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded backend across four chips

One chip, in one process, through the entry points a user calls:

1. ``correctness``: every Pallas mix that compiles, run once compiled
   (``make_kernel``, ``interpret=False``) at a 4 MiB f32 working set and held
   against ``kernels/membench/ref.py`` (the ``rw_*`` family against a plain
   ``jnp`` fold of the same streams).  ``latency_chase`` must be refused.
2. ``runner``: ``load_sum``, ``copy``, ``triad``, ``rw_2to1``, ``fma_8`` and
   ``mxu`` on ``xla`` and ``pallas`` at 4 MiB and 1 GiB per buffer.
3. ``chase``: ``latency_chase`` on ``xla`` at 1 MiB, load 0.
4. ``characterize``: ``python -m repro.bench characterize --smoke --backend
   xla`` through the CLI's ``main``.

``--chips 4`` runs only ``copy`` and ``triad`` on ``sharded`` over four
devices and on ``xla`` on one, at 1 GiB per device, and checks each shard's
accumulator against the single-device oracle on that shard's slice.

Each phase prints JSON lines (first readings, not benchmark numbers).  The
last line is ``{"ok": true, "device": {...}}``; any failure raises, exits
non-zero, and prints no such line.  Off a TPU the script refuses to run.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MIB, GIB = 2**20, 2**30
RUNNER_MIXES = ("load_sum", "copy", "triad", "rw_2to1", "fma_8", "mxu")
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its
    monitoring events; ``lap()`` returns the seconds since the last lap."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in self.EVENTS:
            self.total += secs

    def lap(self) -> float:
        secs, self._mark = self.total - self._mark, self.total
        return secs


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def point_fields(p, kind: str) -> dict:
    return {"mix": p.mix, "backend": p.backend, "bytes": p.nbytes,
            "devices": p.devices, "passes": p.passes, "mean_s": p.mean_s,
            "gbps": p.gbps, "device_kind": kind}


def check_point(p, platform: str, res) -> None:
    check(math.isfinite(p.mean_s) and p.mean_s > 0,
          f"{p.backend}/{p.mix}@{p.nbytes}: mean_s={p.mean_s}")
    check(res.machine["device_platform"] == platform,
          f"result stamped {res.machine['device_platform']!r}")


def seeded_data(shape):
    """Seeded data, exact in bf16 ((k + 128) / 256 for k in [0, 256)): the
    MXU's rounding cannot change it, and every sum is over positives, so a
    lost or doubled tile shows far above the f32 summation error."""
    import jax
    import jax.numpy as jnp
    k = jax.random.randint(jax.random.key(SEED), shape, 0, 256)
    return (k.astype(jnp.float32) + 128.0) / 256.0


def phase_correctness(clock, kind: str) -> None:
    import jax
    import numpy as np
    from repro.bench import BenchSpec, BenchSpecError
    from repro.bench.backends import get_backend
    from repro.bench.mixes import RW_COMBINE_COEF, get_mix, mix_names
    from repro.core.buffers import working_set_shape
    from repro.core.instruction_mix import rw_streams
    from repro.kernels.membench import ops as mb_ops
    from repro.kernels.membench.ref import reference

    shape = working_set_shape(4 * MIB)
    x = seeded_data(shape)
    nbytes = x.size * x.dtype.itemsize
    pallas = get_backend("pallas")
    refused = []
    for name in mix_names("pallas"):
        mix = get_mix(name)
        try:
            pallas.validate(BenchSpec(mixes=(name,), backend="pallas"))
        except BenchSpecError as e:
            refused.append(name)
            emit("correctness", mix=name, backend="pallas", refused=str(e))
            continue
        depth = mix.fma_depth or 8
        fn = mb_ops.make_kernel(name, depth=depth, block_rows=128,
                                interpret=False)
        if name == "triad":
            args = (x, x * 0.5)
        elif mix.rw is not None:
            args = rw_streams(x, mix.rw[0])
        else:
            args = (x,)
        clock.lap()
        compiled = fn.lower(*args).compile()
        compile_s = clock.lap()
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        secs = time.perf_counter() - t0
        if mix.rw is not None:
            fold = args[0]
            for s in args[1:]:
                fold = fold + RW_COMBINE_COEF * s
            check(len(out) == mix.rw[1], f"{name}: {len(out)} outputs")
            for o in out:
                np.testing.assert_allclose(np.asarray(o), np.asarray(fold),
                                           rtol=1e-6, err_msg=name)
        elif name in ("copy", "triad"):
            want = reference(name, x, y=args[-1])
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=1e-6, err_msg=name)
        else:
            want = float(reference(name, x, depth=depth, block_rows=128))
            got = float(out)
            check(abs(got - want) <= 1e-5 * abs(want),
                  f"{name}: kernel {got} vs reference {want}")
        emit("correctness", mix=name, backend="pallas", bytes=nbytes,
             mean_s=secs, gbps=mix.bytes_per_pass(nbytes) / secs / 1e9,
             compile_s=compile_s, device_kind=kind)
    check(refused == ["latency_chase"],
          f"refused Pallas mixes {refused}, expected only latency_chase")


def run_points(runner, clock, phase: str, kind: str, platform: str,
               **spec_kw):
    from repro.bench import BenchSpec
    clock.lap()
    t0 = time.perf_counter()
    res = runner.run(BenchSpec(**spec_kw))
    wall = time.perf_counter() - t0
    compile_s = clock.lap()
    check(len(res.points) == len(spec_kw["mixes"]), f"{phase}: points")
    for p in res.points:
        check_point(p, platform, res)
        extra = {"latency_ns": p.latency_ns} if p.latency_ns else {}
        emit(phase, **point_fields(p, kind), compile_s=compile_s,
             wall_s=wall, **extra)
    return res


def phase_runner(runner, clock, kind, platform) -> None:
    for backend in ("xla", "pallas"):
        for size in (4 * MIB, 1 * GIB):
            for mix in RUNNER_MIXES:
                run_points(runner, clock, "runner", kind, platform,
                           mixes=(mix,), sizes=(size,), backend=backend)


def phase_chase(runner, clock, kind, platform) -> None:
    res = run_points(runner, clock, "chase", kind, platform,
                     mixes=("latency_chase",), sizes=(1 * MIB,),
                     backend="xla", load=0)
    lat = res.points[0].latency_ns
    check(lat is not None and math.isfinite(lat) and lat > 0,
          f"chase latency_ns={lat}")


def phase_characterize(clock, kind) -> None:
    from repro.bench.cli import main as bench_main
    clock.lap()
    t0 = time.perf_counter()
    rc = bench_main(["characterize", "--smoke", "--backend", "xla",
                     "--no-ledger"])
    check(rc == 0, f"characterize --smoke exited {rc}")
    emit("characterize", backend="xla", wall_s=time.perf_counter() - t0,
         compile_s=clock.lap(), device_kind=kind)


def phase_sharded(runner, clock, kind, platform, chips: int) -> None:
    """copy/triad on ``sharded`` over ``chips`` devices vs ``xla`` on one,
    1 GiB per device; each shard's accumulator vs the single-device oracle
    on that shard's slice."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.bench import BenchSpec
    from repro.bench.backends import _mix_operands, get_backend
    from repro.bench.mixes import get_mix
    from repro.core import buffers

    devices = jax.devices()
    check(len(devices) >= chips, f"{len(devices)} devices < {chips}")
    for backend, k in (("sharded", chips), ("xla", 1)):
        run_points(runner, clock, "sharded", kind, platform,
                   mixes=("copy", "triad"), sizes=(k * GIB,),
                   backend=backend, devices=k)

    sharded, xla = get_backend("sharded"), get_backend("xla")
    spec = BenchSpec(mixes=("copy", "triad"), sizes=(chips * GIB,),
                     backend="sharded", devices=chips, passes=1)
    shape = buffers.working_set_shape(chips * GIB)
    rows = shape[0] // chips
    # seeded data made on the mesh: each shard's slice is distinct, so a
    # shard that read another's slice would disagree with its oracle
    on_mesh = NamedSharding(Mesh(np.array(devices[:chips]), ("d",)),
                            P("d", None))
    x = jax.jit(lambda: seeded_data(shape), out_shardings=on_mesh)()
    x = sharded.prepare_buffer(spec, x)
    mesh_devs = {d.id for d in x.sharding.mesh.devices.flat}
    placed = {s.device.id for s in x.addressable_shards}
    check(len(mesh_devs) == chips and placed == mesh_devs,
          f"mesh devices {mesh_devs}, buffer shards on {placed}")
    check(all(s.data.shape == (rows, shape[1]) for s in x.addressable_shards),
          "shard shapes")
    for name in ("copy", "triad"):
        mix = get_mix(name)
        per_shard = sharded.per_shard_case(spec, mix, shape, x.dtype, 1)
        place = lambda a: jax.device_put(a, x.sharding)   # noqa: E731
        bufs = _mix_operands(mix, x, place=place, parts=chips)
        got = np.asarray(per_shard(*bufs))
        oracle = xla.make_case(spec.replace(backend="xla", devices=1), mix,
                               (rows, shape[1]), x.dtype, 1)
        for j in range(chips):
            sl = tuple(jax.device_put(b[j * rows:(j + 1) * rows], devices[j])
                       for b in bufs)
            want = float(oracle(*sl))
            check(abs(got[j] - want) <= 1e-6 * max(abs(want), 1.0),
                  f"sharded {name} shard {j}: {got[j]} vs oracle {want}")
        emit("sharded_check", mix=name, devices=chips,
             shards=[float(v) for v in got], device_kind=kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path across four chips")
    args = ap.parse_args(argv)

    from repro.bench import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(f"error: chip_smoke needs a TPU; JAX found {platform!r} "
              f"({kind})", file=sys.stderr)
        return 2
    print(f"# {len(devices)} x {kind}, compile cache {cache_dir}", flush=True)

    from repro.bench import Runner
    clock, runner = CompileClock(), Runner()
    if args.chips == 4:
        phase_sharded(runner, clock, kind, platform, chips=4)
    else:
        phase_correctness(clock, kind)
        phase_runner(runner, clock, kind, platform)
        phase_chase(runner, clock, kind, platform)
        phase_characterize(clock, kind)
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
