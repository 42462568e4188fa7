"""``Runner.run`` on one held Runner, called back to back: what a user of
``python -m repro.bench run`` pays for, host buffer build, planning,
warm-up and timed reps together.  Set-up is one warm run, which compiles
every case.  One timed call is one ``Runner.run`` and completes one point
per mix.

Traffic keys: ``backend``, ``mixes``, ``passes``, ``reps``, ``warmup``,
``limits``.  The buffer's fill value comes from the seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import accounting, data, extract
from perfbench.harness import reference_module, scalar_type


def setup(config: dict, traffic: dict, seed: int, bench_dir):
    return RunnerSession(config, traffic, seed, bench_dir)


class Pattern:
    """The Runner's fill pattern, ``(v, 1/v, -v, -1/v)`` cycled over the
    flat buffer in float32, read element by element (the references read
    corners) or whole, built from its cycle on the device."""

    def __init__(self, value: float, shape):
        self.value, self.shape = value, tuple(shape)
        v = value
        self.cycle = np.asarray([v, 1.0 / v, -v, -1.0 / v], np.float32)

    def __getitem__(self, idx):
        i, j = (k % n for k, n in zip(idx, self.shape))
        return self.cycle[(i * self.shape[1] + j) % 4]

    def tile(self, cycle):
        """``cycle`` (four values) repeated over the buffer's shape."""
        return _tile(jnp.asarray(cycle), self.shape)


@partial(jax.jit, static_argnames=("shape",))
def _tile(cycle, shape):
    return jnp.tile(cycle, shape[0] * shape[1] // 4).reshape(shape)


class RunnerSession:
    def __init__(self, config, traffic, seed, bench_dir):
        from repro.bench import BenchSpec, Runner
        self.shape = tuple(config["shape"])
        self.dtype = jnp.dtype(config["dtype"])
        self.passes = int(traffic["passes"])
        self.mixes = tuple(traffic["mixes"])
        self.nbytes = self.shape[0] * self.shape[1] * self.dtype.itemsize
        self.value = data.runner_value(seed)
        self.refs = {m: reference_module(bench_dir, m) for m in self.mixes}
        self.spec = BenchSpec(mixes=self.mixes, sizes=(self.nbytes,),
                              dtype=self.dtype.name,
                              backend=traffic["backend"],
                              passes=self.passes, reps=int(traffic["reps"]),
                              warmup=int(traffic["warmup"]),
                              value=self.value)
        self.runner = Runner()
        self.work = {"points": len(self.mixes)}

    def warm(self) -> None:
        self._check_shape(self.runner.run(self.spec))

    def _check_shape(self, res) -> None:
        got = {(p.mix, p.nbytes) for p in res.points}
        want = {(m, self.nbytes) for m in self.mixes}
        if got != want:
            raise RuntimeError(f"Runner points {sorted(got)}, expected "
                               f"{sorted(want)}")

    def call(self):
        return self.runner.run(self.spec)

    def attempted(self, window) -> int:
        return window.calls * len(self.mixes)

    def release(self) -> None:
        """The Runner keeps compiled cases only; the check reuses them."""

    def products(self, outs) -> dict:
        """Every point's passes and declared bytes; and each mix's compiled
        case as the Runner cached it, called on a buffer built as the
        Runner builds it, with the Pallas kernel taken out of that case run
        once over the same buffer (the case itself returns only the pass
        loop's accumulator)."""
        from repro.bench.backends import get_backend
        from repro.bench.mixes import get_mix
        from repro.core import buffers
        points = [(p.mix, p.passes, p.bytes_per_call, p.mean_s)
                  for res in outs for p in res.points]
        backend = get_backend(self.spec.backend)
        x = buffers.working_set(self.nbytes, dtype=self.dtype,
                                value=self.value)
        accs, kernels = {}, {}
        for m in self.mixes:
            mix = get_mix(m)
            key = backend.case_key(self.spec, mix, self.shape, self.dtype,
                                   self.passes)
            case = self.runner._cases[key]
            # the operands the timed call gets, as bind_case passes them
            args = backend.bind_case(lambda *a: a, self.spec, mix, x)()
            accs[m] = float(jax.block_until_ready(case(*args)))
            kernels[m] = extract.kernel_outputs(case, args)
            del args
        return {"points": points, "accs": accs, "kernels": kernels}

    def reference(self, precision: str) -> dict:
        dt = scalar_type(precision)
        x = Pattern(self.value, self.shape)
        itemsize = min(np.dtype(dt).itemsize, self.dtype.itemsize)
        n = self.shape[0] * self.shape[1]
        return {"accs": {m: self.refs[m].timed_acc(x, self.passes, dt)
                         for m in self.mixes},
                "kernels": {m: [x.tile(self.refs[m].kernel_output(x.cycle,
                                                                   dt))]
                            for m in self.mixes},
                "points": [(m, self.passes, accounting.bytes_per_pass(
                    m, n * itemsize) * self.passes, None)
                    for m in self.mixes]}

    def compare(self, got: dict, want: dict, limits: dict):
        """Each mix's accumulator and kernel output against the
        reference's, and every point's bytes per call against the
        benchmark's own formula."""
        numbers, failed = {}, 0
        for m in self.mixes:
            ref = want["accs"][m]
            numbers[f"{m}_acc_rel_gap"] = abs(got["accs"][m] - ref) / abs(ref)
            numbers[f"{m}_kernel_rel_gap"] = extract.kernels_rel_gap(
                got["kernels"][m], want["kernels"][m][0])
        declared = {m: (passes, b) for m, passes, b, _ in want["points"]}
        gap = 0.0
        for m, passes, b, mean_s in got["points"]:
            want_passes, want_b = declared[m]
            g = abs(b - want_b) / want_b
            gap = max(gap, g)
            if (passes != want_passes or not g <= limits["bytes_rel_gap"]
                    or not (mean_s is None or mean_s > 0)):
                failed += 1
        numbers["bytes_rel_gap"] = gap
        return numbers, failed
