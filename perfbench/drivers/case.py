"""A bandwidth mix through a backend's compiled case, bound to its working
set as the Runner binds it (``make_case`` then ``bind_case``), called back
to back.  The timed call returns the pass loop's accumulator.

Traffic keys: ``backend``, ``mix``, ``passes``, ``limits``.  On the
``pallas`` backend the check also runs the Pallas kernel taken out of the
timed program once over the working set (``perfbench/extract.py``), and a
traced run counts that kernel's events.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import accounting, data, extract
from perfbench.harness import reference_module, scalar_type


def setup(config: dict, traffic: dict, seed: int, bench_dir):
    return CaseSession(config, traffic, seed, bench_dir)


class CaseSession:
    def __init__(self, config, traffic, seed, bench_dir):
        from repro.bench import BenchSpec
        from repro.bench.backends import get_backend
        from repro.bench.mixes import get_mix
        self.shape = tuple(config["shape"])
        self.dtype = jnp.dtype(config["dtype"])
        self.mix = traffic["mix"]
        self.passes = int(traffic["passes"])
        self.ref = reference_module(bench_dir, self.mix)
        self.x = data.working_set(seed, self.shape, self.dtype)
        n = self.x.size
        nbytes = n * self.dtype.itemsize
        self.spec = BenchSpec(mixes=(self.mix,), sizes=(nbytes,),
                              dtype=self.dtype.name,
                              backend=traffic["backend"], passes=self.passes)
        self.backend = get_backend(traffic["backend"])
        self.mixdef = get_mix(self.mix)
        self._case = self.backend.make_case(self.spec, self.mixdef,
                                            self.shape, self.dtype,
                                            self.passes)
        self._fn = self.backend.bind_case(self._case, self.spec, self.mixdef,
                                          self.x)
        kernel_bytes = accounting.bytes_per_pass(self.mix, nbytes)
        self.work = {"bytes": kernel_bytes * self.passes}
        if self.backend.name == "pallas":
            self.work.update(kernel_bytes=kernel_bytes,
                             kernel_flops=accounting.flops_per_pass(self.mix,
                                                                    n),
                             kernel_calls=self.passes)

    def warm(self) -> None:
        for _ in range(2):
            jax.block_until_ready(self._fn())

    def call(self):
        return jax.block_until_ready(self._fn())

    def attempted(self, window) -> int:
        return window.calls

    def release(self) -> None:
        self._fn = None

    def products(self, outs) -> dict:
        accs = [float(v) for v in jax.device_get(outs)]
        kernels = None
        if self.backend.name == "pallas":
            # the operands the timed call gets, as bind_case passes them
            args = self.backend.bind_case(lambda *a: a, self.spec,
                                          self.mixdef, self.x)()
            kernels = extract.kernel_outputs(self._case, args)
        return {"accs": accs, "kernels": kernels}

    def reference(self, precision: str) -> dict:
        dt = scalar_type(precision)
        return {"accs": [self.ref.timed_acc(self.x, self.passes, dt)],
                "kernels": [self.ref.kernel_output(self.x, dt)]}

    def compare(self, got: dict, want: dict, limits: dict):
        """(numbers compared, calls whose accumulator fails its limit)."""
        ref = want["accs"][0]
        gaps = np.abs(np.asarray(got["accs"], np.float64) - ref) / abs(ref)
        numbers = {"acc_rel_gap": float(np.max(gaps))}
        failed = int(np.sum(~(gaps <= limits["acc_rel_gap"])))
        if got["kernels"] is not None:
            numbers["kernel_rel_gap"] = extract.kernels_rel_gap(
                got["kernels"], want["kernels"][0])
        return numbers, failed
