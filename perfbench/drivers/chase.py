"""The idle pointer chase through the xla backend's compiled case, called on
the benchmark's seeded successor array, back to back.  The timed call
returns the walk's accumulator of indices.

Traffic keys: ``passes``, ``limits``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import accounting, data
from perfbench.harness import reference_module, scalar_type

MIX = "latency_chase"


def setup(config: dict, traffic: dict, seed: int, bench_dir):
    return ChaseSession(config, traffic, seed, bench_dir)


class ChaseSession:
    def __init__(self, config, traffic, seed, bench_dir):
        from repro.bench import BenchSpec
        from repro.bench.backends import get_backend
        from repro.bench.mixes import get_mix
        self.shape = tuple(config["shape"])
        self.passes = int(traffic["passes"])
        self.ref = reference_module(bench_dir, MIX)
        self.succ = data.successor(seed, self.shape)
        n = self.succ.size
        nbytes = n * self.succ.dtype.itemsize
        spec = BenchSpec(mixes=(MIX,), sizes=(nbytes,), backend="xla",
                         passes=self.passes)
        case = get_backend("xla").make_case(spec, get_mix(MIX), self.shape,
                                            jnp.dtype(jnp.int32),
                                            self.passes)
        succ = self.succ
        self._fn = lambda: case(succ)
        self.work = {"steps": self.passes * n,
                     "bytes": accounting.bytes_per_pass(MIX, nbytes)
                     * self.passes}

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
        return {"accs": [float(v) for v in jax.device_get(outs)]}

    def reference(self, precision: str) -> dict:
        return {"accs": [self.ref.timed_acc(jax.device_get(self.succ),
                                            self.passes,
                                            scalar_type(precision))]}

    def compare(self, got: dict, want: dict, limits: dict):
        """The walk ends on an index: any gap at all is a wrong walk."""
        gaps = np.abs(np.asarray(got["accs"], np.float64) - want["accs"][0])
        failed = int(np.sum(~(gaps <= limits["acc_gap"])))
        return {"acc_gap": float(np.max(gaps))}, failed
