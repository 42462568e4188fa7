"""A collective mix through the ``sharded`` backend's compiled case, over a
mesh of the cell's chips, bound to its working set as the Runner binds it
(``make_case``, ``prepare_buffer``, ``bind_case``), called back to back.
The timed call returns every rank's accumulator.

Traffic keys: ``backend``, ``mix``, ``passes``, ``limits``.  The mesh holds
the first ``chips`` devices JAX has (fewer only where a test drives the run
on the CPU).  ``work["bytes"]`` is the payload a rank, times the passes, so
``gbps`` is nccl-tests' algbw.  The check also takes the exchange out of
the timed program (the nested jit ``collective_<mix>`` that each pass
calls) and runs it once over the working set, so every rank's whole output
is compared with the reference.  No Pallas kernel runs: the session
declares no ``kernel_calls``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import collective_accounting as accounting
from perfbench import data
from perfbench.harness import reference_module, scalar_type


def setup(config: dict, traffic: dict, seed: int, bench_dir):
    return CollectiveSession(config, traffic, seed, bench_dir)


class CollectiveSession:
    def __init__(self, config, traffic, seed, bench_dir):
        from repro.bench import BenchSpec
        from repro.bench.backends import get_backend
        from repro.bench.mixes import get_mix
        self.shape = tuple(config["shape"])
        self.dtype = jnp.dtype(config["dtype"])
        self.mix = traffic["mix"]
        self.passes = int(traffic["passes"])
        self.ranks = min(int(config["chips"]), jax.device_count())
        self.ref = reference_module(bench_dir, self.mix)
        nbytes = math.prod(self.shape) * self.dtype.itemsize
        self.spec = BenchSpec(mixes=(self.mix,), sizes=(nbytes,),
                              dtype=self.dtype.name,
                              backend=traffic["backend"], devices=self.ranks,
                              passes=self.passes)
        self.backend = get_backend(traffic["backend"])
        self.backend.validate(self.spec)
        self.mixdef = get_mix(self.mix)
        self._case = self.backend.make_case(self.spec, self.mixdef,
                                            self.shape, self.dtype,
                                            self.passes)
        self.x = self.backend.prepare_buffer(
            self.spec, data.working_set(seed, self.shape, self.dtype))
        self._fn = self.backend.bind_case(self._case, self.spec, self.mixdef,
                                          self.x)
        self.work = {
            "bytes": accounting.payload_bytes(nbytes, self.ranks)
            * self.passes,
            "bus_bytes": accounting.bus_bytes(self.mix, nbytes, self.ranks),
            "exchanges": self.passes}

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
        """Every call's per-rank accumulators, and every rank's output of
        the exchange taken out of the timed program (None where the
        program has none)."""
        accs = np.asarray(jax.device_get(outs), np.float64)
        exchange = exchange_of(self._case, self.x, f"collective_{self.mix}")
        ranks = None
        if exchange is not None:
            out = np.asarray(exchange(self.x))
            ranks = out.reshape(self.ranks, -1, out.shape[-1])
        return {"accs": accs, "outs": ranks}

    def reference(self, precision: str) -> dict:
        dt = scalar_type(precision)
        total = self.ref.reduced(np.asarray(self.x), self.ranks, dt)
        acc = self.ref.timed_acc(total, self.passes, dt)
        return {"accs": np.full((1, self.ranks), acc),
                "outs": np.broadcast_to(total, (self.ranks,) + total.shape)}

    def compare(self, got: dict, want: dict, limits: dict):
        """(numbers compared, calls in which some rank's accumulator fails
        its limit).  ``out_rel_gap`` is the widest relative gap of any
        rank's output from the reference sum; ``replica_gap`` the widest
        difference between a rank's output and rank 0's."""
        ref_acc = float(np.asarray(want["accs"]).reshape(-1)[0])
        gaps = (np.abs(np.asarray(got["accs"], np.float64) - ref_acc)
                / abs(ref_acc))
        failed = int(np.sum(~np.all(gaps <= limits["acc_rel_gap"], axis=-1)))
        ref = np.asarray(want["outs"][0], np.float64)
        if got["outs"] is None:       # no exchange: the whole answer missing
            return {"acc_rel_gap": float(gaps.max()), "out_rel_gap": 1.0,
                    "replica_gap": float(np.max(np.abs(ref)))}, failed
        rank0 = np.asarray(got["outs"][0], np.float64)
        out_gap = replica_gap = 0.0
        for out in got["outs"]:
            out = np.asarray(out, np.float64)
            out_gap = max(out_gap, float(np.max(np.abs(out - ref)
                                                / np.abs(ref))))
            replica_gap = max(replica_gap,
                              float(np.max(np.abs(out - rank0))))
        return {"acc_rel_gap": float(gaps.max()), "out_rel_gap": out_gap,
                "replica_gap": replica_gap}, failed


def _eqns(jaxpr, primitive: str):
    """Every equation of ``primitive`` in ``jaxpr`` and the programs nested
    in it (jit, shard_map, loops), outermost first."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)    # a ClosedJaxpr's Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, primitive)


def exchange_of(case, x, name: str):
    """The nested jit named ``name`` that ``case``'s ``shard_map`` runs,
    taken out of ``case``'s program on ``x`` and run, with its own
    parameters, over that ``shard_map``'s mesh: a jitted function of the
    whole working set whose output holds each rank's result, one row block
    a rank.  None where the program has no such call."""
    from jax.sharding import PartitionSpec as P
    for smap in _eqns(jax.make_jaxpr(case)(x).jaxpr, "shard_map"):
        for eqn in _eqns(smap.params["jaxpr"], "jit"):
            if eqn.params["name"] == name:
                mesh = smap.params["mesh"]
                (axis,) = mesh.axis_names
                return jax.jit(jax.shard_map(
                    lambda v, eqn=eqn: eqn.primitive.bind(v, **eqn.params)[0],
                    mesh=mesh, in_specs=P(axis, None),
                    out_specs=P(axis, None), check_vma=False))
    return None
