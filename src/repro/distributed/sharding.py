"""Logical-axis sharding: rules, divisibility-checked resolution, ShardCtx.

Models annotate every tensor dim with a *logical* axis name; this module maps
logical names to mesh axes.  A mapping is applied only when the dim size is
divisible by the mesh-axes product (shard_map regions require exact divisibility;
for jit-land tensors the same rule keeps layouts predictable) — otherwise the dim
falls back along the candidate chain (usually to replication), which is recorded
so the roofline report can call out replication waste (e.g. phi3's 40 heads on a
16-way model axis).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# logical axis -> ordered candidate mesh-axis tuples ("fsdp" expands to the data
# axes present in the mesh).  First candidate whose size divides the dim wins.
DEFAULT_RULES: dict[str, list[Optional[tuple[str, ...]]]] = {
    # weights
    "vocab": [("model",), None],
    "embed": [("fsdp",), None],
    "heads": [("model",), None],
    "kv_heads": [("model",), None],
    "head_dim": [None],
    "ffn": [("model",), None],
    "experts": [("model",), None],
    "kv_lora": [None],
    "inner": [("model",), None],
    "state": [None],
    "conv": [None],
    "layers": [None],
    "sites": [None],
    # activations
    "batch": [("dp",), None],          # dp expands to pod+data axes
    "seq": [None],
    "act_seq": [("model",), None],     # sequence parallelism: residual-stream seq
                                       # dim shards over model between blocks
    "act_heads": [("model",), None],
    # decode KV caches: batch takes the data axes first (if divisible), then the
    # sequence dim takes whatever is left — a 32k x 128 cache shards over the
    # full 256-chip pod (data x model), a 500k x 1 cache shards seq over data.
    "kv_seq": [("data",), ("model",), None],
}

FSDP_AXES = ("pod", "data")
DP_AXES = ("pod", "data")


def _expand(candidate: Optional[tuple[str, ...]], mesh: Mesh) -> Optional[tuple[str, ...]]:
    if candidate is None:
        return None
    out: list[str] = []
    for ax in candidate:
        if ax == "fsdp":
            out.extend(a for a in FSDP_AXES if a in mesh.axis_names)
        elif ax == "dp":
            out.extend(a for a in DP_AXES if a in mesh.axis_names)
        elif ax in mesh.axis_names:
            out.append(ax)
    return tuple(out) if out else None


@dataclass
class ShardCtx:
    """Carries the mesh + rules through model code; resolves logical -> physical."""
    mesh: Mesh
    rules: dict[str, list[Optional[tuple[str, ...]]]] = field(
        default_factory=lambda: dict(DEFAULT_RULES))
    fallbacks: list[str] = field(default_factory=list)  # audit log of dropped axes

    # -- mesh helpers -------------------------------------------------------
    def axis_size(self, *names: str) -> int:
        return int(np.prod([self.mesh.shape[n] for n in names if n in self.mesh.axis_names] or [1]))

    @property
    def dp_axes(self) -> tuple[str, ...]:
        return tuple(a for a in DP_AXES if a in self.mesh.axis_names)

    @property
    def fsdp_axes(self) -> tuple[str, ...]:
        return tuple(a for a in FSDP_AXES if a in self.mesh.axis_names)

    @property
    def tp_axis(self) -> Optional[str]:
        return "model" if "model" in self.mesh.axis_names else None

    # -- resolution ---------------------------------------------------------
    def resolve_dim(self, logical: Optional[str], size: int,
                    used: Optional[set] = None) -> Optional[tuple[str, ...]]:
        """First candidate that is present, unused, and divides the dim."""
        if logical is None:
            return None
        used = used or set()
        for cand in self.rules.get(logical, [None]):
            axes = _expand(cand, self.mesh)
            if axes is None:
                return None
            if any(a in used for a in axes):
                continue  # axis already shards another dim — try next candidate
            total = int(np.prod([self.mesh.shape[a] for a in axes]))
            if total <= 1:
                continue
            if size % total == 0:
                return axes
            self.fallbacks.append(f"{logical}({size}) !% {axes}({total})")
        return None

    def spec(self, shape: Sequence[int], axes: Sequence[Optional[str]]) -> P:
        assert len(shape) == len(axes), (shape, axes)
        used: set[str] = set()
        parts: list[Any] = []
        for size, logical in zip(shape, axes):
            r = self.resolve_dim(logical, size, used)
            if r is None:
                parts.append(None)
            else:
                used.update(r)
                parts.append(r if len(r) > 1 else r[0])
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, shape, axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(shape, axes))

    def constrain(self, x, *axes: Optional[str]):
        """with_sharding_constraint by logical axes (len must match x.ndim)."""
        return jax.lax.with_sharding_constraint(x, self.sharding(x.shape, axes))

    # -- tree-level ---------------------------------------------------------
    # tree.map uses the first tree's structure; flatten_up_to stops at its leaf
    # boundary, so the axes tuples in the second tree arrive whole.
    def tree_shardings(self, abstract_tree, axes_tree):
        return jax.tree.map(lambda sds, ax: self.sharding(sds.shape, ax),
                            abstract_tree, axes_tree)

    def tree_abstract(self, abstract_tree, axes_tree):
        """Attach shardings to a ShapeDtypeStruct tree (dry-run inputs)."""
        def one(sds, ax):
            return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                        sharding=self.sharding(sds.shape, ax))
        return jax.tree.map(one, abstract_tree, axes_tree)


def make_smoke_ctx() -> ShardCtx:
    """1-device mesh with the production axis names (CPU tests)."""
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    return ShardCtx(mesh)
