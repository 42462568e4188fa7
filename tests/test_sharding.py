"""Sharding rule resolution: divisibility fallbacks, axis-usage chains."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardCtx
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def ctx111():
    return ShardCtx(make_mesh((1, 1, 1), ("pod", "data", "model")))


def test_single_device_everything_replicated(ctx111):
    spec = ctx111.spec((256, 4096), ("batch", "seq"))
    assert spec == P()


def test_fallback_on_non_divisible():
    # heads=40 on a 16-way model axis must fall back to replication
    ctx = ShardCtx(make_mesh((1, 1, 1), ("pod", "data", "model")))
    assert ctx.resolve_dim("heads", 40) is None


def test_axis_used_once():
    """One mesh axis may shard only one dim of a tensor."""
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    ctx = ShardCtx(mesh)
    spec = ctx.spec((64, 64), ("heads", "ffn"))  # both want 'model'
    # on a 1-device mesh both resolve to None
    assert spec == P()


def test_kv_seq_fallback_chain_documented():
    """batch takes data first; kv_seq then falls through to model."""
    ctx = ShardCtx(make_mesh((1, 1, 1), ("pod", "data", "model")))
    rules = ctx.rules["kv_seq"]
    assert rules[0] == ("data",) and rules[1] == ("model",)


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 4096), st.sampled_from(["heads", "ffn", "vocab", "embed",
                                              "batch", "kv_seq"]))
def test_spec_never_crashes(size, logical):
    ctx = ShardCtx(make_mesh((1, 1, 1), ("pod", "data", "model")))
    spec = ctx.spec((size,), (logical,))
    assert isinstance(spec, P)


def test_tree_abstract_attaches_shardings(ctx111):
    import jax.numpy as jnp
    tree = {"a": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
    axes = {"a": ("batch", "embed")}
    out = ctx111.tree_abstract(tree, axes)
    assert out["a"].sharding is not None
    assert out["a"].shape == (8, 16)


def test_param_specs_cover_all_leaves():
    """every model parameter must carry logical axes of matching rank."""
    from repro.configs import get_arch, list_archs, reduced
    from repro.models.common import abstract_params, logical_axes
    from repro.models.registry import build
    for name in list_archs():
        model = build(reduced(get_arch(name)))
        specs = model.param_specs()
        flat_abs = jax.tree.leaves(abstract_params(specs))
        flat_axes = jax.tree.leaves(logical_axes(specs),
                                    is_leaf=lambda x: isinstance(x, tuple))
        assert len(flat_abs) == len(flat_axes)
        for sds, ax in zip(flat_abs, flat_axes):
            assert len(sds.shape) == len(ax), (name, sds.shape, ax)
