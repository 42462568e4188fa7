"""Attention: chunked online-softmax vs naive oracle, folded variant, RoPE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention.ref import reference as naive_attention
from repro.models.attention import (apply_rope, chunked_attention,
                                    folded_causal_attention, rope_freqs)


def _qkv(key, B, Sq, Sk, H, KV, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, Sq, H, D), dtype),
            jax.random.normal(ks[1], (B, Sk, KV, D), dtype),
            jax.random.normal(ks[2], (B, Sk, KV, D), dtype))


@pytest.mark.parametrize("B,Sq,Sk,H,KV,D,causal,blk", [
    (2, 128, 128, 8, 4, 64, True, 32),
    (1, 64, 64, 4, 4, 32, False, 16),
    (2, 96, 96, 6, 2, 16, True, 32),       # uneven: Sk % blk != 0 path
    (1, 128, 1500 % 128 + 64, 4, 4, 32, False, 64),  # padded KV
])
def test_chunked_matches_naive(B, Sq, Sk, H, KV, D, causal, blk):
    q, k, v = _qkv(jax.random.key(1), B, Sq, Sk, H, KV, D)
    out = chunked_attention(q, k, v, causal=causal, kv_block=blk, q_block=blk)
    ref = naive_attention(q, k, v, causal=causal)
    # chunked_attention computes in bf16 (production mixed precision); the
    # oracle is f32 => bf16-epsilon tolerance
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_folded_matches_masked():
    q, k, v = _qkv(jax.random.key(2), 2, 256, 256, 8, 4, 32)
    masked = chunked_attention(q, k, v, causal=True, kv_block=64, q_block=64)
    folded = folded_causal_attention(q, k, v, q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(folded), np.asarray(masked),
                               rtol=2e-5, atol=2e-5)


def test_query_blocking_invariance():
    q, k, v = _qkv(jax.random.key(3), 1, 256, 256, 4, 4, 32)
    a = chunked_attention(q, k, v, causal=True, kv_block=256, q_block=256)
    b = chunked_attention(q, k, v, causal=True, kv_block=64, q_block=64)
    # different block decompositions reorder bf16 accumulation
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-2, atol=1e-2)


# ---------------------------------------------------------------------------
# RoPE properties
# ---------------------------------------------------------------------------

def test_rope_norm_preserving():
    inv = rope_freqs(64, 1.0, 10000.0)
    x = jax.random.normal(jax.random.key(0), (1, 16, 2, 64))
    y = apply_rope(x, jnp.arange(16), inv)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)


def test_rope_relative_position():
    """<R(p)q, R(p)k> depends only on... identical positions => unrotated dot."""
    inv = rope_freqs(32, 1.0, 10000.0)
    q = jax.random.normal(jax.random.key(1), (1, 1, 1, 32))
    k = jax.random.normal(jax.random.key(2), (1, 1, 1, 32))
    for p in (0, 5, 100):
        qp = apply_rope(q, jnp.array([p]), inv)
        kp = apply_rope(k, jnp.array([p]), inv)
        d0 = float(jnp.sum(q * k))
        dp = float(jnp.sum(qp * kp))
        assert abs(d0 - dp) < 1e-3


def test_partial_rope():
    """rope_pct=0.25 must rotate only the first quarter of dims."""
    inv = rope_freqs(64, 0.25, 10000.0)
    assert inv.shape[0] * 2 == 16
    x = jax.random.normal(jax.random.key(3), (1, 4, 1, 64))
    y = apply_rope(x, jnp.arange(4), inv)
    np.testing.assert_allclose(np.asarray(x[..., 16:]), np.asarray(y[..., 16:]))
    assert not np.allclose(np.asarray(x[..., :16])[0, 1:],
                           np.asarray(y[..., :16])[0, 1:])


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.sampled_from([32, 64, 96]),
       st.sampled_from([(4, 4), (4, 2), (8, 1)]))
def test_chunked_attention_property(b, s, heads):
    """softmax rows sum to one => output within convex hull of V rows."""
    h, kv = heads
    q, k, v = _qkv(jax.random.key(b * s), b, s, s, h, kv, 16)
    out = np.asarray(chunked_attention(q, k, v, causal=True, kv_block=32,
                                       q_block=32))
    vmax = np.asarray(v).max()
    vmin = np.asarray(v).min()
    assert out.max() <= vmax + 1e-4 and out.min() >= vmin - 1e-4
