"""Pallas kernels vs pure-jnp oracles, swept over shapes and dtypes
(interpret mode on CPU; the kernel bodies are the TPU programs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.buffers import working_set
from repro.kernels.flash_attention.ops import flash
from repro.kernels.flash_attention.ref import reference as flash_ref
from repro.kernels.membench import ops as mb_ops
from repro.kernels.membench.ref import reference as mb_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import reference as ssd_ref

# ---------------------------------------------------------------------------
# membench kernels — sweep shapes x dtypes x mixes x block shapes x streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbytes", [16 * 1024, 128 * 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mix", ["load_sum", "copy", "fma_4", "mxu"])
@pytest.mark.parametrize("block_rows,streams", [(8, 1), (32, 2), (16, 4)])
def test_membench_vs_ref(nbytes, dtype, mix, block_rows, streams):
    x = working_set(nbytes, dtype=dtype)
    if x.shape[0] % (block_rows * streams):
        pytest.skip("shape not divisible")
    fn = mb_ops.make_kernel(mix=mix, block_rows=block_rows, streams=streams,
                            interpret=True)
    out = fn(x)
    ref = mb_ref(mix, x, depth=4, block_rows=block_rows)
    n = x.size
    # (v,1/v,-v,-1/v) sums cancel exactly; tolerance scales with n*eps*|v|
    eps = 1e-7 if dtype == jnp.float32 else 8e-3
    atol = max(n * eps * 1.3, 1e-4)
    if mix == "copy":
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=atol)
    else:
        assert abs(float(out) - float(ref)) < atol, (mix, float(out), float(ref))


def test_membench_stream_orders_equivalent():
    """All stream interleavings must visit every block exactly once."""
    x = working_set(64 * 1024)
    outs = [float(mb_ops.make_kernel("load_sum", block_rows=16, streams=s)(x))
            for s in (1, 2, 4)]
    assert max(outs) - min(outs) < 1e-3


def test_membench_interpret_follows_platform(monkeypatch):
    """Interpreted exactly on the CPU; on an accelerator the kernels compile
    and a request for the interpreter is refused."""
    from repro.kernels.membench.membench import resolve_interpret
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True)


def test_membench_work_accounting():
    x = working_set(32 * 1024)
    b, f = mb_ops.work_per_call("load_sum", x)
    assert b == x.size * 4 and f == x.size
    b, f = mb_ops.work_per_call("copy", x)
    assert b == 2 * x.size * 4
    b, f = mb_ops.work_per_call("fma_8", x)
    assert f == 16 * x.size


# ---------------------------------------------------------------------------
# flash attention — shape/dtype sweep vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,D", [
    (2, 128, 8, 4, 64), (1, 256, 4, 4, 32), (2, 128, 8, 2, 64),
    (1, 128, 16, 16, 32),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(B, S, H, KV, D, causal, dtype):
    ks = jax.random.split(jax.random.key(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    out = flash(q, k, v, causal=causal, q_block=64, kv_block=64)
    ref = flash_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_block_shape_invariance():
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 4, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 4, 32), jnp.float32)
    a = flash(q, k, v, causal=True, q_block=256, kv_block=256)
    b = flash(q, k, v, causal=True, q_block=32, kv_block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan — vs token-level recurrence oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("BH,S,P,N,Q", [
    (4, 128, 32, 16, 32), (2, 256, 64, 32, 64), (1, 64, 16, 8, 16),
])
def test_ssd_vs_recurrence(BH, S, P, N, Q):
    ks = jax.random.split(jax.random.key(BH + S), 4)
    xdt = jax.random.normal(ks[0], (BH, S, P)) * 0.5
    dA = -jnp.abs(jax.random.normal(ks[1], (BH, S))) * 0.3
    Bm = jax.random.normal(ks[2], (BH, S, N)) * 0.5
    Cm = jax.random.normal(ks[3], (BH, S, N)) * 0.5
    y, st = ssd(xdt, dA, Bm, Cm, chunk=Q)
    yr, sr = ssd_ref(xdt, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), rtol=2e-4,
                               atol=2e-4)


def test_ssd_chunk_invariance():
    ks = jax.random.split(jax.random.key(5), 4)
    BH, S, P, N = 2, 128, 16, 8
    xdt = jax.random.normal(ks[0], (BH, S, P)) * 0.5
    dA = -jnp.abs(jax.random.normal(ks[1], (BH, S))) * 0.3
    Bm = jax.random.normal(ks[2], (BH, S, N)) * 0.5
    Cm = jax.random.normal(ks[3], (BH, S, N)) * 0.5
    y1, s1 = ssd(xdt, dA, Bm, Cm, chunk=32)
    y2, s2 = ssd(xdt, dA, Bm, Cm, chunk=128)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)


def test_model_ssd_matches_kernel():
    """models/ssm.ssd_chunked (XLA path) == Pallas kernel on the same inputs."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.key(7), 4)
    B, S, H, P, N = 2, 128, 4, 16, 8
    xh = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.5 + 0.1
    A = -jnp.ones((H,)) * 0.5
    Bm = jax.random.normal(ks[2], (B, S, 1, N)) * 0.5
    Cm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.5
    y_model, st_model = ssd_chunked(xh, dt, A, Bm, Cm, 32)
    # kernel expects per-head streams and dt-weighted x
    xdt = (xh * dt[..., None]).transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dA = (dt * A[None, None, :]).transpose(0, 2, 1).reshape(B * H, S)
    Bk = jnp.broadcast_to(Bm, (B, S, H, N)).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    Ck = jnp.broadcast_to(Cm, (B, S, H, N)).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    y_k, _ = ssd(xdt.astype(jnp.float32), dA, Bk, Ck, chunk=32)
    y_k = y_k.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_k),
                               rtol=5e-3, atol=5e-3)
