"""Pallas kernels vs pure-jnp oracles, swept over shapes and dtypes
(interpret mode on CPU; the kernel bodies are the TPU programs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.buffers import working_set
from repro.kernels.membench import ops as mb_ops
from repro.kernels.membench.ref import reference as mb_ref

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
