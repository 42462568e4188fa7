"""Fault tolerance: checkpoint roundtrip/publish, error-feedback compression,
straggler detection, optimizer convergence."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import checkpoint as ckpt
from repro.ft.stragglers import StepTimer, probe_devices
from repro.optim import adamw
from repro.optim.compression import compress_grads, dequantize, init_error, quantize


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree(key):
    ks = jax.random.split(key, 3)
    return {"w": {"a": jax.random.normal(ks[0], (16, 8)),
                  "b": jax.random.normal(ks[1], (4,))},
            "step_arr": jnp.arange(5)}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree(jax.random.key(0))
    ckpt.save(tmp_path, 7, tree)
    restored, manifest = ckpt.restore(tmp_path, tree)
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_multiple(tmp_path):
    t1, t2 = _tree(jax.random.key(1)), _tree(jax.random.key(2))
    ckpt.save(tmp_path, 10, t1)
    ckpt.save(tmp_path, 20, t2)
    assert ckpt.latest_step(tmp_path) == 20
    restored, _ = ckpt.restore(tmp_path, t2, step=10)
    np.testing.assert_array_equal(np.asarray(restored["w"]["a"]),
                                  np.asarray(t1["w"]["a"]))


def test_checkpoint_async(tmp_path):
    tree = _tree(jax.random.key(3))
    ckpt.save(tmp_path, 5, tree, blocking=False)
    ckpt.wait_async()
    assert ckpt.latest_step(tmp_path) == 5


def test_checkpoint_torn_write_fallback(tmp_path):
    tree = _tree(jax.random.key(4))
    ckpt.save(tmp_path, 5, tree)
    # corrupt LATEST to point at a missing dir (simulated preemption mid-publish)
    (Path(tmp_path) / "LATEST").write_text("step_99999999")
    assert ckpt.latest_step(tmp_path) == 5


def test_checkpoint_structure_mismatch_detected(tmp_path):
    ckpt.save(tmp_path, 1, _tree(jax.random.key(5)))
    with pytest.raises(AssertionError):
        ckpt.restore(tmp_path, {"different": jnp.zeros(3)})


# ---------------------------------------------------------------------------
# gradient compression (int8 error feedback)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.floats(min_value=1e-4, max_value=1e3))
def test_quantize_roundtrip_bounded(scale_mag):
    g = jnp.array([0.5, -1.0, 0.25, 1.0]) * scale_mag
    q, s = quantize(g)
    err = np.abs(np.asarray(dequantize(q, s) - g))
    assert err.max() <= float(s) / 2 * (1 + 1e-5)  # half-ulp of the int8 grid


def test_error_feedback_preserves_signal():
    """Sum of compressed grads over steps tracks the true sum (EF property)."""
    true_g = jnp.full((64,), 0.001)          # tiny gradient, below 1 int8 ulp
    grads = {"w": true_g}
    err = init_error(grads)
    total = jnp.zeros((64,))
    for _ in range(100):
        cg, err = compress_grads(grads, err)
        total = total + cg["w"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(true_g * 100),
                               rtol=0.15)


def test_compressed_sgd_converges():
    """SGD on a quadratic with int8 EF compression still converges."""
    w = jnp.array([5.0, -3.0, 2.0])
    target = jnp.array([1.0, 1.0, 1.0])
    err = init_error({"w": w})
    for _ in range(300):
        g = {"w": 2 * (w - target)}
        cg, err = compress_grads(g, err)
        w = w - 0.05 * cg["w"]
    np.testing.assert_allclose(np.asarray(w), np.asarray(target), atol=1e-2)


# ---------------------------------------------------------------------------
# stragglers
# ---------------------------------------------------------------------------

def test_probe_devices_runs():
    probes = probe_devices(nbytes=256 * 1024, passes=2, reps=2)
    assert len(probes) == len(jax.devices())
    assert all(p.gbps > 0 for p in probes)


def test_step_timer_flags_outlier():
    t = StepTimer(z_threshold=3.0)
    for i in range(20):
        t.update(i, 0.1 + 0.001 * (i % 3))
    assert t.update(20, 1.0) is True        # 10x step time => straggler
    assert t.slow_steps and t.slow_steps[-1][0] == 20


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                            total_steps=200, grad_clip=100.0)
    params = {"w": jnp.array([4.0, -2.0])}
    state = adamw.init_state(params)
    for _ in range(200):
        g = {"w": 2 * params["w"]}
        params, state, _ = adamw.apply(cfg, params, state, g)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adamw_grad_clip():
    cfg = adamw.AdamWConfig(grad_clip=1.0)
    params = {"w": jnp.zeros(3)}
    state = adamw.init_state(params)
    _, _, m = adamw.apply(cfg, params, state, {"w": jnp.full(3, 100.0)})
    assert float(m["grad_norm"]) > 1.0       # reported pre-clip


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_ratio=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(s))) for s in [1, 5, 10, 50, 100]]
    assert lrs[0] < lrs[1] < lrs[2] == pytest.approx(1.0, abs=0.01)
    assert lrs[3] < 1.0 and lrs[4] == pytest.approx(0.1, abs=0.02)


def test_bf16_moment_storage():
    cfg = adamw.AdamWConfig()
    params = {"w": jnp.zeros(4)}
    state = adamw.init_state(params)
    state = {"mu": jax.tree.map(lambda x: x.astype(jnp.bfloat16), state["mu"]),
             "nu": jax.tree.map(lambda x: x.astype(jnp.bfloat16), state["nu"]),
             "step": state["step"]}
    _, new_state, _ = adamw.apply(cfg, params, state, {"w": jnp.ones(4)})
    assert new_state["mu"]["w"].dtype == jnp.bfloat16
