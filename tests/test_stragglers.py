"""Straggler detection: the per-device membench probe and the step-time
monitor."""
import jax

from repro.ft.stragglers import StepTimer, probe_devices


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
