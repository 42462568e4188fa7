"""The reduction from a profiler trace to the per-layer numbers: interval
arithmetic on hand-made events, and the whole reduction on a small trace
recorded on one TPU v5e (``data/stream_copy.xplane.pb``: 2 s of
``stream_copy``, 70 timed calls of 4 Pallas copy passes over 1 GiB)."""
from pathlib import Path

import pytest

from perfbench import harness, trace_reduce
from perfbench.trace_reduce import Reduced

DATA = Path(__file__).parent / "data"


def test_union_merges_overlaps_and_keeps_gaps():
    got = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert got == [(0, 3), (5, 8), (10, 11)]


def _reduced():
    ops = [("k", 10, 20), ("k", 30, 40), ("fusion", 35, 50), ("k", 80, 90)]
    host = [("perfbench.window", 0, 100), ("perfbench.call", 6, 55),
            ("perfbench.call", 60, 95)]
    return Reduced(0, 100, [ops], host)


def test_busy_is_the_union_of_operations():
    red = _reduced()
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s() == pytest.approx(40e-9)        # 10 + 20 + 10
    assert red.seconds_of("^k$") == pytest.approx(30e-9)
    assert len(red.matching("^k$")) == 3


def test_top_ops_and_idle_gaps_are_named():
    red = _reduced()
    assert trace_reduce.top_ops(red) == [["k", pytest.approx(30e-9)],
                                         ["fusion", pytest.approx(15e-9)]]
    gaps = trace_reduce.idle_gaps(red)
    assert gaps[0] == ["perfbench.call", pytest.approx(30e-9)]   # 50..80
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert sum(g[1] for g in gaps) == pytest.approx(60e-9)
    assert ["perfbench.window", pytest.approx(10e-9)] in gaps    # 0..10


def test_no_device_plane_gives_nothing():
    red = Reduced(0, 100, [], [])
    assert red.busy_s() == 0.0
    assert red.matching("k") == []
    assert trace_reduce.top_ops(red) == [] == trace_reduce.idle_gaps(red)


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce(DATA / "stream_copy.xplane.pb")


def test_recorded_trace_window_and_busy(recorded):
    assert recorded.window_s == pytest.approx(2.028953717)
    assert recorded.busy_s() == pytest.approx(1.964460934)
    assert len(recorded.devices) == 1


def test_recorded_trace_kernel_runs_once_per_pass(recorded):
    calls = sum(1 for ev in recorded.host if ev[0] == "perfbench.call")
    kernels = recorded.matching(trace_reduce.PALLAS_KERNEL)
    assert calls == 70 and len(kernels) == 4 * calls
    mean_s = sum(e - s for _, s, e in kernels) / len(kernels) / 1e9
    assert mean_s == pytest.approx(6.201659503571428e-3)
    # the kernel's share of the v5e roofline: 2 GiB per pass at 819 GB/s
    assert 100 * 2 * 2**30 / 819e9 / mean_s == pytest.approx(42.2803, abs=1e-4)


def test_kernel_event_gap_shows_passes_left_out_or_added(recorded):
    assert harness.kernel_event_gap(recorded, 70, 4) == 0
    kernels = recorded.matching(trace_reduce.PALLAS_KERNEL)
    halved = Reduced(recorded.start_ns, recorded.end_ns,
                     [[ev for ev in recorded.devices[0]
                       if ev not in kernels[::2]]], recorded.host)
    assert harness.kernel_event_gap(halved, 70, 4) == 140
    assert harness.kernel_event_gap(recorded, 70, 3) == 70


def test_recorded_trace_breakdown(recorded):
    ops = trace_reduce.top_ops(recorded)
    assert ops[0][0] == "%while.1 while"
    assert ops[1][0] == "%fn.3 custom-call tpu_custom_call"
    assert ops[2][0] == "%copy.15 copy"
    assert len(ops) <= 10 and len(trace_reduce.idle_gaps(recorded)) == 10
    idle = sum(g[1] for g in trace_reduce.idle_gaps(recorded, n=10**6))
    assert idle == pytest.approx(recorded.window_s - recorded.busy_s())
