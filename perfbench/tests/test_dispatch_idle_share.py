"""``dispatch_idle_share`` on windows built by hand: the device's idle time
inside ``case.dispatch`` spans, as a share of the window, once the device
plane is on the host's clock."""
from types import SimpleNamespace

import pytest

from conftest import BENCH
from perfbench import harness
from perfbench.trace_reduce import Reduced

READER = harness.load_module(BENCH / "metrics" / "dispatch_idle_share.py")


def read(red):
    return READER.read(SimpleNamespace(trace=red))


def loop(calls, ahead=0.0, lo=0.0, hi=3000.0):
    """A window of back-to-back calls, each ``(start, dispatch_end,
    program_start, program_end, end)`` on the host's clock, with the device
    plane's events ``ahead`` of it."""
    host = [("perfbench.window", lo, hi)]
    ops = []
    for start, dispatch_end, p0, p1, end in calls:
        host += [("perfbench.call", start, end),
                 ("case.dispatch", start, dispatch_end)]
        # two operations a program, back to back
        ops += [("op", p0 - ahead, (p0 + p1) / 2 - ahead),
                ("op", (p0 + p1) / 2 - ahead, p1 - ahead)]
    return Reduced(lo, hi, [ops], host)


# dispatch 100, launch 50 after it, program 600, completion wait 150
SERIAL = [(1000 * k, 1000 * k + 100, 1000 * k + 150, 1000 * k + 750,
           1000 * k + 900) for k in range(3)]


@pytest.mark.parametrize("ahead", [0.0, 400.0, 1800.0])
def test_serial_calls_read_their_dispatch_time(ahead):
    # 3 x 100 of idle dispatch in a window of 3000, however far ahead the
    # device plane runs
    assert read(loop(SERIAL, ahead)) == pytest.approx(10.0, abs=0,
                                                      rel=1e-12)


def test_raw_clocks_would_hide_the_dispatch():
    """The device plane 400 ahead puts each program over its own dispatch:
    without the shift the reading would be 0."""
    red = loop(SERIAL, ahead=400.0)
    busy = red.busy(0)
    assert any(s <= 0 and e >= 100 for s, e in busy)
    assert read(red) > 0


def test_dispatch_over_a_running_program_counts_only_its_idle_part():
    # the second call is dispatched while the first program still runs
    # (no wait between them): 60 of its 100 fall in device time
    calls = [(0, 100, 100, 700, 710), (640, 740, 740, 1340, 1400)]
    assert read(loop(calls, ahead=250.0, hi=1400.0)) == pytest.approx(
        100.0 * (100 + 40) / 1400, abs=0, rel=1e-12)


def test_dispatch_spans_are_clipped_to_the_window():
    calls = [(-50, 50, 100, 700, 900), (1000, 1100, 1150, 1750, 1900)]
    assert read(loop(calls, hi=2000.0)) == pytest.approx(7.5, abs=0,
                                                         rel=1e-12)


def test_a_gap_outside_every_dispatch_span_counts_zero():
    # the window opens as the first program starts; the second call is
    # dispatched while it runs; the device then idles 1600-1700, outside
    # every dispatch span
    calls = [(0, 100, 100, 900, 950), (200, 300, 910, 1600, 1700)]
    red = loop(calls, ahead=500.0, lo=100.0, hi=1700.0)
    assert read(red) == 0.0


def test_none_without_a_device_plane_or_dispatch_spans():
    assert read(None) is None
    red = loop(SERIAL)
    assert read(Reduced(red.start_ns, red.end_ns, [], red.host)) is None
    no_spans = [ev for ev in red.host if ev[0] != "case.dispatch"]
    assert read(Reduced(red.start_ns, red.end_ns, red.devices,
                        no_spans)) is None
    # fewer device runs than dispatches: nothing to pair them with
    one_op = [[("op", 150, 750)]]
    assert read(Reduced(red.start_ns, red.end_ns, one_op, red.host)) is None


def test_split_names_are_read_by_the_one_file():
    for part in ("bw", "chase"):
        assert harness.reader_path(BENCH, f"dispatch_idle_share.{part}") \
            == BENCH / "metrics" / "dispatch_idle_share.py"
