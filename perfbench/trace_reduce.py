"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: the traced window, the device's operations in it, their busy
union, and what the host was doing in each idle gap.

The trace is read with ``jax.profiler.ProfileData`` alone.  On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per executed HLO operation (a Pallas kernel is the custom call named after
its kernel function); the host's plane ``/host:CPU`` holds a line per
thread, with the benchmark's ``jax.profiler.TraceAnnotation`` spans on the
thread that drove the window.  Both share one clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

WINDOW_SPAN = "perfbench.window"
#: a Pallas kernel's device event: its HLO custom call (sound while a cell's
#: program runs one Pallas kernel)
PALLAS_KERNEL = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = re.compile(r"/device:TPU:\d+\Z")
OP_LINE = "XLA Ops"

Event = tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclass
class Reduced:
    """One traced window: its bounds, every operation each device ran while
    the profiler was on (only the window's calls ran then), and the host
    spans of the driving thread in the window.  Busy time and idle gaps
    are clipped to the window; counts and kernel times are not, since the
    device's clock and the host's agree only to some microseconds."""
    start_ns: float
    end_ns: float
    devices: list[list[Event]]
    host: list[Event]

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def busy(self, device: int = 0) -> list[tuple[float, float]]:
        """The device's busy intervals, clipped to the window."""
        return _clip(union([(s, e) for _, s, e in self.devices[device]]),
                     self.start_ns, self.end_ns)

    def busy_s(self) -> float:
        """Seconds of the window in which some operation ran, averaged
        over devices."""
        if not self.devices:
            return 0.0
        return sum(_length(self.busy(d)) for d in range(len(self.devices))
                   ) / len(self.devices) / 1e9

    def matching(self, pattern: str, device: int = 0) -> list[Event]:
        if device >= len(self.devices):
            return []
        rx = re.compile(pattern)
        return [ev for ev in self.devices[device] if rx.search(ev[0])]

    def seconds_of(self, pattern: str, device: int = 0) -> float:
        """Seconds of the window in which an operation whose name matches
        ``pattern`` ran."""
        return _length(_clip(union([(s, e) for _, s, e
                                    in self.matching(pattern, device)]),
                             self.start_ns, self.end_ns)) / 1e9


def trace_file(trace_dir: str | Path) -> Path:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(path: str | Path, window: str = WINDOW_SPAN,
           device_plane: re.Pattern = DEVICE_PLANE,
           op_line: str = OP_LINE) -> Reduced:
    """Reduce the trace at ``path`` to the window span named ``window``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(path))
    bounds, host = None, []
    device_planes = []
    for plane in profile.planes:
        if device_plane.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:") and bounds is None:
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                spans = [ev for ev in events if ev[0] == window]
                if spans:
                    bounds, host = (spans[0][1], spans[0][2]), events
                    break
    if bounds is None:
        raise ValueError(f"no {window!r} span in {path}")
    lo, hi = bounds
    devices = []
    for plane in sorted(device_planes, key=lambda p: _index(p.name)):
        ops = []
        for line in plane.lines:
            if line.name == op_line:
                ops += [(e.name, e.start_ns, e.end_ns) for e in line.events]
        devices.append(sorted(ops, key=lambda ev: ev[1]))
    host = [ev for ev in host if ev[2] > lo and ev[1] < hi]
    return Reduced(lo, hi, devices, host)


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _index(plane_name: str) -> int:
    return int(plane_name.rsplit(":", 1)[1])


_HLO = re.compile(r"(%[\w.\-]+) = .*? ([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name: str) -> str:
    """``%fn.3 custom-call tpu_custom_call`` for an event named by its whole
    HLO instruction; other names pass through."""
    m = _HLO.match(name)
    if not m:
        return name
    target = _TARGET.search(name)
    return " ".join(m.groups() + ((target.group(1),) if target else ()))


def top_ops(red: Reduced, n: int = 10, device: int = 0) -> list[list]:
    """The ``n`` operations (by ``op_label``) that took the most device
    seconds; a loop's time includes its body's."""
    if device >= len(red.devices):
        return []
    total: dict[str, float] = {}
    for name, s, e in red.devices[device]:
        label = op_label(name)
        total[label] = total.get(label, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(red: Reduced, n: int = 10, device: int = 0) -> list[list]:
    """The ``n`` longest stretches of the window in which the device ran
    nothing, each named by the innermost host span that covers its middle
    (``host idle`` where the benchmark had no span open)."""
    if device >= len(red.devices):
        return []
    busy = red.busy(device)
    edges = [red.start_ns] + [t for iv in busy for t in iv] + [red.end_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        covering = [ev for ev in red.host if ev[1] <= mid <= ev[2]]
        label = (min(covering, key=lambda ev: ev[2] - ev[1])[0]
                 if covering else "host idle")
        out.append([label, (e - s) / 1e9])
    return out
