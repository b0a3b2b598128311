"""Reduction of a profiler trace to device intervals, idle share and breakdown.

``read_trace`` loads the newest ``*.xplane.pb`` that ``jax.profiler``
wrote and keeps two things: the operations that ran on the device
(events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and
the host spans the benchmark itself opened with ``TraceAnnotation``
(any host event whose name is in ``SPANS``).  All times are nanoseconds
on the trace's common clock.  The rest is plain interval arithmetic.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
# Spans the benchmark opens around its calls into the program.
SPANS = ("window", "job", "block_until_ready")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: list[Event]      # device operations, all devices
    spans: list[Event]    # the benchmark's host spans
    window: tuple[float, float]
    devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops_in_window(self) -> list[Event]:
        lo, hi = self.window
        return [e for e in self.ops if e.end > lo and e.start < hi]


def read_trace(log_dir: Path) -> Trace:
    """Parse the newest trace under ``log_dir`` (jax.profiler's layout)."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans, devices = [], [], 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0] or 0)
            devices += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name, e.start_ns, e.duration_ns, dev)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in SPANS)
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    w = max(windows, key=lambda s: s.dur)
    return Trace(ops=ops, spans=spans, window=(w.start, w.end), devices=max(devices, 1))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping or touching (start, end) intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals, window) -> list[tuple[float, float]]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy_ns(events: list[Event], window: tuple[float, float], devices: int) -> float:
    """Union of operation intervals inside ``window``, averaged over devices."""
    total = 0.0
    for dev in range(devices):
        spans = clip([(e.start, e.end) for e in events if e.device == dev], window)
        total += sum(b - a for a, b in union(spans))
    return total / devices


def idle_share(events: list[Event], window: tuple[float, float], devices: int = 1) -> float:
    """1 − busy ÷ window, as a fraction."""
    length = window[1] - window[0]
    return 1.0 - busy_ns(events, window, devices) / length


def top_ops(events: list[Event], n: int = 10) -> list[list]:
    """The ``n`` operations that took most device time, in seconds, by
    the instruction name before `` = `` in the event's name."""
    acc: dict[str, float] = {}
    for e in events:
        name = e.name.split(" = ", 1)[0]
        acc[name] = acc.get(name, 0.0) + e.dur
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(events: list[Event], spans: list[Event], window, n: int = 10) -> list[list]:
    """The ``n`` longest gaps with no device operation (device 0), each
    named by the innermost host span that covers most of it."""
    busy = union(clip([(e.start, e.end) for e in events if e.device == 0], window))
    gaps, cursor = [], window[0]
    for lo, hi in busy:
        if lo > cursor:
            gaps.append((cursor, lo))
        cursor = max(cursor, hi)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for lo, hi in gaps[:n]:
        best, best_key = "none", (0.0, 0.0)
        for s in spans:
            if s.name == WINDOW_SPAN:
                continue
            cover = min(hi, s.end) - max(lo, s.start)
            key = (cover, -s.dur)  # most overlap, then the innermost span
            if cover > 0 and key > best_key:
                best, best_key = s.name, key
        out.append([best, (hi - lo) * 1e-9])
    return out
