"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's readings.

The window is the union of the benchmark's ``bench_window`` host spans.
Device busy time is the union of the intervals in which an operation ran
on a device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
clipped to the window and averaged over the devices that ran anything.
An idle gap is a stretch of the window in which no operation ran; each
is charged to the benchmark host spans (``step``, ``fetch``) open at its
midpoint, or to ``host`` where none was.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("step", "fetch")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10

Interval = tuple[float, float]


@dataclasses.dataclass(frozen=True)
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the devices that ran an operation
    devices: int
    device_ops: list  # [[op, seconds], ...]: the TOP largest self times, by op name
    idle_gaps: list  # [[open host spans, seconds], ...], the TOP largest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals: list[Interval]) -> list[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The stretches of [lo, hi] that no interval of ``busy`` covers."""
    out, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        out.append((cursor, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def op_name(event_name: str) -> str:
    """``fusion.3`` of ``%fusion.3 = f32[64,16384]{...} fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Nanoseconds of each op name less the time of the ops nested in it
    (a ``while`` holds its body's ops on the same line)."""
    out: dict[str, float] = collections.Counter()
    stack: list[list] = []  # [name, start, end, time of direct children]

    def close():
        name, s, e, children = stack.pop()
        out[name] += (e - s) - children

    for name, s, e in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return out


def reduce(planes) -> TraceSummary | None:
    """Readings from the planes of one trace, or None where the trace
    holds no benchmark window or no device operation."""
    window: list[Interval] = []
    spans: dict[str, list[Interval]] = collections.defaultdict(list)
    device_busy: list[list[Interval]] = []
    ops: list[tuple[str, float, float]] = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            busy = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for name, s, e in _events(line):
                        busy.append((s, e))
                        ops.append((op_name(name), s, e))
            if busy:
                device_busy.append(busy)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN:
                        window.append((s, e))
                    elif name in HOST_SPANS:
                        spans[name].append((s, e))
    if not window or not device_busy:
        return None
    lo = min(s for s, _ in window)
    hi = max(e for _, e in window)

    op_ns = self_times([(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi])
    busy_each = [union(clip(b, lo, hi)) for b in device_busy]
    busy_ns = sum(sum(e - s for s, e in b) for b in busy_each) / len(busy_each)

    idle: dict[str, float] = collections.Counter()
    open_spans = {name: union(iv) for name, iv in spans.items()}
    starts = {name: [a for a, _ in iv] for name, iv in open_spans.items()}

    def is_open(name: str, t: float) -> bool:
        i = bisect.bisect_right(starts.get(name, []), t) - 1
        return i >= 0 and t < open_spans[name][i][1]

    # the gaps of the device that was busiest
    busiest = max(busy_each, key=lambda b: sum(e - s for s, e in b))
    for s, e in gaps(busiest, lo, hi):
        mid = 0.5 * (s + e)
        label = "+".join(n for n in HOST_SPANS if is_open(n, mid)) or "host"
        idle[label] += (e - s) * 1e-9

    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9,
        devices=len(device_busy),
        device_ops=[[n, t * 1e-9] for n, t in op_ns.most_common(TOP)],
        idle_gaps=[[n, t] for n, t in idle.most_common(TOP)],
    )


def find_xplane(log_dir: Path) -> Path | None:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under log_dir."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None
