"""Device time by the program's scopes, and device idle by its spans.

Two readings of a profiler trace beside ``bench/trace.py``'s, over the
same window (the ``bench_window`` spans):

- ``device_scopes``: the self time of every device operation, summed by
  where it ran. An operation inside a ``jit_track_frame`` event of the
  device's ``XLA Modules`` line goes to the innermost ``tracker.*``
  scope (``jax.named_scope`` in ``core/tracker.py``) in its metadata in
  the compiled step, or to ``unscoped`` where it has none; one inside
  any other module goes to that module's name (``jit__threefry_split``).
- ``program_gaps``: the idle time of the busiest device (the gaps
  ``bench/trace.py`` charges to ``idle_gaps``), each stretch of it
  charged to the innermost ``tracker.*`` host span open then
  (``Tracker.step``'s spans), under the benchmark span open around it:
  ``step>tracker.dispatch``. With several client threads, each thread's
  label, sorted and joined by ``+``. Where no thread has a program span
  open, the label is ``bench/trace.py``'s: the benchmark spans open, or
  ``host``. A gap that crosses spans is split between them, not charged
  whole to the span at its midpoint as ``idle_gaps`` does: the host's
  round trip between two frame steps is one gap that crosses five spans.

- ``step_runs``: the runs of the frame step whose device operations the
  trace kept, the fewest over the devices. The profiler keeps a bounded
  number of device events and drops the rest; a frame step it dropped
  reads as idle in every reading above and in ``bench/trace.py``'s, so
  the harness holds this count against the frames of the window.

The trace names each operation but not its scope: that is in the
compiled step's HLO text (``compiled.as_text()``), whose instruction
names the trace's op names are.

The device's timestamps and the host's are not on one clock: in traces
from a TPU v5e every program starts on the device 0.2 to 1.6 ms before
the host began to enqueue it, and the lag is not constant: it stepped
down by 0.08 to 0.28 ms once, about 1 s into a 3 s trace. Each gap is
moved onto the host's clock by ``clock_lag`` at its midpoint, which the
runtime's enqueue events pin down: they carry the program's ``run_id``,
as the device's module events do.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

import numpy as np

from bench import trace

STEP_MODULE = "jit_track_frame"
MODULES_LINE = "XLA Modules"
PROGRAM_SPAN = "tracker."
UNSCOPED = "unscoped"
RUN_ID = "run_id"
QUEUED_NS = 1e6  # a run this far below the tightest waited on the device
LAG_WINDOW_NS = 0.1e9  # some 30 runs at 128x128, 7 at 320x240

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')
_SCOPE = re.compile(r"tracker\.\w+")


@dataclasses.dataclass(frozen=True)
class ScopeSummary:
    device_scopes: list  # [[scope or module, seconds], ...], largest first
    program_gaps: list  # [[open spans, seconds], ...], largest first
    clock_lag: list  # vertices [(device ns, ns to add to read it on the host's clock)]
    step_runs: int  # frame steps with their device operations kept, fewest over devices


def op_scopes(hlo_text: str) -> dict[str, str]:
    """The innermost ``tracker.*`` scope of each instruction of an HLO
    module's text, by instruction name; those with none are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scopes = _SCOPE.findall(m.group(2))
            if scopes:
                out[m.group(1)] = scopes[-1]
    return out


def module_name(event_name: str) -> str:
    """``jit_track_frame`` of ``jit_track_frame(10918100778859533558)``."""
    return event_name.split("(", 1)[0]


def _run_id(event):
    return next((value for key, value in event.stats if key == RUN_ID), None)


def clock_lag(module_runs: list[tuple[float, int]], host_runs: dict) -> list[tuple[float, float]]:
    """How far the device's clock runs behind the host's, as vertices
    ``[(device time, lag)]`` to interpolate between, constant past the
    ends; empty where no run is on both clocks.

    A program's start on the device, ``(start, run_id)``, cannot precede
    the first host event of its run (``host_runs``: run_id to host time),
    so each run bounds the lag from below, tightly for a program that
    did not wait on the device. Runs more than ``QUEUED_NS`` below the
    tightest waited behind other programs and are left out; at each run
    left, the lag is the tightest bound within ``LAG_WINDOW_NS``, which
    follows the lag where it steps."""
    points = sorted((start, host_runs[run] - start) for start, run in module_runs
                    if run in host_runs)
    if not points:
        return []
    top = max(lag for _, lag in points)
    points = [(t, lag) for t, lag in points if lag > top - QUEUED_NS]
    times = [t for t, _ in points]
    return [(t, max(lag for _, lag in points[bisect.bisect_left(times, t - LAG_WINDOW_NS):
                                             bisect.bisect_right(times, t + LAG_WINDOW_NS)]))
            for t in times]


def _kept_runs(ops: list, modules: list, module: str) -> int:
    """Runs of ``module`` (module events, by start) in which at least one
    of ``ops`` starts."""
    starts = sorted(s for _, s, _ in ops)
    return sum(1 for name, s, e in modules if module_name(name) == module
               and bisect.bisect_left(starts, s) < bisect.bisect_left(starts, e))


def _segments(spans: list[tuple[str, float, float]]) -> list[tuple]:
    """One thread's nested spans as disjoint stretches, sorted, each with
    the innermost benchmark span and the innermost program span open in
    it: ``[(start, end, benchmark span or None, program span or None)]``."""
    out, stack, cursor = [], [], 0.0

    def emit(end):
        if end > cursor:
            names = [n for n, _ in reversed(stack)]
            bench = next((n for n in names if n in trace.HOST_SPANS), None)
            program = next((n for n in names if n.startswith(PROGRAM_SPAN)), None)
            out.append((cursor, end, bench, program))

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            cursor = max(cursor, stack.pop()[1])
        if stack:
            emit(s)
        stack.append((name, e))
        cursor = s
    while stack:
        emit(stack[-1][1])
        cursor = max(cursor, stack.pop()[1])
    return out


def _open_at(segments: list[tuple], starts: list[float], t: float) -> tuple:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < segments[i][1]:
        return segments[i][2], segments[i][3]
    return None, None


def _label(open_spans: list[tuple]) -> str:
    if any(program for _, program in open_spans):
        labels = {f"{bench}>{program}" if bench and program else bench or program
                  for bench, program in open_spans if bench or program}
        return "+".join(sorted(labels))
    benches = {bench for bench, _ in open_spans}
    return "+".join(n for n in trace.HOST_SPANS if n in benches) or "host"


def _stretches(threads: list[list]) -> list[tuple[float, float, str]]:
    """The host's time line cut wherever a thread opens or closes a span,
    each stretch with the label of what the threads have open in it."""
    segments = [_segments(spans) for spans in threads]
    starts = [[s for s, *_ in seg] for seg in segments]
    cuts = sorted({t for seg in segments for s, e, *_ in seg for t in (s, e)})
    cuts = [-float("inf")] + cuts + [float("inf")]
    return [(a, b, _label([_open_at(seg, st, 0.5 * (a + b)) for seg, st in zip(segments, starts)]))
            for a, b in zip(cuts, cuts[1:])]


def reduce(planes, scope_of: dict[str, str]) -> ScopeSummary | None:
    """Readings from the planes of one trace, with ``scope_of`` from
    :func:`op_scopes` of the compiled step, or None where the trace holds
    no benchmark window or no device operation."""
    window: list[trace.Interval] = []
    threads: list[list] = []  # per host line: its benchmark and program spans
    devices: list[tuple[list, list]] = []  # per device: (ops, modules)
    module_runs: list[tuple[float, int]] = []  # (start on the device, run_id)
    host_runs: dict = {}  # run_id: start of the first host event of the run
    for plane in planes:
        if trace.DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops = list(trace._events(line))
                elif line.name == MODULES_LINE:
                    modules = sorted(trace._events(line), key=lambda m: m[1])
                    module_runs += [(float(ev.start_ns), _run_id(ev)) for ev in line.events]
            if ops:
                devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    name, s = ev.name, float(ev.start_ns)
                    if name == trace.WINDOW_SPAN:
                        window.append((s, s + ev.duration_ns))
                    elif name in trace.HOST_SPANS or name.startswith(PROGRAM_SPAN):
                        spans.append((name, s, s + ev.duration_ns))
                    else:
                        run = _run_id(ev)
                        if run is not None and s < host_runs.get(run, s + 1):
                            host_runs[run] = s
                if spans:
                    threads.append(spans)
    if not window or not devices:
        return None
    lo = min(s for s, _ in window)
    hi = max(e for _, e in window)

    scope_ns: dict[str, float] = collections.Counter()
    busy_each = []
    step_runs = min(_kept_runs(ops, modules, STEP_MODULE) for ops, modules in devices)
    for ops, modules in devices:
        starts = [s for _, s, _ in modules]
        labelled = []
        for name, s, e in ops:
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = module_name(modules[i][0]) if i >= 0 and s < modules[i][2] else None
            if module == STEP_MODULE:
                label = scope_of.get(trace.op_name(name), UNSCOPED)
            else:
                label = module or UNSCOPED
            labelled.append((label, max(s, lo), min(e, hi)))
        scope_ns.update(trace.self_times(labelled))
        busy_each.append(trace.union([(s, e) for _, s, e in labelled]))

    lag = clock_lag(module_runs, host_runs)
    stretches = _stretches(threads)
    stretch_starts = [a for a, _, _ in stretches]
    idle: dict[str, float] = collections.Counter()
    busiest = max(busy_each, key=lambda b: sum(e - s for s, e in b))
    idle_gaps = trace.gaps(busiest, lo, hi)
    mids = [0.5 * (s + e) for s, e in idle_gaps]
    shifts = np.interp(mids, *zip(*lag)) if lag else np.zeros(len(mids))
    for (s, e), shift in zip(idle_gaps, shifts.tolist()):
        s, e = s + shift, e + shift
        i = bisect.bisect_right(stretch_starts, s) - 1
        while i < len(stretches) and stretches[i][0] < e:
            a, b, label = stretches[i]
            idle[label] += (min(b, e) - max(a, s)) * 1e-9
            i += 1

    return ScopeSummary(
        device_scopes=[[n, t * 1e-9] for n, t in scope_ns.most_common()],
        program_gaps=[[n, t] for n, t in idle.most_common()],
        clock_lag=lag,
        step_runs=step_runs,
    )

