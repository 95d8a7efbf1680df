"""Device time by the program's scopes and device idle by its spans."""

import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import scopes, trace


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start, stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v) for k, v in lines.items()])


HLO = """\
HloModule jit_track_frame, is_scheduled=true
%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(track_frame)/tracker.spawn/tracker.evaluate/neg" source_file="/src/repro/core/tracker.py" source_line=3}
}
ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(track_frame)/tracker.spawn/tracker.evaluate/neg" source_file="/src/repro/core/tracker.py" source_line=3}
  %while.2 = f32[8]{0} while(%fusion.1), condition=%c, body=%b, metadata={op_name="jit(track_frame)/tracker.optimize/while" source_file="/src/repro/core/pso.py" source_line=9}
  %select_reduce_fusion.3 = f32[8]{0} fusion(%while.2), kind=kOutput, calls=%f3, metadata={op_name="jit(track_frame)/tracker.optimize/while/body/closed_call/tracker.evaluate/vmap()/dot_general" stack_frame_id=322}, backend_config={"flag_configs":[]}
  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%p)
  %tuple.5 = (f32[8]{0}) tuple(%p), metadata={op_name="jit(track_frame)/jit(main)/pjit" source_file="/src/repro/core/tracker.py" source_line=9}
  ROOT %add.6 = f32[8]{0} add(%select_reduce_fusion.3, %p), metadata={op_name="jit(track_frame)/tracker.refine/add" source_file="/src/repro/core/tracker.py" source_line=12}
}
"""

SCOPE_OF = scopes.op_scopes(HLO)


def test_op_scopes_takes_the_innermost_tracker_scope_of_each_instruction():
    assert SCOPE_OF == {
        "neg.1": "tracker.evaluate", "fusion.1": "tracker.evaluate",
        "while.2": "tracker.optimize", "select_reduce_fusion.3": "tracker.evaluate",
        "add.6": "tracker.refine",
    }


def test_module_names_drop_the_fingerprint():
    assert scopes.module_name("jit_track_frame(10918100778859533558)") == "jit_track_frame"
    assert scopes.module_name("jit__threefry_split(1)") == "jit__threefry_split"


def tpu(ops, modules):
    return plane("/device:TPU:0", XLA_Modules=modules, XLA_Ops=ops)


# One frame: the key split's module at 100-130, the step's at 300-900.
MODULES = [ev("jit__threefry_split(11)", 100, 130), ev("jit_track_frame(22)", 300, 900)]
OPS = [
    ev("%fusion.16 = u32[1]{0} fusion(u32[2]{0} %key.1), kind=kLoop", 100, 120),
    ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 300, 340),
    ev("%while.2 = f32[8]{0} while(f32[8]{0} %fusion.1)", 350, 800),
    ev("%select_reduce_fusion.3 = f32[8]{0} fusion(f32[8]{0} %while.2)", 360, 700),
    ev("%copy.9 = f32[8]{0} copy(f32[8]{0} %p)", 810, 830),
    ev("%add.6 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %p)", 840, 900),
]
HOST = plane(
    "/host:CPU",
    main=[ev("bench_window", 0, 1000)],
    worker=[
        ev("fetch", 0, 40), ev("step", 40, 960),
        ev("tracker.step", 50, 940), ev("tracker.key_split", 60, 150),
        ev("tracker.dispatch", 150, 320), ev("tracker.wait", 320, 920),
        ev("PjitFunction(track_frame)", 160, 310),  # the runtime's own: not a span of ours
    ],
)


def test_device_time_goes_to_the_innermost_scope_or_the_module():
    s = scopes.reduce([HOST, tpu(OPS, MODULES)], SCOPE_OF)
    assert dict(s.device_scopes) == pytest.approx({
        "tracker.evaluate": 380e-9,  # fusion.1 40 + select_reduce_fusion.3 340
        "tracker.optimize": 110e-9,  # while.2 less the fusion nested in it
        "tracker.refine": 60e-9,
        "unscoped": 20e-9,  # copy.9 has no metadata
        "jit__threefry_split": 20e-9,  # its fusion.16 is not the step's
    })


def test_idle_goes_to_the_innermost_program_span_under_its_benchmark_span():
    s = scopes.reduce([HOST, tpu(OPS, MODULES)], SCOPE_OF)
    # gaps 0-100, 120-300, 340-350, 800-810, 830-840, 900-1000, each split
    # where the host opens or closes a span
    assert dict(s.program_gaps) == pytest.approx({
        "fetch": 40e-9,  # 0-40
        "step": 30e-9,  # 40-50, 940-960
        "step>tracker.step": 30e-9,  # 50-60, 920-940
        "step>tracker.key_split": 70e-9,  # 60-100, 120-150
        "step>tracker.dispatch": 150e-9,  # 150-300
        "step>tracker.wait": 50e-9,  # 340-350, 800-810, 830-840, 900-920
        "host": 40e-9,  # 960-1000
    })


def test_idle_sums_to_the_window_s_and_without_program_spans_keeps_today_s_labels():
    bench_only = plane("/host:CPU", main=[ev("bench_window", 0, 1000)],
                       worker=[ev("fetch", 0, 40), ev("step", 40, 960)])
    for host in (HOST, bench_only):
        planes = [host, tpu(OPS, MODULES)]
        s, today = scopes.reduce(planes, SCOPE_OF), trace.reduce(planes)
        assert sum(t for _, t in s.program_gaps) == pytest.approx(today.window_s - today.busy_s)
        assert sum(t for _, t in s.device_scopes) == pytest.approx(today.busy_s)
    s = scopes.reduce([bench_only, tpu(OPS, MODULES)], SCOPE_OF)
    assert dict(s.program_gaps) == pytest.approx({"fetch": 40e-9, "step": 330e-9, "host": 40e-9})


def test_several_threads_join_their_labels_sorted():
    host = plane(
        "/host:CPU",
        main=[ev("bench_window", 0, 100)],
        a=[ev("step", 0, 100), ev("tracker.step", 0, 100), ev("tracker.wait", 10, 90)],
        b=[ev("step", 0, 50), ev("tracker.step", 0, 50), ev("tracker.dispatch", 0, 50),
           ev("fetch", 50, 100)],
        c=[ev("fetch", 0, 100)],
    )
    ops = [ev("%fusion.1 = f32[8]{0} fusion()", a, b) for a, b in ((8, 20), (22, 40), (60, 100))]
    s = scopes.reduce([host, tpu(ops, [ev("jit_track_frame(1)", 0, 100)])], SCOPE_OF)
    assert dict(s.program_gaps) == pytest.approx({
        "fetch+step>tracker.dispatch+step>tracker.step": 8e-9,  # 0-8: a before its wait
        "fetch+step>tracker.dispatch+step>tracker.wait": 12e-9,  # 20-22, 40-50
        "fetch+step>tracker.wait": 10e-9,  # 50-60: b has closed its step
    })


def test_an_op_outside_any_module_is_unscoped():
    s = scopes.reduce([HOST, tpu([ev("fusion.1", 100, 200)], [])], SCOPE_OF)
    assert dict(s.device_scopes) == pytest.approx({"unscoped": 100e-9})


def test_step_runs_count_the_frame_steps_whose_operations_were_kept():
    assert scopes.reduce([HOST, tpu(OPS, MODULES)], SCOPE_OF).step_runs == 1
    # a second frame step whose operations the profiler dropped
    dropped = MODULES + [ev("jit_track_frame(22)", 1100, 1700)]
    assert scopes.reduce([HOST, tpu(OPS, dropped)], SCOPE_OF).step_runs == 1
    assert scopes.reduce([HOST, tpu(OPS, MODULES[:1])], SCOPE_OF).step_runs == 0


@pytest.mark.parametrize("planes", [
    [HOST],  # no device ran anything
    [tpu(OPS, MODULES)],  # no benchmark window
    [HOST, plane("/device:CPU:0", XLA_Ops=OPS)],
], ids=["no_device_op", "no_window", "not_a_tpu"])
def test_nothing_to_read_gives_none(planes):
    assert scopes.reduce(planes, SCOPE_OF) is None


RECORDED = Path(__file__).parent / "data" / "tpu_v5e_scopes.xplane.pb.gz"
RECORDED_HLO = Path(__file__).parent / "data" / "tpu_v5e_scopes.hlo.txt.gz"


@pytest.fixture(scope="module")
def recorded():
    """A traced run of 32x32 frames on one TPU v5e (8 particles, 2
    generations, one client, a 0.04 s window) with the program's spans
    and scopes: the ``.xplane.pb`` that ``jax.profiler`` wrote, and the
    compiled step's HLO text, both gzipped."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))
    planes = list(data.planes)
    hlo = gzip.decompress(RECORDED_HLO.read_bytes()).decode()
    return data, planes, scopes.reduce(planes, scopes.op_scopes(hlo)), trace.reduce(planes)


def _events(planes, plane_match, line_name=None):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in planes if plane_match(p.name)
            for line in p.lines if line_name in (None, line.name) for ev in line.events]


def test_recorded_scopes_sum_to_the_step_s_busy_time(recorded):
    _, planes, s, today = recorded
    (lo, hi), = [(a, b) for n, a, b in _events(planes, lambda n: n.startswith("/host:"))
                 if n == trace.WINDOW_SPAN]
    device = trace.DEVICE_PLANE.match
    steps = [(a, b) for n, a, b in _events(planes, device, scopes.MODULES_LINE)
             if scopes.module_name(n) == scopes.STEP_MODULE]
    ops = trace.clip([(a, b) for _, a, b in _events(planes, device, trace.OPS_LINE)], lo, hi)
    in_step = [(a, b) for a, b in ops if any(s0 <= a < s1 for s0, s1 in steps)]
    step_busy = sum(b - a for a, b in trace.union(in_step)) * 1e-9
    by_scope = dict(s.device_scopes)
    ours = {k: v for k, v in by_scope.items() if k.startswith("tracker.") or k == scopes.UNSCOPED}
    assert set(ours) >= {"tracker.spawn", "tracker.optimize", "tracker.evaluate"}
    assert sum(ours.values()) == pytest.approx(step_busy, rel=1e-6)
    assert sum(by_scope.values()) == pytest.approx(today.busy_s, rel=1e-6)
    assert max(ours, key=ours.get) == "tracker.evaluate"
    assert by_scope.get(scopes.UNSCOPED, 0.0) < 0.05 * step_busy


def test_recorded_program_gaps_sum_to_the_window_s_idle_time(recorded):
    _, _, s, today = recorded
    assert sum(t for _, t in s.program_gaps) == pytest.approx(
        today.window_s - today.busy_s, rel=1e-6)
    labels = {n for n, _ in s.program_gaps}
    assert {"step>tracker.key_split", "step>tracker.dispatch"} <= labels


def test_recorded_steps_start_after_their_dispatch_opens_once_the_clocks_agree(recorded):
    """As recorded, the device's clock runs behind the host's: each frame
    step starts on the device before the host opened its
    ``tracker.dispatch``, by over 0.5 ms. Moved by ``clock_lag``, the
    k-th step starts after the k-th ``tracker.dispatch`` opened, and ends
    before the k-th ``tracker.wait`` (the score on the host) closed."""
    _, planes, s, _ = recorded
    host = _events(planes, lambda n: n.startswith("/host:"))
    dispatch = sorted(a for n, a, _ in host if n == "tracker.dispatch")
    waits = sorted(b for n, _, b in host if n == "tracker.wait")
    steps = sorted((a, b) for n, a, b in _events(planes, trace.DEVICE_PLANE.match, scopes.MODULES_LINE)
                   if scopes.module_name(n) == scopes.STEP_MODULE)
    assert len(steps) >= 2 and len(dispatch) >= len(steps)
    assert all(a < d - 0.5e6 for (a, _), d in zip(steps, dispatch))
    for k, (start, end) in enumerate(steps):
        shift = np.interp(start, *zip(*s.clock_lag))
        assert 0.5e6 < shift < 3e6
        assert dispatch[k] <= start + shift and end + shift < waits[k]


def test_the_device_clock_is_moved_to_the_host_s_by_run():
    """A program cannot start on the device before the host began to
    enqueue it (the first host event of its ``run_id``); the least shift
    that makes it so moves each gap onto the host's clock."""
    modules = [ev("jit_track_frame(22)", 300, 900, run_id=7)]
    ops = [ev("%fusion.1 = f32[8]{0} fusion()", 300, 900)]
    host = plane("/host:CPU", main=[ev("bench_window", 0, 1000)],
                 worker=[ev("step", 0, 1000), ev("tracker.step", 0, 1000),
                         ev("tracker.dispatch", 0, 450), ev("tracker.wait", 450, 1000)],
                 runtime=[ev("DoEnqueueProgram", 400, 420, run_id=7),
                          ev("Execute=>Done", 950, 960, run_id=7)])
    s = scopes.reduce([host, tpu(ops, modules)], SCOPE_OF)
    assert s.clock_lag == [(300.0, 100.0)]
    # the gap 0-300 reads 100-400 on the host's clock, in the dispatch; the
    # gap 900-1000 reads 1000-1100, after every span
    assert dict(s.program_gaps) == pytest.approx({"step>tracker.dispatch": 300e-9,
                                                 "host": 100e-9})


def test_the_lag_follows_the_tightest_runs_nearby_and_skips_queued_ones():
    ms = 1e6
    # runs 1, 2 and 4 started on the device 1.2, 1.0 and 1.1 ms before the
    # host enqueued them; run 3 waited 20 ms on the device; run 9 has no
    # host event; run 1 is more than a window away from run 2
    module_runs = [(0.0, 1), (0.4e9, 2), (0.45e9, 3), (0.48e9, 4), (0.7e9, 9)]
    host_runs = {1: 1.2 * ms, 2: 0.4e9 + 1.0 * ms, 3: 0.45e9 - 20 * ms, 4: 0.48e9 + 1.1 * ms}
    assert scopes.clock_lag(module_runs, host_runs) == pytest.approx(
        [(0.0, 1.2 * ms), (0.4e9, 1.1 * ms), (0.48e9, 1.1 * ms)])
    assert scopes.clock_lag([(300.0, 7)], {8: 0.0}) == []


@pytest.mark.parametrize("metric, value", [
    ("eval_device_ms", lambda s: dict(s.device_scopes)["tracker.evaluate"]),
    ("step_host_gap_ms", lambda s: sum(t for label, t in s.program_gaps if label in (
        "step>tracker.key_split", "step>tracker.dispatch", "step>tracker.wait"))),
])
def test_readers_read_the_recorded_trace_as_reduced(recorded, metric, value):
    """Each reader gives, per frame of the window, what ``scopes.reduce``
    read from the recorded trace; and nothing without the scopes."""
    from bench import harness

    _, planes, s, today = recorded
    host = _events(planes, lambda n: n.startswith("/host:"))
    (lo, hi), = [(a, b) for n, a, b in host if n == trace.WINDOW_SPAN]
    frames = sum(1 for n, a, _ in host if n == "tracker.step" and lo <= a < hi)
    assert frames >= 2
    run = harness.Run(setup_s=1.0, client_frames=[frames], client_seconds=[today.window_s],
                      latencies_s=np.ones(frames), frame_flops=1.0, peaks={},
                      trace=today, scopes=s)
    read = harness.load_reader(metric)
    assert value(s) > 0
    assert read(run) == pytest.approx(1e3 * value(s) / frames, rel=1e-12)
    run.scopes = None
    assert read(run) is None


def _kept(planes, keep):
    """The planes with only the device events for which ``keep(line name,
    start)`` holds, as a profiler that dropped the rest wrote them."""
    return [NS(name=p.name, lines=[
        NS(name=line.name, events=[e for e in line.events if not trace.DEVICE_PLANE.match(p.name)
                                   or keep(line.name, e.start_ns)])
        for line in p.lines]) for p in planes]


@pytest.mark.parametrize("keep", [
    lambda line, start, cut: line != trace.OPS_LINE or start < cut,
    lambda line, start, cut: start < cut,
    lambda line, start, cut: line != trace.OPS_LINE or start >= cut,
], ids=["later_ops", "later_ops_and_modules", "earlier_ops"])
def test_a_trace_that_dropped_frame_steps_fails_the_run(recorded, keep):
    """The recorded trace kept every frame step of its window; cut at its
    sixth step's start, it holds fewer than the frames the run counted,
    and ``check_trace_kept`` refuses it."""
    from bench import harness

    _, planes, s, _ = recorded
    host = _events(planes, lambda n: n.startswith("/host:"))
    (lo, hi), = [(a, b) for n, a, b in host if n == trace.WINDOW_SPAN]
    frames = sum(1 for n, a, _ in host if n == "tracker.step" and lo <= a < hi)
    assert s.step_runs == frames
    harness.check_trace_kept(s, frames)
    steps = sorted(a for n, a, _ in _events(planes, trace.DEVICE_PLANE.match, scopes.MODULES_LINE)
                   if scopes.module_name(n) == scopes.STEP_MODULE)
    hlo = gzip.decompress(RECORDED_HLO.read_bytes()).decode()
    cut = scopes.reduce(_kept(planes, lambda line, start: keep(line, start, steps[5])),
                        scopes.op_scopes(hlo))
    assert 0 < cut.step_runs < frames
    with pytest.raises(RuntimeError, match="dropped events"):
        harness.check_trace_kept(cut, frames)
