"""End-to-end tracker behaviour on synthetic sequences."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import handmodel as hm
from repro.core import objective, pso, tracker
from repro.core.camera import Camera
from repro.data import rgbd

CAM = Camera(width=64, height=64, fx=60.0, fy=60.0, cx=31.5, cy=31.5)


@pytest.fixture(scope="module")
def short_sequence():
    cfg = rgbd.SequenceConfig(
        num_frames=12, camera=CAM, noise_std=0.001,
        fast_burst=(100, 101),  # no burst in this short clip
        position_amplitude=0.04, curl_amplitude=0.5,
    )
    return rgbd.render_sequence(cfg)


def test_tracks_synthetic_sequence(short_sequence):
    frames, truth = short_sequence
    cfg = tracker.TrackerConfig(
        camera=CAM, pso=pso.PSOConfig(num_particles=48, num_generations=20),
        smoothing=0.0,
    )
    t = tracker.Tracker(cfg, h0=truth[0])
    errs = []
    for i in range(1, frames.shape[0]):
        h, score = t.step(frames[i])
        errs.append(float(jnp.linalg.norm(h[:3] - truth[i][:3])))
    assert np.mean(errs) < 0.03, errs  # < 3 cm mean position error


def test_stage_composition_matches_fused(short_sequence):
    """Running the 4 stages separately == the fused track_frame (the
    Single-Step / Multi-Step implementations are the same math)."""
    frames, truth = short_sequence
    cfg = tracker.TrackerConfig(
        camera=CAM, pso=pso.PSOConfig(num_particles=16, num_generations=5)
    )
    key = jax.random.PRNGKey(0)
    h_prev = truth[0]
    depth = frames[1]
    fused = tracker.make_track_frame(cfg)
    _, h_fused, score_fused = fused(key, h_prev, depth)

    _, sub = jax.random.split(key)  # the fused step draws from the split key
    d_o, mask = tracker.stage_preprocess(cfg, h_prev, depth)
    eval_fn = tracker._make_eval_fn(cfg, d_o, mask)
    state, lo, hi = tracker.stage_spawn(cfg, sub, h_prev, eval_fn)
    state = tracker.stage_optimize(cfg, state, lo, hi, eval_fn)
    h_multi, score_multi = tracker.stage_refine(cfg, state, h_prev)
    np.testing.assert_allclose(
        np.asarray(h_fused), np.asarray(h_multi), atol=1e-5
    )
    assert float(score_fused) == pytest.approx(float(score_multi), abs=1e-6)


def test_staged_description_is_valid():
    cfg = tracker.TrackerConfig(camera=CAM)
    comp = tracker.build_staged(cfg)
    comp.validate()
    assert [s.name for s in comp.stages] == [
        "preprocess", "spawn", "optimize", "refine",
    ]
    # the GPGPU stage dominates the FLOP budget (that is what's offloaded)
    flops = {s.name: s.flops for s in comp.stages}
    assert flops["optimize"] > 0.9 * comp.total_flops()


def test_executed_simulation_couples_drops_to_quality():
    """Slower deployments process fewer frames; with a fast burst in the
    clip, the local-slow run must not beat the fast run on error."""
    from repro.core.offload import Environment, Link, Policy, Tier, WrapperModel
    from repro.sim import runtime

    cfg = rgbd.SequenceConfig(num_frames=20, camera=CAM, fast_burst=(8, 14))
    frames, truth = cfg, None
    frames, truth = rgbd.render_sequence(cfg)
    tcfg = tracker.TrackerConfig(
        camera=CAM, pso=pso.PSOConfig(num_particles=32, num_generations=10),
        smoothing=0.0,
    )
    comp_flops = tracker.build_staged(tcfg).total_flops()
    fast = Tier("fast", comp_flops * 60, 50e9)  # 60 fps-capable
    slow = Tier("slow", comp_flops * 5, 20e9)  # 5 fps-capable
    link = Link("eth", 117e6, 0.3e-3)
    env_fast = Environment(client=fast, server=fast, link=link, wrapped=False)
    env_slow = Environment(client=slow, server=slow, link=link, wrapped=False)
    r_fast = runtime.executed_run(tcfg, env_fast, Policy.LOCAL, frames, truth)
    r_slow = runtime.executed_run(tcfg, env_slow, Policy.LOCAL, frames, truth)
    assert r_slow.sim.stats.dropped > r_fast.sim.stats.dropped
    assert len(r_fast.sim.stats.processed) > len(r_slow.sim.stats.processed)


TINY = Camera(width=16, height=16, fx=14.0, fy=14.0, cx=7.5, cy=7.5)
TINY_CFG = tracker.TrackerConfig(
    camera=TINY, pso=pso.PSOConfig(num_particles=8, num_generations=2)
)
SCOPES = ("tracker.preprocess", "tracker.spawn", "tracker.optimize",
          "tracker.refine", "tracker.evaluate")


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_frame_step_carries_the_tracker_scopes(use_kernel):
    """Every stage and the population evaluation run under their own
    ``jax.named_scope``, which the compiled step keeps in each op's
    metadata, so a device trace can be read by stage; spawn's evaluation
    and optimize's share one scope."""
    cfg = dataclasses.replace(TINY_CFG, use_kernel=use_kernel)
    compiled = tracker.make_track_frame(cfg).lower(
        jax.random.PRNGKey(0), hm.default_pose(), jnp.zeros((16, 16))
    ).compile()
    op_names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    innermost = {re.findall(r"tracker\.\w+", n)[-1] for n in op_names if "tracker." in n}
    assert innermost == set(SCOPES)
    evaluated_in = {n.split("/tracker.evaluate/")[0] for n in op_names if "/tracker.evaluate/" in n}
    assert any("tracker.spawn" in n for n in evaluated_in)
    assert any("tracker.optimize" in n for n in evaluated_in)


def test_each_pixel_rung_runs_under_its_scope_inside_evaluate():
    """The population evaluation has one branch per rung of the pixel
    ladder, each under ``pixels_<K>`` in the compiled step's metadata,
    and the benchmark's scope reduction still charges every op of them to
    ``tracker.evaluate``."""
    from bench import scopes

    cam = Camera(width=32, height=32, fx=28.0, fy=28.0, cx=15.5, cy=15.5)
    cfg = dataclasses.replace(TINY_CFG, camera=cam)
    text = tracker.make_track_frame(cfg).lower(
        jax.random.PRNGKey(0), hm.default_pose(), jnp.zeros((32, 32))
    ).compile().as_text()
    by_op = scopes.op_scopes(text)
    rungs = {}
    for op, op_name in re.findall(
            r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
            text, re.M):
        rung = re.search(r"/pixels_(\d+)/", op_name)
        if rung:
            rungs.setdefault(int(rung.group(1)), set()).add(by_op.get(op))
    assert sorted(rungs) == list(objective.box_ladder(cam.num_pixels)) == [128, 256, 512, 1024]
    assert all(charged == {"tracker.evaluate"} for charged in rungs.values())


def test_step_counts_its_phases():
    t = tracker.Tracker(TINY_CFG, h0=hm.default_pose())
    phases = tracker.StepPhases.PHASES
    assert t.phases.frames == 0
    t.step(jnp.zeros((16, 16)))
    assert t.phases.frames == 1
    assert set(t.phases.seconds) == set(phases)
    assert all(s >= 0 for s in t.phases.seconds.values())
    assert t.phases.slowest == t.phases.seconds  # one step: it is the slowest
    first = dict(t.phases.seconds)
    t.step(jnp.zeros((16, 16)))
    second = {p: t.phases.seconds[p] - first[p] for p in phases}
    assert t.phases.frames == 2
    slowest = max(sum(first.values()), sum(second.values()))
    assert sum(t.phases.slowest.values()) == pytest.approx(slowest)


def test_step_writes_its_spans_when_the_profiler_traces(tmp_path):
    """The spans of ``Tracker.step`` land in the profiler's trace, nested
    in ``tracker.step``, which carries the frame number."""
    from jax.profiler import ProfileData

    t = tracker.Tracker(TINY_CFG, h0=hm.default_pose())
    t.step(jnp.zeros((16, 16)))  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        t.step(jnp.zeros((16, 16)))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("tracker.")]
    names = [n for n, *_ in spans]
    assert sorted(names) == sorted(
        ["tracker.step"] + [f"tracker.{p}" for p in tracker.StepPhases.PHASES])
    (outer,) = [s for s in spans if s[0] == "tracker.step"]
    assert outer[3]["frame"] == 1
    for name, start, end, _ in spans:
        assert outer[1] <= start <= end <= outer[2], name


def test_the_tracker_carries_the_host_split_key_stream():
    """The key the step hands on is the one ``jax.random.split`` gives on
    the host, frame after frame, to the bit: the random stream is the
    same wherever the split runs."""
    t = tracker.Tracker(TINY_CFG, h0=hm.default_pose(), seed=11)
    key = jax.random.PRNGKey(11)
    for _ in range(6):
        t.step(jnp.zeros((16, 16)))
        key, _ = jax.random.split(key)
        np.testing.assert_array_equal(np.asarray(t.key), np.asarray(key))


def test_step_fetches_pose_and_score_in_one_wait(monkeypatch):
    """One ``jax.device_get`` brings pose and score to the host together;
    the step returns the device pose it fetched, which the next frame
    takes, and the score as a float, and keeps the key on the device."""
    fetched = []

    def device_get(x):
        fetched.append(x)
        return real(x)

    real = jax.device_get
    t = tracker.Tracker(TINY_CFG, h0=hm.default_pose())
    monkeypatch.setattr(jax, "device_get", device_get)
    h, score = t.step(jnp.zeros((16, 16)))
    assert len(fetched) == 1
    pose, fetched_score = fetched[0]
    assert pose is h is t.h and isinstance(h, jax.Array)
    assert type(score) is float and score == float(fetched_score)
    assert isinstance(t.key, jax.Array)
    assert t.key.dtype == jnp.uint32 and t.key.shape == (2,)


def test_a_warm_step_runs_the_frame_program_alone():
    """After warm-up a step compiles nothing; with JAX's caches cleared,
    the one program it compiles, so the one it dispatches, is the jitted
    frame step: no eager key split or other op runs beside it."""
    compiled = []

    def listen(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kwargs.get("fun_name"))

    t = tracker.Tracker(TINY_CFG, h0=hm.default_pose())
    depth = jnp.zeros((16, 16))
    t.step(depth)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        t.step(depth)
        assert compiled == []
        jax.clear_caches()
        t.step(depth)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiled == ["jit(track_frame)"]


# Four one-hand 32x32 frames tracked by the tree before poses of two
# hands: each frame's score, and a sha256 over each pose's and score's
# float32 bytes in turn. A pose of one hand takes the same path, to the bit.
ONE_HAND_SCORES = [0.021686801686882973, 0.034813933074474335,
                   0.05444156751036644, 0.05730573460459709]
ONE_HAND_DIGEST = "4b729ddf97678a04183c1e67a06496192f6e9f5beae902bbd5398e011eaba004"


def test_one_hand_frames_are_tracked_as_before():
    import hashlib

    cam = Camera(width=32, height=32, fx=27.5, fy=27.5, cx=15.5, cy=15.5)
    frames, truth = rgbd.render_sequence(rgbd.SequenceConfig(
        num_frames=5, camera=cam, noise_std=0.002, fast_burst=(100, 101)))
    cfg = tracker.TrackerConfig(camera=cam, pso=pso.PSOConfig(num_particles=16,
                                                              num_generations=4))
    t = tracker.Tracker(cfg, h0=truth[0], seed=7)
    digest, scores = hashlib.sha256(), []
    for i in range(1, 5):
        h, score = t.step(frames[i])
        digest.update(np.asarray(h, np.float32).tobytes())
        digest.update(np.float32(score).tobytes())
        scores.append(float(np.float32(score)))
    assert scores == ONE_HAND_SCORES
    assert digest.hexdigest() == ONE_HAND_DIGEST


def _compiled_text(h0):
    cam = Camera(width=32, height=32, fx=28.0, fy=28.0, cx=15.5, cy=15.5)
    cfg = dataclasses.replace(TINY_CFG, camera=cam)
    return tracker.make_track_frame(cfg).lower(
        jax.random.PRNGKey(0), h0, jnp.zeros((32, 32))).compile().as_text()


def test_the_penalty_runs_under_collide_inside_evaluate_for_two_hands_only():
    """With two hands, kc runs under ``tracker.collide``, nested in
    ``tracker.evaluate`` (in spawn's evaluation and in the PSO loop's),
    which ``bench.scopes`` charges it to; one hand has no such scope."""
    from bench import scopes

    two = _compiled_text(jnp.concatenate([hm.default_pose(), hm.default_pose()]))
    collide = set(re.findall(r'op_name="([^"]*tracker\.collide[^"]*)"', two))
    assert collide and all("tracker.evaluate/tracker.collide/" in n for n in collide)
    assert any("tracker.spawn/" in n for n in collide)
    assert any("tracker.optimize/" in n for n in collide)
    assert "tracker.collide" in set(scopes.op_scopes(two).values())
    assert "tracker.collide" not in _compiled_text(hm.default_pose())


@pytest.mark.parametrize("hands", [1, 2])
def test_the_staged_description_is_sized_by_the_hands(hands):
    cfg = tracker.TrackerConfig(camera=CAM)
    h0 = jnp.concatenate([hm.default_pose()] * hands)
    one, comp = tracker.build_staged(cfg), tracker.build_staged(cfg, h0=h0)
    h_next = {d.name: d.nbytes for s in comp.stages for d in s.outputs}["h_next"]
    assert h_next == 4 * hands * hm.NUM_PARAMS
    ratio = tracker._eval_flops_per_generation(cfg, hands) / tracker._eval_flops_per_generation(cfg)
    assert ratio == pytest.approx(hands, rel=1e-2)  # but the ~3 operations a pixel
    assert comp.total_flops() == pytest.approx(hands * one.total_flops(), rel=1e-2)
