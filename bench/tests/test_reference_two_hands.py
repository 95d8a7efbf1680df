"""The two-hand reference agrees with the program at a 32x32 camera."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, generator, harness, reference
from bench import reference_two_hands as two
from repro.core import handmodel, objective, tracker
from repro.core.camera import Camera
from repro.kernels import ref as kernel_ref

CAMERA = {"width": 32, "height": 32, "fx": 27.5, "fy": 27.5, "cx": 15.5, "cy": 15.5}
CAM = Camera(**CAMERA)
RAYS = two.camera_rays(CAMERA)
MOTION = json.loads(harness.traffic_file("interact").read_text())["motion"]


@pytest.fixture(scope="module")
def truth():
    return two.trajectory(MOTION, 60, offset=11)


def _perturbed(truth, seed):
    rng = np.random.default_rng(seed)
    return (truth + rng.normal(0, 0.02, truth.shape)).astype(np.float32)


def _interpenetrating(truth):
    """Each pose with its right hand moved onto the left hand, 1 cm on."""
    hs = truth.copy()
    hs[:, 27:30] = hs[:, 0:3] + np.float32([0.01, 0.0, 0.0])
    return hs


def _kc(hs):
    return np.asarray(jax.vmap(lambda h: two.interpenetration(two.hand_spheres(h)))(
        jnp.asarray(hs)))


def _overlap(h):
    """Pixels that both hands of pose h cover, each rendered alone."""
    left = reference.sphere_depth(RAYS, two.left_hand_spheres(h[:27]))
    right = reference.render(h[27:], RAYS)
    return int(jnp.sum((left < 5.0) & (right < 5.0)))


def test_the_program_packs_the_reference_s_spheres(truth):
    """Hand 0 is the right hand mirrored in its local x, hand 1 the right
    hand: the same 96 spheres, to the bit."""
    hs = jnp.asarray(_perturbed(truth[::6], 1))
    want = jax.vmap(two.hand_spheres)(hs)
    got = jax.vmap(handmodel.pack_spheres)(hs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.shape == (hs.shape[0], two.NUM_SPHERES, 4)


def test_the_left_hand_is_the_right_hand_mirrored():
    h = np.array(handmodel.default_pose(0.5))
    h[7:] = np.linspace(0.0, 0.8, 20)
    pose = jnp.asarray(np.concatenate([h, h]))
    spheres = handmodel.pack_spheres(pose)
    left, right = np.asarray(spheres[:48]), np.asarray(spheres[48:])
    np.testing.assert_array_equal(left[:, 1:], right[:, 1:])
    np.testing.assert_array_equal(left[:, 0], -right[:, 0])


@pytest.mark.parametrize("interpenetrating", [False, True],
                         ids=["overlapping", "interpenetrating"])
def test_batched_objective_matches_the_reference_score(truth, interpenetrating):
    """E = E_D + λ kc of a population against a noisy observation of two
    hands that overlap in the image, over B of the true pose."""
    hs = _perturbed(truth[::6], 2)
    if interpenetrating:
        hs = _interpenetrating(hs)
        assert np.all(_kc(hs) > 0.01)
    obs = two.render(jnp.asarray(truth[0]), RAYS) + 0.002 * jax.random.normal(
        jax.random.PRNGKey(0), (RAYS.shape[0],))
    mask = two.box_mask(obs, jnp.asarray(truth[0]), 0.25)
    assert _overlap(jnp.asarray(truth[0])) > 0 and int(mask.sum()) > 50
    want = jax.vmap(lambda h: two.score(h, obs, mask, RAYS))(jnp.asarray(hs))
    got = jax.jit(lambda hs: objective.batched_objective(
        hs, obs.reshape(32, 32), CAM, mask.reshape(32, 32)))(jnp.asarray(hs))
    # float32 sums of a few hundred terms in another order (B's compacted
    # rung against the whole frame): about 2e-6 of the score at most here
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=4e-6, atol=1e-7)


def test_interpenetration_is_zero_apart_and_positive_inside(truth):
    apart = truth[::6].copy()
    apart[:, 27] += 0.5
    inside = _interpenetrating(truth[::6])
    s_apart = jax.vmap(handmodel.pack_spheres)(jnp.asarray(apart))
    s_inside = jax.vmap(handmodel.pack_spheres)(jnp.asarray(inside))
    assert not np.any(np.asarray(objective.interpenetration(s_apart)))
    got = np.asarray(objective.interpenetration(s_inside))
    assert np.all(got > 0.01)
    np.testing.assert_allclose(got, _kc(inside), rtol=1e-5)


def test_padding_spheres_never_count():
    """Each hand's padding spheres sit at its root with no radius; the
    other hand's palm around that root adds nothing."""
    h = np.array(handmodel.default_pose(0.5))
    pose = np.concatenate([h, h])
    pose[27] += 0.021  # a right palm sphere holds the left hand's root
    spheres = handmodel.pack_spheres(jnp.asarray(pose))
    n, s = handmodel.NUM_SPHERES_RAW, handmodel.NUM_SPHERES
    left_root = spheres[n:s, :3]
    right_real = spheres[s:s + n]
    reach = jnp.linalg.norm(left_root[:, None] - right_real[None, :, :3], axis=-1)
    assert bool(jnp.any(reach < right_real[None, :, 3]))  # a padding sphere lies inside one
    real = np.asarray(spheres[:n]), np.asarray(right_real)
    overlap = sum(max(0.0, float(p[3] + q[3] - np.linalg.norm(p[:3] - q[:3])))
                  for p in real[0] for q in real[1])
    assert float(objective.interpenetration(spheres)) == pytest.approx(overlap, rel=1e-5)
    assert float(two.interpenetration(two.hand_spheres(jnp.asarray(pose)))) == pytest.approx(
        overlap, rel=1e-5)


def test_bounding_box_is_the_reference_s_union(truth):
    cfg = tracker.TrackerConfig(camera=CAM)
    for h in truth[::15]:
        obs = two.render(jnp.asarray(h), RAYS)
        _, got = tracker.stage_preprocess(cfg, jnp.asarray(h), obs.reshape(32, 32))
        want = two.box_mask(obs, jnp.asarray(h), cfg.bbox_half_width)
        np.testing.assert_array_equal(np.asarray(got).reshape(-1), np.asarray(want))
        left_only = reference.box_mask(obs, h[:27], cfg.bbox_half_width)
        assert int(want.sum()) >= int(left_only.sum())


def test_the_kernel_path_agrees_with_the_jnp_path(truth):
    """``use_kernel`` (Pallas, interpret mode on the CPU) renders the 96
    spheres over the whole frame and adds the same penalty."""
    hs = jnp.asarray(np.concatenate([_perturbed(truth[:4], 4),
                                     _interpenetrating(_perturbed(truth[4:8], 5))]))
    obs = two.render(jnp.asarray(truth[0]), RAYS).reshape(32, 32)
    cfg = tracker.TrackerConfig(camera=CAM)
    d_o, mask = tracker.stage_preprocess(cfg, jnp.asarray(truth[0]), obs)
    jnp_path = tracker._make_eval_fn(cfg, d_o, mask)
    kernel_path = tracker._make_eval_fn(dataclasses.replace(cfg, use_kernel=True), d_o, mask)
    got, want = np.asarray(kernel_path(hs)), np.asarray(jax.jit(jnp_path)(hs))
    assert np.all(want[4:] > want[:4].max())  # the penalty is in both
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=kernel_ref.score_atol(mask))


def test_tracking_eight_frames_is_correct():
    """``Tracker.step`` over 8 frames of the interact traffic, read by the
    check against the configuration's own limits."""
    cell = harness.load_cell("twohands128.interact")
    config = dict(cell.config, camera=CAMERA)
    traffic = dict(cell.traffic, sequence_frames=12)
    (seq,) = generator.build(two, CAMERA, traffic, 2**31 + 7)
    trk = tracker.Tracker(harness.tracker_config(config), h0=jnp.asarray(seq.truth[0]),
                          seed=seq.seed)
    poses, scores = [seq.truth[0]], []
    for k in range(1, 9):
        h, score = trk.step(seq.frames[k])
        poses.append(np.asarray(h))
        scores.append(score)
    tracked = {"poses": np.stack(poses), "scores": np.asarray(scores),
               "frames": seq.frames[1:9], "truth": seq.truth[1:9]}
    numbers, _ = check.readings(two, config, [tracked])
    ok, checks = check.compare(numbers, config["limits"])
    assert ok, checks
    assert poses[-1].shape == (54,)
