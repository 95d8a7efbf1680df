"""The benchmark's own reference agrees with the program at a small camera."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, generator, harness, reference
from bench.tests import two_hands
from repro.core import handmodel, objective, tracker
from repro.core.camera import Camera

CAMERA = {"width": 40, "height": 32, "fx": 34.0, "fy": 34.0, "cx": 19.5, "cy": 15.5}
MOTION = {
    "base_distance": 0.5, "position_amplitude": 0.06, "rotation_amplitude": 0.5,
    "curl_amplitude": 0.9, "burst_every": 90, "burst_length": 15,
    "burst_speed": 3.0, "noise_std": 0.002,
}


@pytest.fixture(scope="module")
def poses():
    """Truth poses with a random perturbation: curls, rotations, offsets."""
    truth = reference.trajectory(MOTION, 40, offset=7)[::5]
    rng = np.random.default_rng(3)
    return (truth + rng.normal(0, 0.02, truth.shape)).astype(np.float32)


def test_hand_spheres_match_the_program(poses):
    want = jax.vmap(handmodel.pack_spheres)(jnp.asarray(poses))
    got = jax.vmap(reference.hand_spheres)(jnp.asarray(poses))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-7)


def test_camera_rays_match_the_program():
    want = Camera(**CAMERA).rays_flat()
    np.testing.assert_array_equal(np.asarray(reference.camera_rays(CAMERA)), np.asarray(want))


def test_scores_agree_with_batched_objective(poses):
    """E_D of a population against a rendered, noisy observation."""
    rays = reference.camera_rays(CAMERA)
    obs = reference.render(jnp.asarray(poses[0]), rays) + 0.002 * jax.random.normal(
        jax.random.PRNGKey(0), (rays.shape[0],))
    mask = reference.bbox_mask(obs, poses[0][2], 0.25)
    got = jax.vmap(lambda h: reference.score(h, obs, mask, rays))(jnp.asarray(poses))
    cam = Camera(**CAMERA)
    want = objective.batched_objective(
        jnp.asarray(poses), obs.reshape(cam.height, cam.width), cam,
        mask.reshape(cam.height, cam.width))
    assert np.asarray(mask).sum() > 50
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)


def test_high_precision_product_is_below_highest():
    """The control's product is off by about 2**-17 of its size, never 0."""
    rays = reference.camera_rays(CAMERA)
    c = jnp.asarray(np.random.default_rng(0).normal(0, 0.05, (48, 3)) + [0, 0, 0.5],
                    jnp.float32)
    hi = np.asarray(reference.dot_highest(rays, c), np.float64)
    lo = np.asarray(reference.dot_high(rays, c), np.float64)
    rel = np.abs(lo - hi) / np.abs(hi).max()
    assert 0 < rel.max() < 2.0 ** -14


def test_recover_best_inverts_the_refine_stage(poses):
    cfg = tracker.TrackerConfig(camera=Camera(**CAMERA))
    best = jax.vmap(handmodel.normalize_configuration)(jnp.asarray(poses[1:]))
    prev = jnp.asarray(poses[:-1])

    class State:
        global_best_score = 0.0

    def refine(b, p):
        State.global_best = b
        return tracker.stage_refine(cfg, State, p)[0]

    h = np.stack([np.asarray(refine(b, p)) for b, p in zip(best, prev)])
    got = reference.recover_best(h, np.asarray(prev), cfg.smoothing)
    np.testing.assert_allclose(got, np.asarray(best), atol=5e-7)


def test_readings_hold_the_worst_client():
    """Two clients, one of whose scores are all off by 1e-3: the compared
    gap is that client's, not a median diluted by the sound client."""
    cfg = tracker.TrackerConfig(camera=Camera(**CAMERA))
    config = {"camera": CAMERA, "tracker": {"smoothing": cfg.smoothing, "bbox_half_width": 0.25}}
    truth = reference.trajectory(MOTION, 6, offset=2)
    frames = generator.render(reference, CAMERA, 0.002, truth, 5).reshape(6, CAMERA["height"], -1)

    class State:
        global_best_score = 0.0

    poses = [truth[0]]
    for b in truth:
        State.global_best = jax.device_get(handmodel.normalize_configuration(jnp.asarray(b)))
        poses.append(np.asarray(tracker.stage_refine(cfg, State, jnp.asarray(poses[-1]))[0]))
    poses = np.stack(poses).astype(np.float32)
    best = reference.recover_best(poses[1:], poses[:-1], cfg.smoothing)
    scores = check.reference_scores(reference, config, best, frames, poses[:-1])
    sound = {"poses": poses, "scores": scores.astype(np.float32), "frames": frames,
             "truth": truth}
    off = dict(sound, scores=(scores + 1e-3).astype(np.float32))
    compared, unchecked = check.readings(reference, config, [sound, off])
    assert unchecked["client_score_gap_median"][0] < 1e-6
    assert compared["score_gap_median"] == pytest.approx(1e-3, rel=1e-3)
    assert compared["pos_err_mean_m"] == max(unchecked["client_pos_err_mean_m"])
    # what these inputs read before the hand model came from the
    # configuration's reference module: the same numbers, to the bit
    assert compared == READINGS_BEFORE
    assert unchecked == UNCHECKED_BEFORE


READINGS_BEFORE = {
    "score_gap_median": 0.0010000000474974513, "pos_err_mean_m": 0.000610058254096657,
    "score_over_truth_mean": 0.0009999332833103836,
    "score_over_truth_median": 0.0009999626781791449,
}
UNCHECKED_BEFORE = {
    "score_gap_p90": 0.0010000000474974513, "score_gap_p99": 0.0010000001511070878,
    "score_gap_max": 0.0010000001639127731,
    "client_score_gap_median": [0.0, 0.0010000000474974513],
    "client_pos_err_mean_m": [0.000610058254096657, 0.000610058254096657],
    "client_score_over_truth_mean": [-6.67447845141093e-08, 0.0009999332833103836],
    "client_score_over_truth_median": [-3.742752596735954e-08, 0.0009999626781791449],
}


def test_a_hand_model_of_another_width_needs_no_harness_edit():
    """A configuration naming the 54-parameter, 96-sphere stand-in loads
    it, and the generator and the check run through it: the truth fed
    back as the tracked poses, with no smoothing, reads no gap at all."""
    config = {"name": "two_hands", "reference": "bench/tests/two_hands.py",
              "hand": {"num_params": 54, "num_spheres": 96},
              "camera": {"width": 32, "height": 32, "fx": 27.5, "fy": 27.5,
                         "cx": 15.5, "cy": 15.5},
              "tracker": {"smoothing": 0.0, "bbox_half_width": 0.25}}
    ref = harness.reference_module(config)
    assert ref is two_hands
    traffic = json.loads(harness.traffic_file("solo").read_text())
    (seq,) = generator.build(ref, config["camera"], dict(traffic, sequence_frames=40),
                             2**31 + 3)
    assert seq.truth.shape == (40, 54) and seq.frames.shape == (40, 32, 32)
    one_hand = generator.render(reference, config["camera"], 0.0, seq.truth[:, :27], 1)
    assert (seq.frames < 1.0).sum() > 1.5 * (one_hand < 1.0).sum()  # both hands in view
    poses = np.concatenate([seq.truth[:1], seq.truth])
    scores = check.reference_scores(ref, config, seq.truth, seq.frames, poses[:-1])
    tracked = {"poses": poses, "scores": scores.astype(np.float32),
               "frames": seq.frames, "truth": seq.truth}
    compared, _ = check.readings(ref, config, [tracked])
    assert compared["pos_err_mean_m"] == 0.0
    assert compared["score_gap_median"] == 0.0
