"""The two-hand cell: its files load by name, its traffic is what its
``why`` says, and its per-layer metric reads the program's scope."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, scopes
from bench import reference_two_hands as two
from bench.tests.test_harness import _fake_run
from repro.core import objective

CELL = "twohands128.interact"


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def frames(cell):
    """Per true pose that a seed can start from (offsets 0 to 89, 600
    frames): the pixels both hands cover, each rendered alone, at the
    configuration's camera; kc; and |B| around the pose itself."""
    motion = cell.traffic["motion"]
    truth = two.trajectory(motion, motion["burst_every"] + cell.traffic["sequence_frames"], 0)
    rays = two.camera_rays(cell.config["camera"])
    half = cell.config["tracker"]["bbox_half_width"]

    def one(h):
        left = reference.sphere_depth(rays, two.left_hand_spheres(h[:27])) < 5.0
        right = reference.render(h[27:], rays) < 5.0
        box = two.box_mask(two.render(h, rays), h, half)
        return (jnp.sum(left & right), two.interpenetration(two.hand_spheres(h)),
                jnp.sum(box))

    both, kc, box = jax.jit(lambda t: jax.lax.map(one, t, batch_size=16))(jnp.asarray(truth))
    return np.asarray(both), np.asarray(kc), np.asarray(box)


def test_the_cell_loads_its_two_hand_files(cell):
    assert cell.reference is two
    assert (two.NUM_PARAMS, two.NUM_SPHERES) == (54, 96)
    assert cell.config["hand"] == {"num_params": 54, "num_spheres": 96}
    assert cell.config["reduced"] == [] and cell.chips == 1
    assert {m["name"] for m in cell.per_layer} >= {"collide_device_ms", "eval_device_ms"}


def test_the_true_hands_never_interpenetrate(frames):
    _, kc, _ = frames
    assert kc.shape == (690,)
    assert not np.any(kc)


def test_the_silhouettes_overlap_in_more_than_half_the_frames(frames):
    """10 cm apart, with the left hand mirrored, the silhouettes overlap
    in 59% of the reachable frames: the front hand hides part of the back
    one in more than half of them, not in nearly every one."""
    both, _, _ = frames
    assert np.mean(both > 0) > 0.5


def test_b_lands_on_the_2048_rung_in_most_frames(cell, frames):
    _, _, box = frames
    cam = cell.config["camera"]
    rungs = np.asarray([int(objective.box_rung(int(n), cam["width"] * cam["height"]))
                        for n in box])
    assert set(rungs) <= {1024, 2048}
    assert np.mean(rungs == 2048) > 0.5


def test_collide_device_ms_reads_the_collide_scope():
    read = harness.load_reader("collide_device_ms")
    run = _fake_run(True)
    assert read(run) is None  # one hand: no such scope
    run.scopes = scopes.ScopeSummary(
        device_scopes=[["tracker.evaluate", 1.2], ["tracker.collide", 0.03]],
        program_gaps=[], clock_lag=[], step_runs=100)
    assert read(run) == pytest.approx(1e3 * 0.03 / 100)
    assert read(_fake_run(False)) is None
