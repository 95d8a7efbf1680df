"""Properties of the paper's E_D objective (Eq. 2)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import handmodel as hm
from repro.core import objective as obj
from repro.core.camera import BACKGROUND_DEPTH, Camera

CAM = Camera(width=48, height=48, fx=45.0, fy=45.0, cx=23.5, cy=23.5)


def test_perfect_hypothesis_scores_zero():
    h = hm.default_pose(0.45)
    d = obj.render_depth(h, CAM)
    assert float(obj.objective(h, d, CAM)) == pytest.approx(0.0, abs=1e-6)


def test_clamp_bounds_objective():
    """E_D <= T by construction (mean of clamped values)."""
    h = hm.default_pose(0.45)
    d_far = jnp.full((CAM.height, CAM.width), BACKGROUND_DEPTH)
    e = float(obj.objective(h, d_far, CAM))
    assert 0.0 <= e <= obj.CLAMP_T + 1e-6


@settings(max_examples=20, deadline=None)
@given(st.floats(0.005, 0.08))
def test_larger_offset_scores_worse(dx):
    """Monotone degradation along a translation ray."""
    h = hm.default_pose(0.45)
    d = obj.render_depth(h, CAM)
    mask = obj.bounding_box_mask(d, h[2])
    e_small = float(obj.objective(h.at[0].add(dx / 2), d, CAM, mask))
    e_large = float(obj.objective(h.at[0].add(dx * 2), d, CAM, mask))
    e_true = float(obj.objective(h, d, CAM, mask))
    assert e_true <= e_small <= e_large + 1e-4


def test_sphere_depth_matches_analytic_center_ray():
    """A sphere dead ahead: depth along the central ray = c_z - r."""
    spheres = jnp.asarray([[0.0, 0.0, 0.5, 0.1]])
    rays = jnp.asarray([[0.0, 0.0, 1.0]])
    d = obj.sphere_depth(rays, spheres)
    np.testing.assert_allclose(np.asarray(d), [0.4], atol=1e-6)


def test_zero_radius_padding_never_hits():
    spheres = jnp.asarray([[0.0, 0.0, 0.0, 0.0]])
    rays = CAM.rays_flat()
    d = obj.sphere_depth(rays, spheres)
    assert float(d.min()) == BACKGROUND_DEPTH


def test_bbox_mask_selects_hand_depth_band():
    h = hm.default_pose(0.45)
    d = obj.render_depth(h, CAM)
    mask = obj.bounding_box_mask(d, h[2], half_width=0.25)
    hand_pixels = d < BACKGROUND_DEPTH - 1
    # every rendered hand pixel near the expected depth is inside B
    assert bool(jnp.all(mask[hand_pixels]))
    # far background is outside B
    assert not bool(jnp.any(mask & (d > 5.0)))


# --- The population evaluation over B's compacted pixels -------------------

BOX_CAM = Camera(width=32, height=32, fx=28.0, fy=28.0, cx=15.5, cy=15.5)  # ladder 128..1024


def _box_scene():
    """A noisy observation of a hand at 0.45 m and a population around a
    pose 1.5 cm off it, so every particle scores above 0."""
    import jax

    h = hm.default_pose(0.45)
    d_o = obj.render_depth(h, BOX_CAM) + 0.002 * jax.random.normal(
        jax.random.PRNGKey(0), (BOX_CAM.height, BOX_CAM.width))
    hs = h.at[0].add(0.015)[None] + 0.01 * jax.random.normal(jax.random.PRNGKey(1), (8, 27))
    return hs, d_o


def _nearest(d_o, n):
    """A mask of the n pixels nearest the camera: the hand first, then
    background pixels in the order of their noise."""
    flat = np.zeros(d_o.size, bool)
    flat[np.argsort(np.asarray(d_o).reshape(-1), kind="stable")[:n]] = True
    return flat.reshape(d_o.shape)


def _scattered(d_o):
    return np.random.default_rng(5).random(d_o.shape) < 0.3


@pytest.mark.parametrize("make_mask", [
    lambda d: np.zeros(d.shape, bool),
    lambda d: _nearest(d, 1),
    lambda d: _nearest(d, 128),
    lambda d: _nearest(d, 129),
    lambda d: _nearest(d, 256),
    lambda d: _nearest(d, 257),
    lambda d: np.ones(d.shape, bool),
    _scattered,
    lambda d: None,
], ids=["empty", "one", "rung0", "rung0+1", "rung1", "rung1+1", "full", "scattered", "none"])
def test_batched_objective_equals_the_full_frame_masked_mean(make_mask):
    """Rendering B's compacted pixels scores what the full-frame render
    scores: the pixels left out add exact zeros."""
    import jax

    hs, d_o = _box_scene()
    mask = make_mask(d_o)
    mask = None if mask is None else jnp.asarray(mask)
    full_frame = jax.jit(jax.vmap(lambda h: obj.objective(h, d_o, BOX_CAM, mask)))
    got = jax.jit(lambda hs: obj.batched_objective(hs, d_o, BOX_CAM, mask))(hs)
    want = full_frame(hs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)
    if mask is not None and int(mask.sum()) == 0:
        assert not np.any(np.asarray(got))


@pytest.mark.parametrize("num_pixels, ladder", [
    (128 * 128, (1024, 2048, 4096, 8192, 16384)),
    (320 * 240, (4864, 9600, 19200, 38400, 76800)),
    (32 * 32, (128, 256, 512, 1024)),
], ids=["128x128", "320x240", "32x32"])
def test_box_ladder_depends_on_the_pixel_count_alone(num_pixels, ladder):
    assert obj.box_ladder(num_pixels) == ladder


def _boundaries():
    for p in (128 * 128, 320 * 240, 32 * 32):
        ladder = obj.box_ladder(p)
        yield p, 0, ladder[0]
        yield p, 1, ladder[0]
        for k, above in zip(ladder, ladder[1:]):
            yield p, k, k
            yield p, k + 1, above
        yield p, p, p


@pytest.mark.parametrize("num_pixels, n, rung", list(_boundaries()))
def test_box_rung_is_the_smallest_rung_holding_n(num_pixels, n, rung):
    assert int(obj.box_rung(n, num_pixels)) == rung
