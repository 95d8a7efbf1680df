"""Kinematics invariants of the 27-DoF hand model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import handmodel as hm

finite_floats = st.floats(-1.0, 1.0, allow_nan=False, width=32)


@st.composite
def configurations(draw):
    pos = [draw(st.floats(-0.3, 0.3)) for _ in range(3)]
    pos[2] = draw(st.floats(0.3, 1.0))  # in front of the camera
    quat = [draw(st.floats(-1.0, 1.0)) for _ in range(4)]
    if all(abs(q) < 1e-3 for q in quat):
        quat = [1.0, 0.0, 0.0, 0.0]
    angles = [draw(st.floats(-2.0, 2.5)) for _ in range(20)]
    return jnp.asarray(pos + quat + angles, dtype=jnp.float32)


def test_sphere_count_and_padding():
    h = hm.default_pose()
    c, r = hm.hand_spheres_world(h)
    assert c.shape == (hm.NUM_SPHERES, 3)
    assert r.shape == (hm.NUM_SPHERES,)
    assert hm.NUM_SPHERES % 8 == 0
    # padding spheres have zero radius
    assert float(r[hm.NUM_SPHERES_RAW:].max(initial=0.0)) == 0.0
    assert float(r[: hm.NUM_SPHERES_RAW].min()) > 0.0


@settings(max_examples=25, deadline=None)
@given(configurations())
def test_rigid_transform_preserves_distances(h):
    """Rotation+translation must not change inter-sphere distances."""
    angles = h[hm.ANGLES_SLICE]
    local_c, _ = hm.hand_spheres_local(angles)
    world_c, _ = hm.hand_spheres_world(h)
    d_local = jnp.linalg.norm(local_c[0] - local_c[10])
    d_world = jnp.linalg.norm(world_c[0] - world_c[10])
    np.testing.assert_allclose(float(d_local), float(d_world), atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(configurations())
def test_quaternion_normalization_invariance(h):
    """Scaling the quaternion must not change geometry (normalized)."""
    h2 = h.at[hm.QUAT_SLICE].multiply(2.5)
    c1, _ = hm.hand_spheres_world(h)
    c2, _ = hm.hand_spheres_world(h2)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-4)


def test_angle_bounds_clip():
    """Angles beyond anatomical limits are clipped: geometry saturates."""
    h = hm.default_pose()
    h_extreme = h.at[hm.ANGLES_SLICE].set(100.0)
    h_limit = h.at[hm.ANGLES_SLICE].set(hm.angle_upper_bounds())
    c1, _ = hm.hand_spheres_world(h_extreme)
    c2, _ = hm.hand_spheres_world(h_limit)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)


def test_bounds_contain_center():
    h = hm.default_pose()
    lo = hm.parameter_lower_bounds(h)
    hi = hm.parameter_upper_bounds(h)
    assert bool(jnp.all(lo <= h)) and bool(jnp.all(h <= hi))


def test_fingers_curl_towards_palm():
    """Flexing all fingers moves fingertips towards -z (palm side)."""
    open_h = hm.default_pose()
    curled = open_h.at[hm.ANGLES_SLICE].set(
        jnp.tile(jnp.asarray([0.0, 1.2, 1.2, 1.0]), 5)
    )
    c_open, _ = hm.hand_spheres_local(open_h[hm.ANGLES_SLICE])
    c_curl, _ = hm.hand_spheres_local(curled[hm.ANGLES_SLICE])
    spheres_per_finger = hm.NUM_BONES_PER_FINGER * hm.SPHERES_PER_BONE + 1
    # index fingertip: palm spheres + thumb block + index bones
    tip = hm.NUM_PALM_SPHERES + 2 * spheres_per_finger - 1
    assert float(c_curl[tip, 2]) < float(c_open[tip, 2])


def _two_hands():
    left = hm.default_pose(0.5).at[0].set(-0.04).at[3:7].set(jnp.asarray([0.9, 0.1, 0.3, 0.0]))
    right = hm.default_pose(0.62).at[0].set(0.04).at[7:].set(0.4)
    return jnp.concatenate([left, right])


def test_a_pose_of_two_hands_acts_hand_by_hand():
    """Bounds, normalisation and spheres of a 54-wide pose are each hand's
    own, the left hand's spheres mirrored in its local x."""
    h = _two_hands()
    left, right = h[:hm.NUM_PARAMS], h[hm.NUM_PARAMS:]
    assert hm.num_hands(h) == 2 and len(hm.hands(h)) == 2
    for fn in (hm.parameter_lower_bounds, hm.parameter_upper_bounds,
               hm.normalize_configuration):
        np.testing.assert_array_equal(
            np.asarray(fn(h)), np.concatenate([np.asarray(fn(left)), np.asarray(fn(right))]))
    batch = jnp.stack([h, 2.0 * h])
    np.testing.assert_array_equal(np.asarray(hm.normalize_configuration(batch)[1]),
                                  np.asarray(hm.normalize_configuration(2.0 * h)))
    spheres = hm.pack_spheres(h)
    assert spheres.shape == (2 * hm.NUM_SPHERES, 4)
    np.testing.assert_array_equal(np.asarray(spheres[hm.NUM_SPHERES:]),
                                  np.asarray(hm.pack_spheres(right)))
    c, r = hm.hand_spheres_world(left, left=True)
    np.testing.assert_array_equal(np.asarray(spheres[:hm.NUM_SPHERES]),
                                  np.asarray(jnp.concatenate([c, r[:, None]], axis=-1)))
    c_right, _ = hm.hand_spheres_world(left)
    assert not np.allclose(np.asarray(c), np.asarray(c_right))


@pytest.mark.parametrize("width", [26, 28, 81, 108])
def test_a_pose_of_neither_one_nor_two_hands_is_refused(width):
    with pytest.raises(ValueError, match="1 or 2 hands"):
        hm.pack_spheres(jnp.zeros((width,)))
