"""Plain float32 reference of the two-hand tracker's objective.

Two strongly interacting hands (Oikonomidis, Kyriazis and Argyros,
CVPR, 2012): one pose of 54 parameters, the left hand's 27 then the right
hand's, each laid out as in ``bench/reference.py``. Both hands' spheres
are rendered into one depth map, so the nearer hand occludes the other,
and the score adds a penalty for the hands passing into each other:

    E(h) = E_D(h) + λ · kc(h),
    kc(h) = sum_{p in S_L, q in S_R} max(0, r_p + r_q - |c_p - c_q|)

over the spheres of the left and of the right hand that have a radius,
with λ = ``COLLIDE_WEIGHT`` per metre of summed penetration (assumed: the
paper's value is not used). The right hand is ``bench.reference``'s hand;
the left hand is its local sphere centres mirrored in x, mirrored here.
It imports nothing of the program under test, and keeps the contract
written in ``bench/reference.py``.

The traffic (``trajectory``) puts both wrists on one motion, the left
hand ``hand_gap_m`` / 2 to the left of it and the right hand as far to
the right and ``depth_gap_m`` further from the camera, with the right
hand's fingers ``finger_lag_frames`` behind the left hand's: the front
hand hides part of the back one in more than half the frames.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import reference as one
from bench.reference import camera_rays, dot_high, dot_highest  # noqa: F401

NUM_PARAMS = 2 * one.NUM_PARAMS
NUM_SPHERES = 2 * one.NUM_SPHERES
COLLIDE_WEIGHT = 1.0  # λ, per metre of summed penetration depth
_MIRROR = np.float32([-1.0, 1.0, 1.0])
_IDENTITY = np.float32([0.0, 0.0, 0.0, 1.0, 0.0, 0.0, 0.0])  # no move, no turn


def _split(h):
    return h[..., :one.NUM_PARAMS], h[..., one.NUM_PARAMS:]


def left_hand_spheres(h: jnp.ndarray) -> jnp.ndarray:
    """(48, 4) spheres of the left hand of pose h (27,): the right hand's
    local centres, mirrored in x, then turned and moved as h says."""
    local = one.hand_spheres(jnp.concatenate([jnp.asarray(_IDENTITY), h[7:]]))
    quat = h[3:7] / (jnp.linalg.norm(h[3:7]) + 1e-12)
    centres = one._quat_rotate(quat, local[:, :3] * _MIRROR) + h[0:3]
    return jnp.concatenate([centres, local[:, 3:]], axis=-1)


def hand_spheres(h: jnp.ndarray) -> jnp.ndarray:
    """(96, 4) spheres of pose h (54,): the left hand's 48, the right's 48."""
    left, right = _split(h)
    return jnp.concatenate([left_hand_spheres(left), one.hand_spheres(right)])


def interpenetration(spheres: jnp.ndarray) -> jnp.ndarray:
    """kc of the (96, 4) spheres of one pose: every (left, right) pair of
    spheres with a radius, by the depth to which they overlap."""
    left, right = spheres[:one.NUM_SPHERES], spheres[one.NUM_SPHERES:]
    diff = left[:, None, :3] - right[None, :, :3]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    overlap = jnp.maximum(left[:, None, 3] + right[None, :, 3] - dist, 0.0)
    real = (left[:, None, 3] > 0) & (right[None, :, 3] > 0)
    return jnp.sum(jnp.where(real, overlap, 0.0))


def render(h, rays, dot=dot_highest):
    """(P,) depth of pose h: the nearest hit over both hands' spheres."""
    return one.sphere_depth(rays, hand_spheres(h), dot)


def score(h, d_o, mask, rays, dot=dot_highest):
    """E of pose h against the observed depth d_o (P,) over mask (P,)."""
    spheres = hand_spheres(h)
    err = jnp.minimum(jnp.abs(one.sphere_depth(rays, spheres, dot) - d_o), one.CLAMP_T)
    m = mask.astype(jnp.float32)
    e_d = jnp.sum(err * m) / jnp.maximum(jnp.sum(m), 1.0)
    return e_d + COLLIDE_WEIGHT * interpenetration(spheres)


def box_mask(d_o, h_prev, half_width):
    """B: the union of each hand's depth band around its own previous depth."""
    left, right = _split(h_prev)
    return one.box_mask(d_o, left, half_width) | one.box_mask(d_o, right, half_width)


def trajectory(motion: dict, num_frames: int, offset: int) -> np.ndarray:
    """(num_frames, 54) poses: both wrists on ``bench.reference``'s motion,
    the left hand x - hand_gap_m / 2, the right hand x + hand_gap_m / 2 and
    z + depth_gap_m, its finger angles those of finger_lag_frames later."""
    left = one.trajectory(motion, num_frames, offset)
    right = left.copy()
    right[:, 7:] = one.trajectory(motion, num_frames, offset + motion["finger_lag_frames"])[:, 7:]
    left[:, 0] -= motion["hand_gap_m"] / 2
    right[:, 0] += motion["hand_gap_m"] / 2
    right[:, 2] += motion["depth_gap_m"]
    return np.concatenate([left, right], axis=1)


def recover_best(h, h_prev, smoothing):
    """The swarm's best hypothesis behind each smoothed pose, hand by hand."""
    (hl, hr), (pl, pr) = _split(h), _split(h_prev)
    return np.concatenate([one.recover_best(hl, pl, smoothing),
                           one.recover_best(hr, pr, smoothing)], axis=1)


def positions(h):
    """(n, 2, 3) positions of the left and the right hand of poses h."""
    left, right = _split(h)
    return np.concatenate([one.positions(left), one.positions(right)], axis=1)
