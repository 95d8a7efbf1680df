"""A reference module of another pose width, for the tests only: two of
``bench.reference``'s hands side by side in one depth map, 54 parameters
(each hand's 27 in turn) and 96 spheres, B the union of the two hands'
boxes. It keeps the contract written in ``bench/reference.py``, and no
configuration uses it: it shows that a hand model of another width runs
through the generator and the check with no edit to either."""

import jax.numpy as jnp
import numpy as np

from bench import reference as one
from bench.reference import camera_rays, dot_high, dot_highest  # noqa: F401

NUM_PARAMS = 2 * one.NUM_PARAMS
NUM_SPHERES = 2 * one.NUM_SPHERES
SPREAD_M = 0.10  # each hand's offset from the centre along x
PHASE_FRAMES = 45  # the second hand moves this far behind the first


def _split(h):
    return h[..., :one.NUM_PARAMS], h[..., one.NUM_PARAMS:]


def trajectory(motion: dict, num_frames: int, offset: int) -> np.ndarray:
    left = one.trajectory(motion, num_frames, offset)
    right = one.trajectory(motion, num_frames, offset + PHASE_FRAMES)
    left[:, 0] -= SPREAD_M
    right[:, 0] += SPREAD_M
    return np.concatenate([left, right], axis=1)


def render(h, rays, dot=dot_highest):
    left, right = _split(h)
    spheres = jnp.concatenate([one.hand_spheres(left), one.hand_spheres(right)])
    return one.sphere_depth(rays, spheres, dot)


def score(h, d_o, mask, rays, dot=dot_highest):
    err = jnp.minimum(jnp.abs(render(h, rays, dot) - d_o), one.CLAMP_T)
    m = mask.astype(jnp.float32)
    return jnp.sum(err * m) / jnp.maximum(jnp.sum(m), 1.0)


def box_mask(d_o, h_prev, half_width):
    left, right = _split(h_prev)
    return one.box_mask(d_o, left, half_width) | one.box_mask(d_o, right, half_width)


def recover_best(h, h_prev, smoothing):
    (hl, hr), (pl, pr) = _split(h), _split(h_prev)
    return np.concatenate([one.recover_best(hl, pl, smoothing),
                           one.recover_best(hr, pr, smoothing)], axis=1)


def positions(h):
    left, right = _split(h)
    return np.concatenate([one.positions(left), one.positions(right)], axis=1)
