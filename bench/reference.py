"""Plain float32 reference of the tracker's render-and-score, E_D.

The benchmark's yardstick for the population evaluation, and the
renderer that makes its observations. It imports nothing of the program
under test: the hand model's forward kinematics, the camera rays, the
sphere ray-cast and the clamped-L1 score are written out here from
arXiv:1804.11256 §3.1 and the 48-sphere hand geometry.

    E_D(h, d_o) = sum_{p in B} min(|d_h(p) - d_o(p)|, T) / |B|

with T = 0.30 m and B the pixels whose observed depth lies within
``bbox_half_width`` of the previous pose's depth.

The ``<ray, centre>`` product cancels badly in the hit test: (d.c)^2 and
|d|^2 (|c|^2 - r^2) are both about 0.25 while their difference is about
1e-4. So the reference forms it at highest matmul precision; the control
forms it in ``high`` (three bfloat16 passes), emulated with explicit
bfloat16 rounding and float32 arithmetic, so that it reads the same on
every backend.

A configuration names its reference module in its ``"reference"`` key
(``bench/reference.py`` for this one-hand model), and the harness, the
traffic generator and the check know the hand only through these names
of it, so a hand model of another pose width or sphere count is a module
of its own:

* ``NUM_PARAMS``, ``NUM_SPHERES``: the pose width and the spheres
  rendered, as the configuration's ``"hand"`` states them;
* ``trajectory(motion, num_frames, offset)``: (num_frames, NUM_PARAMS)
  true poses of a traffic file's ``motion``;
* ``camera_rays(camera)``: (P, 3) rays of a configuration's camera;
* ``render(h, rays, dot)``: (P,) depth of pose h;
* ``score(h, d_o, mask, rays, dot)``: E_D of pose h over ``mask``;
* ``dot_high``: the ``<ray, centre>`` product one precision below the
  configuration's, for ``dot`` (the control);
* ``box_mask(d_o, h_prev, half_width)``: the bounding box B from the
  previous pose;
* ``recover_best(h, h_prev, smoothing)``: rows of the swarm's best
  hypothesis behind rows of smoothed poses;
* ``positions(h)``: (n, k, 3) positions of the k hands of n poses.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

CLAMP_T = 0.30  # meters
BACKGROUND_DEPTH = 10.0  # meters; depth of a ray that hits nothing
NUM_PARAMS = 27
NUM_SPHERES = 48

# --- hand geometry (meters) -------------------------------------------------
PALM_WIDTH, PALM_LENGTH, PALM_THICKNESS = 0.085, 0.095, 0.030
FINGER_BASES = (
    (0.040, 0.005, -0.010),
    (0.032, 0.048, 0.0),
    (0.010, 0.052, 0.0),
    (-0.012, 0.050, 0.0),
    (-0.033, 0.044, 0.0),
)
BONE_LENGTHS = (
    (0.046, 0.035, 0.028),
    (0.040, 0.026, 0.018),
    (0.044, 0.029, 0.019),
    (0.041, 0.027, 0.018),
    (0.032, 0.021, 0.016),
)
FINGER_RADII = (0.011, 0.009, 0.009, 0.0085, 0.0075)
FINGER_DIRS = (
    (0.8, 0.5, -0.2),
    (0.05, 1.0, 0.0),
    (0.0, 1.0, 0.0),
    (-0.05, 1.0, 0.0),
    (-0.12, 1.0, 0.0),
)
ABD_LIMIT, FLEX_LO, FLEX_HI = 0.35, -0.26, 1.9
ANGLE_LO = np.tile(np.float32([-ABD_LIMIT, FLEX_LO, FLEX_LO, FLEX_LO]), 5)
ANGLE_HI = np.tile(np.float32([ABD_LIMIT, FLEX_HI, FLEX_HI, FLEX_HI]), 5)


def _palm() -> tuple[np.ndarray, np.ndarray]:
    xs = np.linspace(-PALM_WIDTH / 2 * 0.7, PALM_WIDTH / 2 * 0.7, 3)
    ys = np.linspace(-PALM_LENGTH / 2 * 0.55, PALM_LENGTH / 2 * 0.75, 3)
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    centres = np.stack([gx.ravel(), gy.ravel(), np.zeros(9)], axis=-1)
    return centres.astype(np.float32), np.full(9, PALM_THICKNESS * 0.75, np.float32)


def _quat_mul(a, b):
    aw, ax, ay, az = a[0], a[1], a[2], a[3]
    bw, bx, by, bz = b[0], b[1], b[2], b[3]
    return jnp.stack([
        aw * bw - ax * bx - ay * by - az * bz,
        aw * bx + ax * bw + ay * bz - az * by,
        aw * by - ax * bz + ay * bw + az * bx,
        aw * bz + ax * by - ay * bx + az * bw,
    ])


def _quat_rotate(q, v):
    """Rotate v (..., 3) by the unit quaternion q (4,)."""
    w, u = q[0], q[1:4]
    uv = jnp.cross(u, v)
    return v + 2.0 * (w * uv + jnp.cross(u, uv))


def _axis_angle(axis, angle):
    axis = axis / (jnp.linalg.norm(axis) + 1e-12)
    return jnp.concatenate([jnp.cos(angle * 0.5)[None], axis * jnp.sin(angle * 0.5)])


def hand_spheres(h: jnp.ndarray) -> jnp.ndarray:
    """(NUM_SPHERES, 4) spheres [cx, cy, cz, r] in camera coordinates.

    h = (position 3, quaternion w x y z 4, 20 finger angles): per finger
    abduction then three flexions. Nine palm spheres, two spheres per
    bone, a tip sphere per finger, zero-radius padding to 48."""
    angles = jnp.clip(h[7:27], ANGLE_LO, ANGLE_HI)
    palm_c, palm_r = _palm()
    centres, radii = [jnp.asarray(palm_c)], [jnp.asarray(palm_r)]
    z_axis = jnp.float32([0.0, 0.0, 1.0])
    for f in range(5):
        a = angles[4 * f: 4 * f + 4]
        rest = jnp.float32(FINGER_DIRS[f])
        rest = rest / jnp.linalg.norm(rest)
        x_axis = jnp.cross(z_axis, rest)
        x_axis = x_axis / (jnp.linalg.norm(x_axis) + 1e-12)
        q = _axis_angle(z_axis, a[0])
        pos = jnp.float32(FINGER_BASES[f])
        direction = rest
        for bone in range(3):
            q = _quat_mul(q, _axis_angle(x_axis, a[1 + bone]))
            direction = _quat_rotate(q / (jnp.linalg.norm(q) + 1e-12), rest)
            length = BONE_LENGTHS[f][bone]
            r = FINGER_RADII[f] * (1.0 - 0.15 * bone)
            for k in range(2):
                centres.append((pos + direction * (length * (k + 1.0) / 2))[None])
                radii.append(jnp.float32([r]))
            pos = pos + direction * length
        centres.append((pos + direction * (FINGER_RADII[f] * 0.5))[None])
        radii.append(jnp.float32([FINGER_RADII[f] * 0.85]))
    c = jnp.concatenate(centres)
    r = jnp.concatenate(radii)
    pad = NUM_SPHERES - c.shape[0]
    c = jnp.concatenate([c, jnp.zeros((pad, 3), jnp.float32)])
    r = jnp.concatenate([r, jnp.zeros((pad,), jnp.float32)])
    quat = h[3:7] / (jnp.linalg.norm(h[3:7]) + 1e-12)
    world = _quat_rotate(quat, c) + h[0:3]
    return jnp.concatenate([world, r[:, None]], axis=-1)


def camera_rays(camera: dict) -> jnp.ndarray:
    """(H*W, 3) pinhole ray directions with d_z = 1, row-major pixels."""
    u = (jnp.arange(camera["width"], dtype=jnp.float32) - camera["cx"]) / camera["fx"]
    v = (jnp.arange(camera["height"], dtype=jnp.float32) - camera["cy"]) / camera["fy"]
    gu, gv = jnp.meshgrid(u, v, indexing="xy")
    return jnp.stack([gu, gv, jnp.ones_like(gu)], axis=-1).reshape(-1, 3)


# --- the <ray, centre> product in two precisions ----------------------------

def dot_highest(rays: jnp.ndarray, centres: jnp.ndarray) -> jnp.ndarray:
    """(P, 3) x (S, 3) -> (P, S) at highest matmul precision."""
    return jnp.matmul(rays, centres.T, precision=jax.lax.Precision.HIGHEST)


def _bf16(x):
    """x rounded to bfloat16, kept in float32. ``reduce_precision`` and not
    a pair of casts, which XLA may drop as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot_high(rays: jnp.ndarray, centres: jnp.ndarray) -> jnp.ndarray:
    """The same product at ``high`` precision: each operand split into a
    bfloat16 head and tail, and the three products head*head, head*tail
    and tail*head summed in float32 (the tail*tail term dropped)."""
    a_hi, b_hi = _bf16(rays), _bf16(centres)
    a_lo, b_lo = _bf16(rays - a_hi), _bf16(centres - b_hi)
    prod = (a_hi[:, None, :] * b_hi[None, :, :]
            + a_hi[:, None, :] * b_lo[None, :, :]
            + a_lo[:, None, :] * b_hi[None, :, :])
    return jnp.sum(prod, axis=-1)


DotFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


def sphere_depth(rays: jnp.ndarray, spheres: jnp.ndarray,
                 dot: DotFn = dot_highest) -> jnp.ndarray:
    """(P,) depth of the nearest sphere hit along each ray.

    Ray x = t d meets sphere (c, r) where
    t^2 |d|^2 - 2 t (d.c) + |c|^2 - r^2 = 0; the near root is taken, and a
    negative discriminant or a root at t <= 1e-4 is a miss."""
    d2 = jnp.sum(rays * rays, axis=-1)[:, None]
    c, r = spheres[:, :3], spheres[:, 3]
    dc = dot(rays, c)
    c2r2 = (jnp.sum(c * c, axis=-1) - r * r)[None, :]
    disc = dc * dc - d2 * c2r2
    t = (dc - jnp.sqrt(jnp.maximum(disc, 0.0))) / d2
    t = jnp.where((disc >= 0.0) & (t > 1e-4), t, BACKGROUND_DEPTH)
    return jnp.min(t, axis=-1)


def render(h: jnp.ndarray, rays: jnp.ndarray, dot: DotFn = dot_highest) -> jnp.ndarray:
    """(P,) depth map of pose h."""
    return sphere_depth(rays, hand_spheres(h), dot)


def bbox_mask(d_o: jnp.ndarray, centre_depth: jnp.ndarray, half_width: float) -> jnp.ndarray:
    """The bounding box B: pixels observed within half_width of centre_depth."""
    return jnp.abs(d_o - centre_depth) < half_width


def score(h: jnp.ndarray, d_o: jnp.ndarray, mask: jnp.ndarray, rays: jnp.ndarray,
          dot: DotFn = dot_highest) -> jnp.ndarray:
    """E_D of pose h against the observed depth d_o (P,) over mask (P,)."""
    err = jnp.minimum(jnp.abs(render(h, rays, dot) - d_o), CLAMP_T)
    m = mask.astype(jnp.float32)
    return jnp.sum(err * m) / jnp.maximum(jnp.sum(m), 1.0)


def box_mask(d_o: jnp.ndarray, h_prev: jnp.ndarray, half_width: float) -> jnp.ndarray:
    """B from the previous pose: pixels observed within half_width of its depth."""
    return bbox_mask(d_o, h_prev[2], half_width)


def positions(h: np.ndarray) -> np.ndarray:
    """(n, 1, 3) hand positions of poses h (n, NUM_PARAMS)."""
    return h[:, None, :3]


def trajectory(motion: dict, num_frames: int, offset: int) -> np.ndarray:
    """(num_frames, 27) poses: frames offset .. offset + num_frames - 1.

    The repository's default motion: hand at ``base_distance``, position
    sweeps, wrist rotation about a wobbling axis, staggered finger curls,
    with ``burst_speed`` times the speed for ``burst_length`` of every
    ``burst_every`` frames."""
    total = offset + num_frames
    t = np.arange(total)
    in_burst = (t % motion["burst_every"]) >= motion["burst_every"] - motion["burst_length"]
    speed = np.where(in_burst, motion["burst_speed"], 1.0)
    phase = (np.cumsum(speed) / 30.0)[offset:]  # seconds at 30 frames/s

    amp = motion["position_amplitude"]
    hs = np.zeros((num_frames, NUM_PARAMS), np.float64)
    hs[:, 0] = amp * np.sin(2 * np.pi * 0.35 * phase)
    hs[:, 1] = amp * 0.6 * np.sin(2 * np.pi * 0.23 * phase + 1.0)
    hs[:, 2] = motion["base_distance"] + 0.04 * np.sin(2 * np.pi * 0.17 * phase)
    ang = motion["rotation_amplitude"] * np.sin(2 * np.pi * 0.3 * phase)
    axis = np.stack(
        [np.sin(0.7 * phase), np.cos(0.9 * phase), 0.4 * np.ones_like(phase)], axis=-1
    )
    axis /= np.linalg.norm(axis, axis=-1, keepdims=True)
    hs[:, 3] = np.cos(ang / 2)
    hs[:, 4:7] = axis * np.sin(ang / 2)[:, None]
    for f in range(5):
        curl = 0.5 * motion["curl_amplitude"] * (
            1 - np.cos(2 * np.pi * (0.4 + 0.05 * f) * phase + f)
        )
        base = 7 + 4 * f
        hs[:, base + 1] = curl * 0.9
        hs[:, base + 2] = curl
        hs[:, base + 3] = curl * 0.7
    return hs.astype(np.float32)


def recover_best(h: np.ndarray, h_prev: np.ndarray, smoothing: float) -> np.ndarray:
    """The swarm's best hypothesis behind a smoothed pose (rows of 27).

    ``Tracker.step`` reports h = normalize((1 - s) * normalize(best) +
    s * h_prev). Position and angles are affine in the best. The
    quaternion q_h is a normalized a * u + b * q_prev with u the best's
    unit quaternion, so u is lambda * q_h - b * q_prev with lambda the
    root that makes |u| = a."""
    h = h.astype(np.float64)
    hp = h_prev.astype(np.float64)
    a, b = np.float32(1.0 - smoothing), np.float32(smoothing)
    best = (h - b * hp) / a
    qh, qp = h[:, 3:7], hp[:, 3:7]
    nh = np.sum(qh * qh, axis=1)
    hp_dot = np.sum(qh * qp, axis=1)
    disc = (b * hp_dot) ** 2 - nh * (b * b * np.sum(qp * qp, axis=1) - a * a)
    lam = (b * hp_dot + np.sqrt(np.maximum(disc, 0.0))) / nh
    u = lam[:, None] * qh - b * qp
    best[:, 3:7] = u / np.linalg.norm(u, axis=1, keepdims=True)
    return best.astype(np.float32)
