"""27-DoF kinematic hand model (paper §3.1, "Hand model").

The configuration vector ``h`` of one hand has 27 kinematic parameters:

* ``h[0:3]``   — 3D location of the hand root (palm center), meters.
* ``h[3:7]``   — 3D orientation as a unit quaternion ``(w, x, y, z)``
  (the paper uses a quaternion "to avoid gimbal locks").
* ``h[7:27]``  — 20 bone angles encoding finger articulation, radians:
  4 per finger ``(abduction, mcp_flex, pip_flex, dip_flex)`` for the four
  fingers, and ``(tm_abd, tm_flex, mcp_flex, ip_flex)`` for the thumb.

The geometry follows the FORTH generative-tracker family (Oikonomidis et
al., BMVC 2011 — reference [8] of the paper): the hand is a union of
quadric primitives. We use spheres placed along each bone (a capsule
approximated by ``SPHERES_PER_BONE`` spheres) plus a palm slab of spheres,
because analytic sphere depth is pure FMA math — the TPU-idiomatic
equivalent of the paper's CUDA rasterizer (see DESIGN.md §2).

A pose of k hands is k such vectors side by side, ``k * NUM_PARAMS``
wide: one hand is the right hand described here; two are a left and a
right hand, in that order, as Oikonomidis, Kyriazis and Argyros track
two strongly interacting hands (CVPR, 2012). The left hand is the right
hand's geometry mirrored in its local x. Every function below that
takes a pose acts hand by hand.

Everything here is pure JAX and differentiable (PSO does not need
gradients — the paper stresses that — but differentiability is free and
lets tests cross-check with gradient descent).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Parameter layout
# ---------------------------------------------------------------------------

NUM_PARAMS = 27  # of one hand
POS_SLICE = slice(0, 3)
QUAT_SLICE = slice(3, 7)
ANGLES_SLICE = slice(7, 27)

FINGER_NAMES = ("thumb", "index", "middle", "ring", "pinky")
ANGLES_PER_FINGER = 4

# Geometry constants (meters). Proportions of an average adult hand.
PALM_WIDTH = 0.085
PALM_LENGTH = 0.095
PALM_THICKNESS = 0.030

# Finger attachment points on the palm, in the hand local frame:
#   +x: thumb side (radial), +y: from wrist towards fingers, +z: out of the
#   back of the hand (towards the camera when the palm faces away).
_FINGER_BASES = (
    # thumb attaches low on the radial side
    (0.040, 0.005, -0.010),
    (0.032, 0.048, 0.0),   # index
    (0.010, 0.052, 0.0),   # middle
    (-0.012, 0.050, 0.0),  # ring
    (-0.033, 0.044, 0.0),  # pinky
)

# Per-finger bone lengths (proximal, middle, distal), meters.
_BONE_LENGTHS = (
    (0.046, 0.035, 0.028),  # thumb (metacarpal treated as proximal)
    (0.040, 0.026, 0.018),  # index
    (0.044, 0.029, 0.019),  # middle
    (0.041, 0.027, 0.018),  # ring
    (0.032, 0.021, 0.016),  # pinky
)

# Per-finger base radii, meters (tapers towards the tip).
_FINGER_RADII = (0.011, 0.009, 0.009, 0.0085, 0.0075)

# Resting direction of each finger in the palm frame (unit-ish vectors,
# normalized in code). The thumb points sideways+forward.
_FINGER_DIRS = (
    (0.8, 0.5, -0.2),
    (0.05, 1.0, 0.0),
    (0.0, 1.0, 0.0),
    (-0.05, 1.0, 0.0),
    (-0.12, 1.0, 0.0),
)

SPHERES_PER_BONE = 2
NUM_BONES_PER_FINGER = 3
# palm spheres: 3 columns x 3 rows
_PALM_GRID = (3, 3)
NUM_PALM_SPHERES = _PALM_GRID[0] * _PALM_GRID[1]
NUM_FINGER_SPHERES = (
    len(FINGER_NAMES) * NUM_BONES_PER_FINGER * SPHERES_PER_BONE
)
NUM_SPHERES_RAW = NUM_PALM_SPHERES + NUM_FINGER_SPHERES + len(FINGER_NAMES)
# pad to a multiple of 8 so kernel tiles stay aligned
NUM_SPHERES = ((NUM_SPHERES_RAW + 7) // 8) * 8

# Whether each hand of a pose of k hands is a left hand, by k.
_LEFT = {1: (False,), 2: (True, False)}


def num_hands(h: jnp.ndarray) -> int:
    """The number of hands of pose(s) h (..., k * NUM_PARAMS): 1 or 2."""
    k, rest = divmod(h.shape[-1], NUM_PARAMS)
    if rest or k not in _LEFT:
        raise ValueError(f"a pose holds 1 or 2 hands of {NUM_PARAMS} parameters, "
                         f"not {h.shape[-1]} parameters")
    return k


def hands(h: jnp.ndarray) -> list:
    """The (..., NUM_PARAMS) parameters of each hand of pose(s) h, in order."""
    k = num_hands(h)
    if k == 1:
        return [h]
    return [h[..., i * NUM_PARAMS:(i + 1) * NUM_PARAMS] for i in range(k)]


def _join(parts: list, axis: int = -1) -> jnp.ndarray:
    """The hands' parts side by side; one hand's part as it is."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


# Per-dimension articulation limits (radians), used both to clamp FK inputs
# and as PSO search bounds.
_ABD_LIMIT = 0.35
_FLEX_LO, _FLEX_HI = -0.26, 1.9


def angle_lower_bounds() -> jnp.ndarray:
    lo = []
    for _ in FINGER_NAMES:
        lo.extend([-_ABD_LIMIT, _FLEX_LO, _FLEX_LO, _FLEX_LO])
    return jnp.asarray(lo, dtype=jnp.float32)


def angle_upper_bounds() -> jnp.ndarray:
    hi = []
    for _ in FINGER_NAMES:
        hi.extend([_ABD_LIMIT, _FLEX_HI, _FLEX_HI, _FLEX_HI])
    return jnp.asarray(hi, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Quaternion utilities (w, x, y, z convention)
# ---------------------------------------------------------------------------


def quat_normalize(q: jnp.ndarray) -> jnp.ndarray:
    return q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)


def quat_multiply(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    aw, ax, ay, az = a[..., 0], a[..., 1], a[..., 2], a[..., 3]
    bw, bx, by, bz = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    return jnp.stack(
        [
            aw * bw - ax * bx - ay * by - az * bz,
            aw * bx + ax * bw + ay * bz - az * by,
            aw * by - ax * bz + ay * bw + az * bx,
            aw * bz + ax * by - ay * bx + az * bw,
        ],
        axis=-1,
    )


def quat_rotate(q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Rotate vector(s) v by unit quaternion(s) q."""
    w = q[..., 0:1]
    u = q[..., 1:4]
    # v' = v + 2 w (u x v) + 2 (u x (u x v))
    uv = jnp.cross(u, v)
    return v + 2.0 * (w * uv + jnp.cross(u, uv))


def quat_from_axis_angle(axis: jnp.ndarray, angle: jnp.ndarray) -> jnp.ndarray:
    axis = axis / (jnp.linalg.norm(axis, axis=-1, keepdims=True) + 1e-12)
    half = angle * 0.5
    s = jnp.sin(half)
    return jnp.concatenate(
        [jnp.cos(half)[..., None], axis * s[..., None]], axis=-1
    )


# ---------------------------------------------------------------------------
# Forward kinematics -> sphere primitives
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HandGeometry:
    """Static geometry description (non-traced constants)."""

    num_spheres: int = NUM_SPHERES
    palm_width: float = PALM_WIDTH
    palm_length: float = PALM_LENGTH


def _palm_spheres_local() -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Palm sphere centers + radii in the hand local frame.

    Built with numpy so the cached constants are real arrays even when
    the first call happens under a jit trace (a jnp build here would
    cache — and leak — tracers)."""
    import numpy as np

    xs = np.linspace(-PALM_WIDTH / 2 * 0.7, PALM_WIDTH / 2 * 0.7, _PALM_GRID[0])
    ys = np.linspace(-PALM_LENGTH / 2 * 0.55, PALM_LENGTH / 2 * 0.75, _PALM_GRID[1])
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    centers = np.stack(
        [gx.reshape(-1), gy.reshape(-1), np.zeros(NUM_PALM_SPHERES)], axis=-1
    )
    radii = np.full((NUM_PALM_SPHERES,), PALM_THICKNESS * 0.75)
    return centers.astype(np.float32), radii.astype(np.float32)


_PALM_CENTERS, _PALM_RADII = None, None  # lazily built (avoid import-time jax)


def _get_palm() -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The cache holds NUMPY arrays; conversion happens per call site so
    a first call under a jit trace can never leak tracers into the
    cache (they would escape to later out-of-trace calls)."""
    global _PALM_CENTERS, _PALM_RADII
    if _PALM_CENTERS is None:
        _PALM_CENTERS, _PALM_RADII = _palm_spheres_local()
    return jnp.asarray(_PALM_CENTERS), jnp.asarray(_PALM_RADII)


def _finger_spheres(
    base: jnp.ndarray,
    rest_dir: jnp.ndarray,
    lengths: Tuple[float, float, float],
    radius: float,
    angles: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """FK for one finger in the hand local frame.

    angles = (abduction, flex1, flex2, flex3). Flexion axis is the local +x
    (curling towards the palm, i.e. rotating the bone direction towards -z);
    abduction swings around +z.
    """
    rest_dir = rest_dir / jnp.linalg.norm(rest_dir)
    # Build the finger base frame: y' = rest_dir, z' = palm normal.
    # Flexion axis z' x dir so positive flexion curls towards the palm
    # (-z), matching anatomical convention.
    z_axis = jnp.asarray([0.0, 0.0, 1.0], dtype=jnp.float32)
    x_axis = jnp.cross(z_axis, rest_dir)
    x_axis = x_axis / (jnp.linalg.norm(x_axis) + 1e-12)

    q_abd = quat_from_axis_angle(z_axis, angles[0])
    q = q_abd
    centers = []
    radii = []
    pos = base
    direction = rest_dir
    for bone_idx in range(NUM_BONES_PER_FINGER):
        flex = angles[1 + bone_idx]
        q_flex = quat_from_axis_angle(x_axis, flex)
        q = quat_multiply(q, q_flex)
        direction = quat_rotate(quat_normalize(q), rest_dir)
        length = lengths[bone_idx]
        r = radius * (1.0 - 0.15 * bone_idx)
        for k in range(SPHERES_PER_BONE):
            frac = (k + 1.0) / SPHERES_PER_BONE
            centers.append(pos + direction * (length * frac))
            radii.append(r)
        pos = pos + direction * length
    # fingertip sphere
    centers.append(pos + direction * (radius * 0.5))
    radii.append(radius * 0.85)
    return jnp.stack(centers), jnp.asarray(radii, dtype=jnp.float32)


def hand_spheres_local(angles: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All sphere primitives in the hand local frame.

    Args:
      angles: (20,) articulation angles.

    Returns:
      centers (NUM_SPHERES, 3), radii (NUM_SPHERES,) — zero-radius padding
      spheres at the end.
    """
    lo, hi = angle_lower_bounds(), angle_upper_bounds()
    angles = jnp.clip(angles, lo, hi)
    palm_c, palm_r = _get_palm()
    centers = [palm_c]
    radii = [palm_r]
    for f, name in enumerate(FINGER_NAMES):
        fa = angles[f * ANGLES_PER_FINGER : (f + 1) * ANGLES_PER_FINGER]
        c, r = _finger_spheres(
            jnp.asarray(_FINGER_BASES[f], dtype=jnp.float32),
            jnp.asarray(_FINGER_DIRS[f], dtype=jnp.float32),
            _BONE_LENGTHS[f],
            _FINGER_RADII[f],
            fa,
        )
        centers.append(c)
        radii.append(r)
    centers = jnp.concatenate(centers, axis=0)
    radii = jnp.concatenate(radii, axis=0)
    pad = NUM_SPHERES - centers.shape[0]
    if pad:
        centers = jnp.concatenate(
            [centers, jnp.zeros((pad, 3), dtype=jnp.float32)], axis=0
        )
        # zero radius => never hit
        radii = jnp.concatenate([radii, jnp.zeros((pad,), dtype=jnp.float32)])
    return centers, radii


def hand_spheres_world(h: jnp.ndarray, left: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sphere primitives in camera/world coordinates for one hand.

    Args:
      h: (NUM_PARAMS,) configuration of one hand.
      left: a left hand: the local sphere centres mirrored in x.

    Returns:
      centers (NUM_SPHERES, 3) in camera frame, radii (NUM_SPHERES,).
    """
    pos = h[POS_SLICE]
    quat = quat_normalize(h[QUAT_SLICE])
    angles = h[ANGLES_SLICE]
    centers_l, radii = hand_spheres_local(angles)
    if left:
        centers_l = centers_l * jnp.asarray([-1.0, 1.0, 1.0], dtype=centers_l.dtype)
    centers_w = quat_rotate(quat[None, :], centers_l) + pos[None, :]
    return centers_w, radii


def pack_spheres(h: jnp.ndarray) -> jnp.ndarray:
    """(k * NUM_SPHERES, 4) packed [cx, cy, cz, r] of the k hands of pose h
    (k * NUM_PARAMS,), hand by hand — the kernel input format."""
    packed = []
    for hand, left in zip(hands(h), _LEFT[num_hands(h)]):
        c, r = hand_spheres_world(hand, left)
        packed.append(jnp.concatenate([c, r[:, None]], axis=-1))
    return _join(packed, axis=0)


def default_pose(distance: float = 0.55) -> jnp.ndarray:
    """A neutral open (right) hand facing the camera at `distance` meters."""
    h = jnp.zeros((NUM_PARAMS,), dtype=jnp.float32)
    h = h.at[2].set(distance)
    h = h.at[3].set(1.0)  # identity quaternion
    return h


def parameter_lower_bounds(center: jnp.ndarray, pos_range: float = 0.12,
                           quat_range: float = 0.25) -> jnp.ndarray:
    """PSO lower bounds: a box around `center` (the previous-frame solution),
    hand by hand.

    The paper: "particles are initialized around the solution of the
    previous frame. The space around that solution is made large enough to
    include the current frame estimation."
    """
    return _join([jnp.concatenate([
        c[POS_SLICE] - pos_range,
        c[QUAT_SLICE] - quat_range,
        jnp.maximum(c[ANGLES_SLICE] - 0.6, angle_lower_bounds()),
    ]) for c in hands(center)])


def parameter_upper_bounds(center: jnp.ndarray, pos_range: float = 0.12,
                           quat_range: float = 0.25) -> jnp.ndarray:
    return _join([jnp.concatenate([
        c[POS_SLICE] + pos_range,
        c[QUAT_SLICE] + quat_range,
        jnp.minimum(c[ANGLES_SLICE] + 0.6, angle_upper_bounds()),
    ]) for c in hands(center)])


def normalize_configuration(h: jnp.ndarray) -> jnp.ndarray:
    """Renormalize each hand's quaternion block (PSO moves particles off the
    unit-quaternion manifold; this projects back)."""
    out = []
    for c in hands(h):
        q = quat_normalize(c[..., QUAT_SLICE])
        out.append(jnp.concatenate([c[..., POS_SLICE], q, c[..., ANGLES_SLICE]], axis=-1))
    return _join(out)
