"""The paper's objective function E_D (Eq. 2) and depth rendering.

    E_D(h, d^o) = (1 / N_P) * sum_{p in B} C(|d_p^h - d_p^o|, T)

where C(x, T) clamps at T = 30 cm to keep outliers from dominating, and B
is a bounding box containing the hand. The render is analytic sphere
ray-casting (DESIGN.md §2 explains why this replaces the paper's CUDA
rasterizer on TPU).

Like the paper's tracker, the population evaluation renders only inside
B: ``batched_objective`` moves B's pixels to the front of the frame and
renders and scores the first K of them, K the smallest rung of a ladder
fixed by the frame's pixel count (``box_ladder``, ``box_rung``) with
K >= |B|. Every pixel outside B adds an exact zero to the sum, so this
changes only the order in which the nonzero terms are added.

A hypothesis of two hands (Oikonomidis et al., CVPR, 2012) renders both
hands' spheres into one depth map, so the nearer hand occludes the
other, and its score adds a penalty for the hands passing into each
other:

    E(h) = E_D(h) + λ · kc(h),
    kc(h) = sum_{p in S_L, q in S_R} max(0, r_p + r_q - |c_p - c_q|)

over the left hand's and the right hand's spheres (``penalized``).

This module is the *reference* (pure jnp) implementation; the Pallas
kernel in ``repro.kernels.render_score`` computes the same quantity with
explicit VMEM tiling, and ``repro.kernels.ref`` re-exports these functions
as the kernel oracle.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import handmodel
from repro.core.camera import BACKGROUND_DEPTH, Camera

CLAMP_T = 0.30  # meters — the paper sets T = 30 cm.
# λ of the two-hand objective, per metre of summed penetration depth: an
# assumed value, not the paper's.
COLLIDE_WEIGHT = 1.0


def sphere_depth(rays: jnp.ndarray, spheres: jnp.ndarray) -> jnp.ndarray:
    """Analytic depth of the nearest sphere along each ray.

    Args:
      rays: (P, 3) ray directions with d_z == 1 (so t == metric depth).
      spheres: (S, 4) packed [cx, cy, cz, r].

    Returns:
      (P,) depth map; BACKGROUND_DEPTH where no sphere is hit.

    Math: for ray x = t*d and sphere (c, r):
      |t d - c|^2 = r^2
      t^2 |d|^2 - 2 t (d.c) + |c|^2 - r^2 = 0
      t = [ (d.c) - sqrt((d.c)^2 - |d|^2 (|c|^2 - r^2)) ] / |d|^2
    We take the near root; a negative discriminant or a behind-camera hit
    maps to BACKGROUND_DEPTH. Zero-radius padding spheres never hit because
    their discriminant is  (d.c)^2 - |d|^2 |c|^2 <= 0 (Cauchy-Schwarz),
    with equality only for rays through the center — give them |c|=0 and
    the near root is t=0, rejected by the t>eps test.

    The discriminant cancels: (d.c)^2 and |d|^2 (|c|^2 - r^2) are both
    ~0.25 m^2 while their difference is ~r^2 ~ 1e-4 m^2. So d.c is a
    float32 product at HIGHEST precision: a TPU runs a default-precision
    float32 matmul as one bfloat16 pass, and that flipped most silhouette
    pixels between hit and miss on a TPU v5e.
    """
    d2 = jnp.sum(rays * rays, axis=-1)  # (P,)
    c = spheres[:, :3]  # (S, 3)
    r = spheres[:, 3]  # (S,)
    dc = jnp.matmul(rays, c.T, precision=jax.lax.Precision.HIGHEST)  # (P, S)
    c2r2 = jnp.sum(c * c, axis=-1) - r * r  # (S,)
    disc = dc * dc - d2[:, None] * c2r2[None, :]  # (P, S)
    safe_disc = jnp.maximum(disc, 0.0)
    t = (dc - jnp.sqrt(safe_disc)) / d2[:, None]  # (P, S)
    hit = (disc >= 0.0) & (t > 1e-4)
    t = jnp.where(hit, t, BACKGROUND_DEPTH)
    return jnp.min(t, axis=-1)


def render_depth(h: jnp.ndarray, camera: Camera) -> jnp.ndarray:
    """Depth map (H, W) of hand configuration h."""
    spheres = handmodel.pack_spheres(h)
    depth = sphere_depth(camera.rays_flat(), spheres)
    return depth.reshape(camera.height, camera.width)


def clamped_l1(d_h: jnp.ndarray, d_o: jnp.ndarray, t: float = CLAMP_T) -> jnp.ndarray:
    """C(|d_h - d_o|, T) elementwise."""
    return jnp.minimum(jnp.abs(d_h - d_o), t)


def discrepancy(
    d_h: jnp.ndarray,
    d_o: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    t: float = CLAMP_T,
) -> jnp.ndarray:
    """E_D for rendered depth d_h against observed depth d_o.

    Args:
      d_h, d_o: (...,) depth maps (flattened or 2D, matching shapes).
      mask: optional boolean bounding-box mask B; True = inside B. When
        None, the whole frame is B (the ROI crop already applied).

    Returns:
      scalar E_D = mean over B of clamped absolute differences.
    """
    err = clamped_l1(d_h, d_o, t)
    if mask is None:
        return jnp.mean(err)
    msk = mask.astype(err.dtype)
    return jnp.sum(err * msk) / jnp.maximum(jnp.sum(msk), 1.0)


def objective(
    h: jnp.ndarray,
    d_o: jnp.ndarray,
    camera: Camera,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """E_D(h, o): render h and score against the observation. Scalar."""
    d_h = render_depth(h, camera)
    return discrepancy(d_h, d_o, mask)


LANE = 128  # pixels in a lane row: every rung but a small frame's top is a multiple


def box_ladder(num_pixels: int) -> Tuple[int, ...]:
    """The rungs K of the pixel ladder of a frame of ``num_pixels`` pixels.

    P/16, P/8, P/4, P/2 and P, each rounded up to a whole lane row and
    capped at P, equal rungs merged: (1024, 2048, 4096, 8192, 16384) at
    128x128, (128, 256, 512, 1024) at 32x32. The spacing is 2x, so a
    rung holds at most twice the pixels of B but for the lowest."""
    rungs = (min(num_pixels, -(-num_pixels // (LANE * d)) * LANE)
             for d in (16, 8, 4, 2, 1))
    return tuple(sorted(set(rungs)))


def box_rung(n, num_pixels: int):
    """The smallest rung K >= n of ``box_ladder(num_pixels)``: the pixels
    rendered for a bounding box of n pixels (n <= num_pixels). Takes a
    Python or a traced integer; returns a scalar array."""
    ladder = jnp.asarray(box_ladder(num_pixels))
    return ladder[jnp.sum(ladder < n)]


def interpenetration(spheres: jnp.ndarray) -> jnp.ndarray:
    """kc of packed two-hand spheres (..., 2 * NUM_SPHERES, 4) -> (...,).

    The sum, over every pair of a left-hand sphere p and a right-hand
    sphere q, of the depth max(0, r_p + r_q - |c_p - c_q|) by which they
    overlap. Padding spheres are left out: they have no radius, and sit
    at their hand's root, where a sphere of the other hand could hold
    them. Element-wise differences, so no matmul precision enters it."""
    with jax.named_scope("tracker.collide"):
        s, n = handmodel.NUM_SPHERES, handmodel.NUM_SPHERES_RAW
        left = spheres[..., :n, None, :]
        right = spheres[..., None, s:s + n, :]
        diff = left[..., :3] - right[..., :3]
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
        depth = jnp.maximum(left[..., 3] + right[..., 3] - dist, 0.0)
        return jnp.sum(depth, axis=(-2, -1))


def penalized(scores: jnp.ndarray, spheres: jnp.ndarray) -> jnp.ndarray:
    """E of a population from its E_D ``scores`` (N,) and packed spheres
    (N, k * NUM_SPHERES, 4): E_D + λ · kc for two hands, E_D for one."""
    if spheres.shape[-2] == handmodel.NUM_SPHERES:
        return scores
    return scores + COLLIDE_WEIGHT * interpenetration(spheres)


def batched_objective(
    hs: jnp.ndarray,
    d_o: jnp.ndarray,
    camera: Camera,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Vectorized E over a particle population. hs: (N, k * 27) -> (N,).

    This is the GPGPU-parallel evaluation the paper offloads; the Pallas
    kernel path (repro.kernels.ops.render_score) computes the same E_D
    with explicit tiling and is swapped in by the tracker when enabled.
    Each hypothesis's k hands are rendered in one pass over their
    k * NUM_SPHERES spheres: the nearest hit over all of them is the
    occlusion. With two hands, λ · kc is added (``penalized``).

    With a mask, only B's pixels are rendered. A stable partition puts
    B's n pixels first; the rays and d_o are gathered into that order, and
    the population is rendered and scored over the first K =
    ``box_rung(n, P)`` of them, slots past n masked out, the sum divided
    by max(n, 1) as ``discrepancy`` does. The terms left out are those a
    full-frame render multiplies by 0, so the score is E_D over B exactly,
    summed in another order. Under a trace, one ``lax.switch`` branch per
    rung, each under ``jax.named_scope(f"pixels_{K}")``, picks K outside
    the vmap over particles. The partition and the gather depend only on
    the frame, so XLA runs them once a frame, outside the PSO loop.
    """
    if mask is None:
        return penalized(jax.vmap(lambda h: objective(h, d_o, camera))(hs),
                         jax.vmap(handmodel.pack_spheres)(hs))
    inside = mask.reshape(-1).astype(bool)
    num_pixels = inside.shape[0]
    n = jnp.sum(inside, dtype=jnp.int32)
    # B's pixels first, in frame order: a sort of one integer key per pixel,
    # its index, plus P outside B. At 320x240 on a TPU v5e the sort took
    # 0.10 ms against 0.68 for `jnp.nonzero`'s scatter-add, and one gather
    # of (ray, depth) rows 0.18 ms against 0.82 for a gather of each. XLA
    # runs both once a frame, outside the PSO loop; a sort that carries
    # the indices as a second operand stays inside it.
    pixel = jnp.arange(num_pixels, dtype=jnp.int32)
    order = jnp.sort(jnp.where(inside, pixel, pixel + num_pixels)) % num_pixels
    pixels = jnp.concatenate([camera.rays_flat(), d_o.reshape(-1, 1)], axis=1)[order]
    rays, observed = pixels[:, :3], pixels[:, 3]
    ladder = box_ladder(num_pixels)

    def rung(k: int):
        def evaluate(spheres):
            with jax.named_scope(f"pixels_{k}"):
                in_box = jnp.arange(k) < n

                def score(s):
                    return discrepancy(sphere_depth(rays[:k], s), observed[:k], in_box)
                return jax.vmap(score)(spheres)
        return evaluate

    rendered = box_rung(n, num_pixels)
    spheres = jax.vmap(handmodel.pack_spheres)(hs)
    if not isinstance(rendered, jax.core.Tracer):
        # Called outside a trace, the count is on the host: run that rung
        # alone and op by op, as the rest of the call runs, rather than
        # compiling every rung for one call.
        return penalized(rung(int(rendered))(spheres), spheres)
    index = jnp.sum(jnp.asarray(ladder) < rendered)
    return penalized(jax.lax.switch(index, [rung(k) for k in ladder], spheres), spheres)


def bounding_box_mask(
    d_o: jnp.ndarray, center_depth: jnp.ndarray, half_width: float = 0.25
) -> jnp.ndarray:
    """Bounding-box B extraction: pixels whose observed depth lies within
    ``half_width`` meters of the previous solution's depth. This is the
    cheap 'segmentation' stage-1 uses; background (far) pixels drop out."""
    return jnp.abs(d_o - center_depth) < half_width
