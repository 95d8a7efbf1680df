"""The paper's objective function E_D (Eq. 2) and depth rendering.

    E_D(h, d^o) = (1 / N_P) * sum_{p in B} C(|d_p^h - d_p^o|, T)

where C(x, T) clamps at T = 30 cm to keep outliers from dominating, and B
is a bounding box containing the hand. The render is analytic sphere
ray-casting (DESIGN.md §2 explains why this replaces the paper's CUDA
rasterizer on TPU).

This module is the *reference* (pure jnp) implementation; the Pallas
kernel in ``repro.kernels.render_score`` computes the same quantity with
explicit VMEM tiling, and ``repro.kernels.ref`` re-exports these functions
as the kernel oracle.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import handmodel
from repro.core.camera import BACKGROUND_DEPTH, Camera

CLAMP_T = 0.30  # meters — the paper sets T = 30 cm.


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


def batched_objective(
    hs: jnp.ndarray,
    d_o: jnp.ndarray,
    camera: Camera,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Vectorized E_D over a particle population. hs: (N, 27) -> (N,).

    This is the GPGPU-parallel evaluation the paper offloads; the Pallas
    kernel path (repro.kernels.ops.render_score) computes the same thing
    with explicit tiling and is swapped in by the tracker when enabled.
    """
    return jax.vmap(lambda h: objective(h, d_o, camera, mask))(hs)


def bounding_box_mask(
    d_o: jnp.ndarray, center_depth: jnp.ndarray, half_width: float = 0.25
) -> jnp.ndarray:
    """Bounding-box B extraction: pixels whose observed depth lies within
    ``half_width`` meters of the previous solution's depth. This is the
    cheap 'segmentation' stage-1 uses; background (far) pixels drop out."""
    return jnp.abs(d_o - center_depth) < half_width
