"""Pure-jnp oracle for the render_score kernel.

Re-derives the exact quantity the kernel computes from the reference
objective implementation in ``repro.core.objective`` — the tests assert
``ops.render_score`` (Pallas) == ``ref.render_score`` (pure jnp) across
shape/dtype sweeps, within ``score_atol``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.camera import BACKGROUND_DEPTH
from repro.core.objective import CLAMP_T, sphere_depth


def render_score_sums(
    spheres: jnp.ndarray,  # (N, S, 4)
    rays: jnp.ndarray,  # (P, 3)
    depth_obs: jnp.ndarray,  # (P,)
    mask: jnp.ndarray,  # (P,)
    *,
    clamp_t: float = CLAMP_T,
    background: float = BACKGROUND_DEPTH,
) -> jnp.ndarray:
    """Unnormalized masked clamped-L1 sums per particle, shape (N,)."""
    del background  # sphere_depth uses the module constant

    mask = mask.astype(jnp.float32)

    def one(sph):
        d_h = sphere_depth(rays, sph)  # (P,)
        err = jnp.minimum(jnp.abs(d_h - depth_obs), clamp_t)
        return jnp.sum(err * mask)

    return jax.vmap(one)(spheres.astype(jnp.float32))


def render_score(
    spheres: jnp.ndarray,
    rays: jnp.ndarray,
    depth_obs: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    clamp_t: float = CLAMP_T,
) -> jnp.ndarray:
    """Normalized E_D per particle (mean over bbox pixels), shape (N,)."""
    sums = render_score_sums(spheres, rays, depth_obs, mask, clamp_t=clamp_t)
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return sums / denom


def score_atol(mask) -> float:
    """Absolute tolerance between kernel and oracle normalized scores.

    It allows ONE silhouette-pixel hit flip per particle: at grazing rays
    the sphere discriminant is ~0, and f32 rounding order (broadcast FMAs
    in the kernel vs a matmul in the oracle) can legitimately flip
    hit/no-hit, shifting the normalized score by at most CLAMP_T / |B|."""
    denom = max(float(np.asarray(mask, dtype=np.float32).sum()), 1.0)
    return CLAMP_T / denom + 1e-6
