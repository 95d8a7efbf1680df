"""Jit'd public wrapper around the render_score Pallas kernel.

Handles shape padding (particles to block_n, pixels to block_p), mask
normalization, and passes ``interpret`` through (``None`` follows the
platform, see ``repro.kernels.resolve_interpret``). This is the drop-in
replacement for ``objective.batched_objective``'s vmapped evaluation —
the tracker selects it with ``TrackerConfig(use_kernel=True)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.objective import CLAMP_T
from repro.kernels import render_score as _kernel


def _pad_to(x: jnp.ndarray, size: int, axis: int, value=0.0) -> jnp.ndarray:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_render_inputs(spheres, rays, depth_obs, mask, block_n, block_p):
    """Pad particles/pixels to block multiples, rank-agnostically: the
    particle and pixel axes are located from the trailing dims, so the
    unbatched (N, …)/(P, …) and batched (B, N, …)/(B, P, …) wrappers
    share one copy of the padding rules."""
    n_axis = spheres.ndim - 3  # (…, N, S, 4)
    p_axis = rays.ndim - 2  # (…, P, 3)
    n_pad = -(-spheres.shape[n_axis] // block_n) * block_n
    p_pad = -(-rays.shape[p_axis] // block_p) * block_p

    spheres_p = _pad_to(spheres, n_pad, axis=n_axis)
    # Padding rays must be well-formed directions (d_z = 1) so the kernel
    # never divides by |d|^2 = 0; their mask is 0 so they score nothing.
    if p_pad != rays.shape[p_axis]:
        pad_shape = rays.shape[:p_axis] + (p_pad - rays.shape[p_axis], 3)
        pad_rays = jnp.zeros(pad_shape, dtype=rays.dtype).at[..., 2].set(1.0)
        rays_p = jnp.concatenate([rays, pad_rays], axis=p_axis)
    else:
        rays_p = rays
    depth_p = _pad_to(depth_obs, p_pad, axis=p_axis)
    mask_p = _pad_to(mask.astype(jnp.float32), p_pad, axis=p_axis)
    return spheres_p, rays_p, depth_p, mask_p


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_p", "clamp_t", "interpret"),
)
def render_score(
    spheres: jnp.ndarray,  # (N, S, 4)
    rays: jnp.ndarray,  # (P, 3)
    depth_obs: jnp.ndarray,  # (P,)
    mask: jnp.ndarray,  # (P,)
    *,
    block_n: int = _kernel.DEFAULT_BLOCK_N,
    block_p: int = _kernel.DEFAULT_BLOCK_P,
    clamp_t: float = CLAMP_T,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Normalized E_D per particle, shape (N,). Matches ref.render_score.

    The single-client case of ``render_score_batched`` (B = 1)."""
    return render_score_batched(
        spheres[None], rays[None], depth_obs[None], mask[None],
        block_n=block_n, block_p=block_p, clamp_t=clamp_t,
        interpret=interpret,
    )[0]


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_p", "clamp_t", "interpret"),
)
def render_score_batched(
    spheres: jnp.ndarray,  # (B, N, S, 4)
    rays: jnp.ndarray,  # (B, P, 3)
    depth_obs: jnp.ndarray,  # (B, P)
    mask: jnp.ndarray,  # (B, P)
    *,
    block_n: int = _kernel.DEFAULT_BLOCK_N,
    block_p: int = _kernel.DEFAULT_BLOCK_P,
    clamp_t: float = CLAMP_T,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Normalized E_D per (client, particle), shape (B, N) — B clients'
    populations scored in ONE fused kernel launch (edge batching).

    Per-client normalization: each row divides by its own bbox pixel
    count, so every slice matches ``render_score`` on that client alone.
    """
    n = spheres.shape[1]
    spheres_p, rays_p, depth_p, mask_p = _pad_render_inputs(
        spheres, rays, depth_obs, mask, block_n, block_p
    )
    sums = _kernel.render_score_sums_batched(
        spheres_p,
        rays_p,
        depth_p,
        mask_p,
        block_n=block_n,
        block_p=block_p,
        clamp_t=clamp_t,
        interpret=interpret,
    )[:, :n]
    denom = jnp.maximum(
        jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True), 1.0
    )
    return sums / denom
