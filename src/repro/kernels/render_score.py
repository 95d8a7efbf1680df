"""Pallas TPU kernel: fused particle-population render + E_D scoring.

This is the GPGPU hot spot the paper offloads: evaluating the PSO
population means rendering every particle's hand hypothesis to a depth
map and scoring it against the observation (Eq. 2). On CUDA the original
tracker rasterizes primitive meshes; on TPU we compute analytic sphere
depth per (particle, pixel, primitive) — dense FMA math with two
reductions (min over primitives, masked-sum over pixels), ideal for the
VPU/MXU with no scatter or z-buffer contention (DESIGN.md §2).

Tiling: grid = (B clients, N/BN particle tiles, P/BP pixel tiles). Each
step loads one particle tile's packed spheres (BN, S, 4), one pixel
tile's rays (3, BP), observed depth and bbox mask (1, BP), renders the
depth tile via a min over S spheres, and accumulates the masked
clamped-L1 partial sums into the output block (BN, 1, 1) across the
pixel-tile grid axis (j == 0 initializes, j > 0 accumulates — the
canonical Pallas reduction pattern).

Layout: pixels sit on the 128-wide lane axis and spheres on sublanes,
so the (BN, S, BP) intermediates are lane-dense and the min over
spheres is a sublane reduction. Every block's trailing two dims are
either the whole array dim or a multiple of (8, 128), which is what
Mosaic requires. The ray-centre products <ray, centre> are three
broadcast FMAs on the VPU rather than an MXU matmul: a contraction of
length 3 would waste the MXU, and a default-precision float32 matmul
runs on a TPU as one bfloat16 pass, too coarse for the discriminant
(see ``objective.sphere_depth``).

Edge batching: the client axis lets a whole gather-window's worth of
client swarms evaluate in one fused launch
(``render_score_sums_batched``, the ``BatchingSlotServer`` event the
fleet simulator prices sublinearly). ``render_score_sums`` is the same
launch at B = 1, so the single-client and batched paths share one
kernel.

VMEM budget at the default BN=8, BP=512, S=48, f32: the sphere tile
pads its 4-wide lane dim to 128 (8*48*128*4 B = 192 KiB), and each
(BN, S, BP) intermediate is 8*48*512*4 B = 768 KiB — a few MiB in all,
well inside the 16 MiB scoped VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.core.camera import BACKGROUND_DEPTH
from repro.core.objective import CLAMP_T

DEFAULT_BLOCK_N = 8
DEFAULT_BLOCK_P = 512


def _score_tile(spheres, rays, d_o, msk, *, clamp_t, background):
    """Masked clamped-L1 partial sums of one (particle, pixel) tile.

    spheres (BN, S, 4), rays (3, BP), d_o and msk (1, BP) -> (BN, 1, 1).
    """
    cx = spheres[:, :, 0:1]  # (BN, S, 1): spheres on sublanes
    cy = spheres[:, :, 1:2]
    cz = spheres[:, :, 2:3]
    r = spheres[:, :, 3:4]
    rx = rays[0:1][None]  # (1, 1, BP): pixels on lanes
    ry = rays[1:2][None]
    rz = rays[2:3][None]

    d2 = rx * rx + ry * ry + rz * rz  # (1, 1, BP)
    dc = cx * rx + cy * ry + cz * rz  # (BN, S, BP) = <ray_p, centre_{n,s}>
    c2r2 = cx * cx + cy * cy + cz * cz - r * r  # (BN, S, 1)
    disc = dc * dc - d2 * c2r2
    t = (dc - jnp.sqrt(jnp.maximum(disc, 0.0))) / d2
    hit = (disc >= 0.0) & (t > 1e-4)
    t = jnp.where(hit, t, background)
    d_h = jnp.min(t, axis=1, keepdims=True)  # (BN, 1, BP)

    err = jnp.minimum(jnp.abs(d_h - d_o[None]), clamp_t)
    return jnp.sum(err * msk[None], axis=-1, keepdims=True)  # (BN, 1, 1)


def _render_score_kernel(
    spheres_ref,  # (1, BN, S, 4) f32 — one client's particle tile
    rays_ref,  # (1, 3, BP) f32
    depth_ref,  # (1, 1, BP) f32
    mask_ref,  # (1, 1, BP) f32 (0/1)
    out_ref,  # (1, BN, 1, 1) f32
    *,
    clamp_t: float,
    background: float,
):
    j = pl.program_id(2)
    partial = _score_tile(
        spheres_ref[0],
        rays_ref[0],
        depth_ref[0],
        mask_ref[0],
        clamp_t=clamp_t,
        background=background,
    )

    @pl.when(j == 0)
    def _init():
        out_ref[0] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[0] = out_ref[0] + partial


def render_score_sums(
    spheres: jnp.ndarray,  # (N, S, 4)
    rays: jnp.ndarray,  # (P, 3)
    depth_obs: jnp.ndarray,  # (P,)
    mask: jnp.ndarray,  # (P,) float32 or bool
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_p: int = DEFAULT_BLOCK_P,
    clamp_t: float = CLAMP_T,
    background: float = BACKGROUND_DEPTH,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Unnormalized masked score sums per particle, shape (N,).

    Shapes must already be padded: N % block_n == 0, P % block_p == 0
    (``ops.render_score`` handles padding/normalization). ``interpret``
    defaults to the platform's mode (``kernels.resolve_interpret``).
    """
    return render_score_sums_batched(
        spheres[None], rays[None], depth_obs[None], mask[None],
        block_n=block_n, block_p=block_p, clamp_t=clamp_t,
        background=background, interpret=interpret,
    )[0]


def render_score_sums_batched(
    spheres: jnp.ndarray,  # (B, N, S, 4) — one swarm per client
    rays: jnp.ndarray,  # (B, P, 3)
    depth_obs: jnp.ndarray,  # (B, P)
    mask: jnp.ndarray,  # (B, P) float32 or bool
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_p: int = DEFAULT_BLOCK_P,
    clamp_t: float = CLAMP_T,
    background: float = BACKGROUND_DEPTH,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused multi-client population evaluation: score sums, (B, N).

    One Pallas launch with grid (B, N/block_n, P/block_p) — B clients'
    swarms evaluate together, which is the edge-batching amortization
    the fleet simulator's ``BatchServiceModel`` prices.  The unbatched
    ``render_score_sums`` is this launch at B = 1, and the grid iterates
    the pixel axis innermost, so each (client, particle-tile) accumulates
    partial sums in exactly the single-client order.
    """
    bsz, n, s, _ = spheres.shape
    p = rays.shape[1]
    assert n % block_n == 0, (n, block_n)
    assert p % block_p == 0, (p, block_p)
    # Lane-dense pixel planes: rays (B, 3, P), depth and mask (B, 1, P).
    rays_t = jnp.swapaxes(rays.astype(jnp.float32), 1, 2)
    depth = depth_obs.astype(jnp.float32).reshape(bsz, 1, p)
    mask = mask.astype(jnp.float32).reshape(bsz, 1, p)

    kernel = functools.partial(
        _render_score_kernel, clamp_t=clamp_t, background=background
    )
    plane = pl.BlockSpec((1, 1, block_p), lambda b, i, j: (b, 0, j))
    sums = pl.pallas_call(
        kernel,
        grid=(bsz, n // block_n, p // block_p),
        in_specs=[
            pl.BlockSpec((1, block_n, s, 4), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, 3, block_p), lambda b, i, j: (b, 0, j)),
            plane,
            plane,
        ],
        out_specs=pl.BlockSpec((1, block_n, 1, 1), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n, 1, 1), jnp.float32),
        interpret=kernels.resolve_interpret(interpret),
    )(spheres.astype(jnp.float32), rays_t, depth, mask)
    return sums.reshape(bsz, n)
