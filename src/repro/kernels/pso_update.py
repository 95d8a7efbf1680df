"""Pallas TPU kernel: fused PSO swarm update (velocity + position).

The second GPGPU component of the paper's per-frame loop (the first —
population evaluation — is kernels/render_score.py): the Clerc–Kennedy
update

    v' = w v + c1 r1 (pbest - x) + c2 r2 (gbest - x)
    v' = clip(v', -vclip*span, +vclip*span)
    x' = clip(x + v', lo, hi)

is pure elementwise VPU math over the (particles, dims) plane. Fusing it
keeps the whole swarm state in VMEM for one pass instead of ~8 HBM
round-trips of (N, D) intermediates.

Tiling: grid over particle tiles; each step loads (BN, D) blocks of
x/v/pbest/r1/r2 plus the broadcast (D,) rows (gbest, lo, hi). D = 27 is
padded to 32 by ops.py — within a lane-width of the (8, 128) vector
registers at the particle counts PSO uses.

Edge batching: ``pso_update_batched`` grows a leading batch axis so B
clients' swarms update in ONE fused launch — the amortization the fleet
simulator's ``BatchingSlotServer`` models.  The fast path extends the
Pallas grid to (B, N/BN) over (1, BN, D) blocks; since the update is
pure elementwise math with row broadcasts, the *same* kernel body
serves both ranks, so the B = 1 slice is bit-for-bit the unbatched
kernel (golden test in tests/test_batching.py).  A ``path="vmap"``
fallback vmaps the unbatched call for comparison/debugging.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

DEFAULT_BLOCK_N = 8


def _pso_update_kernel(
    x_ref, v_ref, pb_ref, r1_ref, r2_ref,  # (BN, D)
    gb_ref, lo_ref, hi_ref,  # (1, D) broadcast rows
    x_out_ref, v_out_ref,  # (BN, D)
    *,
    inertia: float,
    cognitive: float,
    social: float,
    velocity_clip: float,
):
    x = x_ref[...]
    v = v_ref[...]
    pb = pb_ref[...]
    r1 = r1_ref[...]
    r2 = r2_ref[...]
    gb = gb_ref[...]  # (1, D) broadcasts over particles
    lo = lo_ref[...]
    hi = hi_ref[...]

    vel = (
        inertia * v
        + cognitive * r1 * (pb - x)
        + social * r2 * (gb - x)
    )
    vmax = velocity_clip * (hi - lo)
    vel = jnp.clip(vel, -vmax, vmax)
    pos = jnp.clip(x + vel, lo, hi)
    x_out_ref[...] = pos
    v_out_ref[...] = vel


def pso_update(
    x: jnp.ndarray,  # (N, D) padded: N % block_n == 0
    v: jnp.ndarray,
    pbest: jnp.ndarray,
    gbest: jnp.ndarray,  # (D,)
    r1: jnp.ndarray,
    r2: jnp.ndarray,
    lo: jnp.ndarray,  # (D,)
    hi: jnp.ndarray,
    *,
    inertia: float,
    cognitive: float,
    social: float,
    velocity_clip: float,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
):
    """Returns (new_positions, new_velocities), both (N, D) f32."""
    n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    kernel = functools.partial(
        _pso_update_kernel,
        inertia=inertia,
        cognitive=cognitive,
        social=social,
        velocity_clip=velocity_clip,
    )
    row = lambda a: a.reshape(1, d).astype(jnp.float32)
    grid = (n // block_n,)
    tile = pl.BlockSpec((block_n, d), lambda i: (i, 0))
    brow = pl.BlockSpec((1, d), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile, tile, tile, brow, brow, brow],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
        ],
        interpret=kernels.resolve_interpret(interpret),
    )(
        x.astype(jnp.float32), v.astype(jnp.float32),
        pbest.astype(jnp.float32), r1.astype(jnp.float32),
        r2.astype(jnp.float32), row(gbest), row(lo), row(hi),
    )


def pso_update_batched(
    x: jnp.ndarray,  # (B, N, D) padded: N % block_n == 0
    v: jnp.ndarray,
    pbest: jnp.ndarray,
    gbest: jnp.ndarray,  # (B, D) — one global best per swarm
    r1: jnp.ndarray,
    r2: jnp.ndarray,
    lo: jnp.ndarray,  # (D,) or (B, D) — shared model bounds
    hi: jnp.ndarray,
    *,
    inertia: float,
    cognitive: float,
    social: float,
    velocity_clip: float,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
    path: str = "grid",
):
    """Fused multi-swarm update: (new_positions, new_velocities), (B, N, D).

    ``path="grid"`` runs ONE Pallas launch with grid (B, N/block_n) —
    the edge-batching fast path; ``path="vmap"`` vmaps the unbatched
    kernel (one launch per swarm under interpret mode) as the
    reshape-free reference implementation.
    """
    b, n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    brow_arr = lambda a: jnp.broadcast_to(
        a.astype(jnp.float32), (b, d)
    ).reshape(b, 1, d)
    if path == "vmap":
        fn = functools.partial(
            pso_update,
            inertia=inertia,
            cognitive=cognitive,
            social=social,
            velocity_clip=velocity_clip,
            block_n=block_n,
            interpret=interpret,
        )
        lo_b = jnp.broadcast_to(lo.astype(jnp.float32), (b, d))
        hi_b = jnp.broadcast_to(hi.astype(jnp.float32), (b, d))
        return jax.vmap(fn)(x, v, pbest, gbest, r1, r2, lo_b, hi_b)
    if path != "grid":
        raise ValueError(f"unknown path {path!r}")
    kernel = functools.partial(
        _pso_update_kernel,
        inertia=inertia,
        cognitive=cognitive,
        social=social,
        velocity_clip=velocity_clip,
    )
    grid = (b, n // block_n)
    # the kernel body is rank-agnostic elementwise math, so the batched
    # (1, BN, D) tiles reuse it unchanged — B=1 is the unbatched kernel
    tile = pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0))
    brow = pl.BlockSpec((1, 1, d), lambda bi, i: (bi, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile, tile, tile, brow, brow, brow],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        ],
        interpret=kernels.resolve_interpret(interpret),
    )(
        x.astype(jnp.float32), v.astype(jnp.float32),
        pbest.astype(jnp.float32), r1.astype(jnp.float32),
        r2.astype(jnp.float32),
        gbest.astype(jnp.float32).reshape(b, 1, d),
        brow_arr(lo), brow_arr(hi),
    )
