"""Pallas TPU kernels: delta-frame RGBD payload codec.

The paper's bottom line is that the offloaded tracker is *payload
bound* — the RGBD frame crossing the network dominates the loop, and
"compressing the information flow" is its named future work.  These
kernels implement that compression on the accelerator so encode rides
the same device the tracker already uses:

* :func:`delta_encode` / :func:`delta_decode` — keyframe + per-tile
  temporal delta with change masks.  The grid tiles the frame plane;
  each program compares its (block_h, block_w) tile against the
  receiver's reference frame, flags it changed when any pixel moved
  more than ``threshold``, and emits the XOR of the f32 bit patterns
  for changed tiles (integer XOR inverts exactly, so changed tiles
  reconstruct bit-for-bit; ``threshold == 0`` makes the whole frame
  lossless to the bit).
* :func:`quantize_pack` / :func:`unpack_dequantize` — uniform depth
  quantization to ``bits``-wide codes (roundtrip error <= half a
  quantization step, see ``ref.quant_step``) with ``32 // bits``
  adjacent codes bit-packed per int32 word along the lane axis.

Batched variants grow a leading client axis exactly like PR 3's fused
tracker kernels: the Pallas grid extends to (B, tiles...) over
(1, block_h, block_w) tiles, and since every kernel body is
rank-agnostic tile math, the B = 1 slice is bit-for-bit the unbatched
kernel (golden test in tests/test_codec.py).  A ``path="vmap"``
fallback vmaps the unbatched call for comparison/debugging.

``codec.ref`` holds the pure-jnp oracles; wrappers here handle padding
to tile multiples and slicing back, mirroring ``kernels/ops.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.codec.ref import (
    DEFAULT_BLOCK_H,
    DEFAULT_BLOCK_W,
    _check_bits,
    quant_step,
)


def _pad_plane(x: jnp.ndarray, block_h: int, block_w: int) -> jnp.ndarray:
    """Zero-pad the trailing two axes up to tile multiples."""
    h, w = x.shape[-2:]
    pad_h = -h % block_h
    pad_w = -w % block_w
    if not pad_h and not pad_w:
        return x
    widths = [(0, 0)] * (x.ndim - 2) + [(0, pad_h), (0, pad_w)]
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# temporal delta
# ---------------------------------------------------------------------------


def _delta_encode_kernel(f_ref, r_ref, d_out, m_out, *, threshold: float):
    """Rank-agnostic tile body: serves the (BH, BW) unbatched tiles and
    the (1, BH, BW) batched tiles unchanged, so B=1 is bit-for-bit."""
    f = f_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    changed = (jnp.max(jnp.abs(f - r)) > threshold).astype(jnp.int32)
    xor = jax.lax.bitcast_convert_type(
        f, jnp.int32
    ) ^ jax.lax.bitcast_convert_type(r, jnp.int32)
    d_out[...] = xor * changed
    m_out[...] = jnp.full(m_out.shape, changed.astype(jnp.float32))


def _delta_decode_kernel(d_ref, r_ref, out_ref):
    bits = jax.lax.bitcast_convert_type(
        r_ref[...].astype(jnp.float32), jnp.int32
    ) ^ d_ref[...]
    out_ref[...] = jax.lax.bitcast_convert_type(bits, jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("threshold", "block_h", "block_w", "interpret")
)
def delta_encode(
    frame: jnp.ndarray,  # (H, W) f32
    ref: jnp.ndarray,  # (H, W) f32
    *,
    threshold: float = 0.0,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(delta_bits (H, W) i32, mask f32)`` — matches
    ``ref.delta_encode`` on tile-aligned shapes.  Unaligned frames are
    zero-padded to tile multiples: the delta plane is cropped back to
    (H, W), while the mask covers the *padded* tile grid
    (ceil(H/bh), ceil(W/bw)) — pad-only tiles are zero in both planes
    and therefore never marked changed."""
    h, w = frame.shape
    f = _pad_plane(frame.astype(jnp.float32), block_h, block_w)
    r = _pad_plane(ref.astype(jnp.float32), block_h, block_w)
    hp, wp = f.shape
    grid = (hp // block_h, wp // block_w)
    tile = pl.BlockSpec((block_h, block_w), lambda i, j: (i, j))
    cell = pl.BlockSpec((1, 1), lambda i, j: (i, j))
    delta, mask = pl.pallas_call(
        functools.partial(_delta_encode_kernel, threshold=threshold),
        grid=grid,
        in_specs=[tile, tile],
        out_specs=[tile, cell],
        out_shape=[
            jax.ShapeDtypeStruct((hp, wp), jnp.int32),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        interpret=kernels.resolve_interpret(interpret),
    )(f, r)
    return delta[:h, :w], mask


@functools.partial(
    jax.jit, static_argnames=("block_h", "block_w", "interpret")
)
def delta_decode(
    delta_bits: jnp.ndarray,  # (H, W) i32
    ref: jnp.ndarray,  # (H, W) f32
    *,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Reconstruct the frame: bit-exact on changed tiles, reference
    passthrough (error <= encode threshold) on unchanged ones."""
    h, w = delta_bits.shape
    d = _pad_plane(delta_bits, block_h, block_w)
    r = _pad_plane(ref.astype(jnp.float32), block_h, block_w)
    hp, wp = d.shape
    tile = pl.BlockSpec((block_h, block_w), lambda i, j: (i, j))
    out = pl.pallas_call(
        _delta_decode_kernel,
        grid=(hp // block_h, wp // block_w),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((hp, wp), jnp.float32),
        interpret=kernels.resolve_interpret(interpret),
    )(d, r)
    return out[:h, :w]


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "block_h", "block_w", "interpret", "path"),
)
def delta_encode_batched(
    frames: jnp.ndarray,  # (B, H, W) f32
    refs: jnp.ndarray,  # (B, H, W) f32
    *,
    threshold: float = 0.0,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
    path: str = "grid",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """B clients' frames delta-encoded in ONE fused launch — the edge
    decodes/encodes batched exactly like it scores batched swarms.
    ``path="grid"`` extends the Pallas grid to (B, tiles_h, tiles_w);
    the tile body is shared with the unbatched kernel, so the B=1 slice
    is bit-for-bit ``delta_encode`` (mask over the padded tile grid,
    like the unbatched wrapper)."""
    if path == "vmap":
        fn = functools.partial(
            delta_encode,
            threshold=threshold,
            block_h=block_h,
            block_w=block_w,
            interpret=interpret,
        )
        return jax.vmap(fn)(frames, refs)
    if path != "grid":
        raise ValueError(f"unknown path {path!r}")
    b, h, w = frames.shape
    f = _pad_plane(frames.astype(jnp.float32), block_h, block_w)
    r = _pad_plane(refs.astype(jnp.float32), block_h, block_w)
    hp, wp = f.shape[1:]
    grid = (b, hp // block_h, wp // block_w)
    tile = pl.BlockSpec((1, block_h, block_w), lambda bi, i, j: (bi, i, j))
    cell = pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, i, j))
    delta, mask = pl.pallas_call(
        functools.partial(_delta_encode_kernel, threshold=threshold),
        grid=grid,
        in_specs=[tile, tile],
        out_specs=[tile, cell],
        out_shape=[
            jax.ShapeDtypeStruct((b, hp, wp), jnp.int32),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        interpret=kernels.resolve_interpret(interpret),
    )(f, r)
    return delta[:, :h, :w], mask


# ---------------------------------------------------------------------------
# entropy stage: per-tile significant-bit widths
# ---------------------------------------------------------------------------


def _sig_width_kernel(d_ref, w_out):
    """Significant-bit width of the tile's max |residual| word, read as
    uint32 — the side information ``ref.entropy_encode_words`` writes
    per tile.  ``(m >= 2**k)`` summed over k in [0, 32) counts exactly
    ``m.bit_length()`` without a loop-carried dependency (pure VPU
    compare + reduce, no integer log)."""
    words = d_ref[...].astype(jnp.uint32)
    m = jnp.max(words)
    thresholds = jnp.uint32(2) ** jnp.arange(32, dtype=jnp.uint32)
    width = jnp.sum((m >= thresholds).astype(jnp.int32))
    w_out[...] = jnp.full(w_out.shape, width, dtype=jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("block_h", "block_w", "interpret")
)
def significant_bit_widths(
    delta_bits: jnp.ndarray,  # (H, W) i32 XOR residual plane
    *,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Per-tile significant-bit widths of a residual plane:
    ``(ceil(H/bh), ceil(W/bw)) i32`` in [0, 32].  This is the entropy
    stage's device-side half — the coded size of each tile is
    ``ceil(tile_samples * width / 8) + 1`` bytes, so the host can price
    (and the byte packer emit) the stream without touching the full
    plane again.  Pad tiles are all-zero and report width 0."""
    d = _pad_plane(delta_bits.astype(jnp.int32), block_h, block_w)
    hp, wp = d.shape
    grid = (hp // block_h, wp // block_w)
    tile = pl.BlockSpec((block_h, block_w), lambda i, j: (i, j))
    cell = pl.BlockSpec((1, 1), lambda i, j: (i, j))
    return pl.pallas_call(
        _sig_width_kernel,
        grid=grid,
        in_specs=[tile],
        out_specs=cell,
        out_shape=jax.ShapeDtypeStruct(grid, jnp.int32),
        interpret=kernels.resolve_interpret(interpret),
    )(d)


@functools.partial(
    jax.jit, static_argnames=("block_h", "block_w", "interpret", "path")
)
def significant_bit_widths_batched(
    deltas: jnp.ndarray,  # (B, H, W) i32
    *,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
    path: str = "grid",
) -> jnp.ndarray:
    """B clients' residual planes width-scanned in one fused launch;
    the B=1 slice is bit-for-bit :func:`significant_bit_widths`."""
    if path == "vmap":
        fn = functools.partial(
            significant_bit_widths,
            block_h=block_h,
            block_w=block_w,
            interpret=interpret,
        )
        return jax.vmap(fn)(deltas)
    if path != "grid":
        raise ValueError(f"unknown path {path!r}")
    b = deltas.shape[0]
    d = _pad_plane(deltas.astype(jnp.int32), block_h, block_w)
    hp, wp = d.shape[1:]
    grid = (b, hp // block_h, wp // block_w)
    tile = pl.BlockSpec((1, block_h, block_w), lambda bi, i, j: (bi, i, j))
    cell = pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, i, j))
    return pl.pallas_call(
        _sig_width_kernel,
        grid=grid,
        in_specs=[tile],
        out_specs=cell,
        out_shape=jax.ShapeDtypeStruct(grid, jnp.int32),
        interpret=kernels.resolve_interpret(interpret),
    )(d)


# ---------------------------------------------------------------------------
# quantize + pack
# ---------------------------------------------------------------------------


def _quantize_pack_kernel(
    x_ref, out_ref, *, lo: float, hi: float, bits: int, step: float
):
    ratio = 32 // bits
    x = jnp.clip(x_ref[...].astype(jnp.float32), lo, hi)
    codes = jnp.clip(
        jnp.round((x - lo) / step).astype(jnp.int32), 0, (1 << bits) - 1
    )
    shifts = jnp.arange(ratio, dtype=jnp.int32) * bits
    grouped = codes.reshape(
        codes.shape[:-1] + (codes.shape[-1] // ratio, ratio)
    )
    out_ref[...] = jnp.sum(grouped << shifts, axis=-1).astype(jnp.int32)


def _unpack_dequantize_kernel(
    w_ref, out_ref, *, lo: float, bits: int, step: float
):
    ratio = 32 // bits
    words = w_ref[...]
    shifts = jnp.arange(ratio, dtype=jnp.int32) * bits
    lanes = (words[..., None] >> shifts) & ((1 << bits) - 1)
    codes = lanes.reshape(words.shape[:-1] + (words.shape[-1] * ratio,))
    out_ref[...] = lo + codes.astype(jnp.float32) * step


@functools.partial(
    jax.jit,
    static_argnames=("lo", "hi", "bits", "block_h", "block_w", "interpret"),
)
def quantize_pack(
    depth: jnp.ndarray,  # (H, W) f32
    lo: float,
    hi: float,
    *,
    bits: int = 8,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Quantize depth to ``bits``-wide codes and bit-pack the lane axis
    into int32 words: returns ``(H, W * bits / 32) i32``.  Requires
    ``W`` divisible by ``32 // bits`` (depth planes are)."""
    ratio = _check_bits(bits)
    h, w = depth.shape
    if w % ratio:
        raise ValueError(f"width {w} not divisible by pack ratio {ratio}")
    x = _pad_plane(depth.astype(jnp.float32), block_h, block_w)
    hp, wp = x.shape
    step = quant_step(lo, hi, bits)
    tile = pl.BlockSpec((block_h, block_w), lambda i, j: (i, j))
    out_tile = pl.BlockSpec((block_h, block_w // ratio), lambda i, j: (i, j))
    words = pl.pallas_call(
        functools.partial(
            _quantize_pack_kernel, lo=lo, hi=hi, bits=bits, step=step
        ),
        grid=(hp // block_h, wp // block_w),
        in_specs=[tile],
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((hp, wp // ratio), jnp.int32),
        interpret=kernels.resolve_interpret(interpret),
    )(x)
    return words[:h, : w // ratio]


@functools.partial(
    jax.jit,
    static_argnames=("lo", "hi", "bits", "block_h", "block_w", "interpret"),
)
def unpack_dequantize(
    words: jnp.ndarray,  # (H, W * bits / 32) i32
    lo: float,
    hi: float,
    *,
    bits: int = 8,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Inverse of :func:`quantize_pack`: ``(H, W) f32`` with per-pixel
    error <= ``ref.quant_step(lo, hi, bits) / 2`` inside [lo, hi]."""
    ratio = _check_bits(bits)
    h, wpk = words.shape
    step = quant_step(lo, hi, bits)
    pack_w = max(block_w // ratio, 1)
    x = _pad_plane(words, block_h, pack_w)
    hp, wpp = x.shape
    in_tile = pl.BlockSpec((block_h, pack_w), lambda i, j: (i, j))
    out_tile = pl.BlockSpec((block_h, pack_w * ratio), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(
            _unpack_dequantize_kernel, lo=lo, bits=bits, step=step
        ),
        grid=(hp // block_h, wpp // pack_w),
        in_specs=[in_tile],
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((hp, wpp * ratio), jnp.float32),
        interpret=kernels.resolve_interpret(interpret),
    )(x)
    return out[:h, : wpk * ratio]


@functools.partial(
    jax.jit,
    static_argnames=(
        "lo", "hi", "bits", "block_h", "block_w", "interpret", "path",
    ),
)
def quantize_pack_batched(
    depths: jnp.ndarray,  # (B, H, W) f32
    lo: float,
    hi: float,
    *,
    bits: int = 8,
    block_h: int = DEFAULT_BLOCK_H,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
    path: str = "grid",
) -> jnp.ndarray:
    """Fused multi-client quantize+pack: ``(B, H, W * bits / 32) i32``;
    the B=1 slice is bit-for-bit :func:`quantize_pack`."""
    if path == "vmap":
        fn = functools.partial(
            quantize_pack,
            bits=bits,
            block_h=block_h,
            block_w=block_w,
            interpret=interpret,
        )
        return jax.vmap(lambda d: fn(d, lo, hi))(depths)
    if path != "grid":
        raise ValueError(f"unknown path {path!r}")
    ratio = _check_bits(bits)
    b, h, w = depths.shape
    if w % ratio:
        raise ValueError(f"width {w} not divisible by pack ratio {ratio}")
    x = _pad_plane(depths.astype(jnp.float32), block_h, block_w)
    hp, wp = x.shape[1:]
    step = quant_step(lo, hi, bits)
    tile = pl.BlockSpec((1, block_h, block_w), lambda bi, i, j: (bi, i, j))
    out_tile = pl.BlockSpec(
        (1, block_h, block_w // ratio), lambda bi, i, j: (bi, i, j)
    )
    words = pl.pallas_call(
        functools.partial(
            _quantize_pack_kernel, lo=lo, hi=hi, bits=bits, step=step
        ),
        grid=(b, hp // block_h, wp // block_w),
        in_specs=[tile],
        out_specs=out_tile,
        out_shape=jax.ShapeDtypeStruct((b, hp, wp // ratio), jnp.int32),
        interpret=kernels.resolve_interpret(interpret),
    )(x)
    return words[:, :h, : w // ratio]
