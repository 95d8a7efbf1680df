"""Pallas kernels vs their jnp references. The kernels run in the
platform's mode (``repro.kernels.default_interpret``): on the CPU that is
the Pallas interpreter, so those rows are correctness-grade timings and
carry ``_pallas_interpret`` in their names."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import handmodel, objective
from repro import kernels
from repro.core.camera import Camera
from repro.kernels import ops, ref

try:
    from benchmarks.common import time_fn
except ModuleNotFoundError:  # run as a script: sys.path[0] is benchmarks/
    from common import time_fn


def bench() -> list:
    cam = Camera(width=64, height=64, fx=60.0, fy=60.0, cx=31.5, cy=31.5)
    n = 16
    hs = jnp.stack([handmodel.default_pose(0.4).at[0].add(0.01 * i) for i in range(n)])
    spheres = jax.vmap(handmodel.pack_spheres)(hs)
    rays = cam.rays_flat()
    d_o = objective.render_depth(hs[0], cam).reshape(-1)
    mask = d_o < 5.0

    interp = kernels.default_interpret()
    mode = "pallas_interpret" if interp else "pallas"
    rows = []
    work = n * rays.shape[0] * handmodel.NUM_SPHERES
    t_ref = time_fn(
        jax.jit(lambda s: ref.render_score(s, rays, d_o, mask)), spheres
    )
    rows.append((
        "kernel/render_score_ref",
        t_ref * 1e6,
        f"particle_px_sphere_per_s={work / t_ref:.2e}",
    ))
    t_k = time_fn(
        jax.jit(lambda s: ops.render_score(s, rays, d_o, mask)), spheres
    )
    rows.append((
        f"kernel/render_score_{mode}",
        t_k * 1e6,
        f"particle_px_sphere_per_s={work / t_k:.2e};interpret={interp}",
    ))

    # second kernel: fused swarm update
    from repro.kernels import pso_ref, pso_update as kmod

    np_, d = 32, 32
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    lo, hi = -jnp.ones((d,)), jnp.ones((d,))
    x = jax.random.uniform(ks[0], (np_, d), minval=-1, maxval=1)
    v = jax.random.normal(ks[1], (np_, d)) * 0.1
    pb = jax.random.uniform(ks[2], (np_, d), minval=-1, maxval=1)
    gb = pb[0]
    r1 = jax.random.uniform(ks[3], (np_, d))
    r2 = jax.random.uniform(ks[4], (np_, d))
    consts = dict(inertia=0.7298, cognitive=1.49618, social=1.49618,
                  velocity_clip=0.5)
    t_upd = time_fn(
        jax.jit(lambda *a: kmod.pso_update(*a, **consts)),
        x, v, pb, gb, r1, r2, lo, hi,
    )
    rows.append((
        f"kernel/pso_update_{mode}",
        t_upd * 1e6,
        f"particle_dims_per_s={np_ * d / t_upd:.2e};interpret={interp}",
    ))

    # edge batching: B clients' swarms in ONE fused launch vs B launches
    b = 4
    tile = lambda a: jnp.broadcast_to(a, (b,) + a.shape)
    t_fused = time_fn(
        jax.jit(lambda *a: kmod.pso_update_batched(*a, **consts)),
        tile(x), tile(v), tile(pb), tile(gb), tile(r1), tile(r2), lo, hi,
    )
    rows.append((
        f"kernel/pso_update_batched_b{b}_{mode}",
        t_fused * 1e6,
        f"particle_dims_per_s={b * np_ * d / t_fused:.2e};"
        f"per_client_vs_solo={t_fused / (b * t_upd):.2f};interpret={interp}",
    ))

    # payload codec: delta-encode + quantize-pack one depth plane (the
    # uplink's per-frame encode work) and its exact wire footprint
    from repro.codec import kernels as ckern, ref as cref

    h, w = 240, 320
    prev = objective.render_depth(hs[0], Camera()).reshape(128, 128)
    frame = jnp.pad(prev + 0.001, ((0, h - 128), (0, w - 128)))
    prev = jnp.pad(prev, ((0, h - 128), (0, w - 128)))
    raw_bytes = frame.size * 4
    t_delta = time_fn(
        jax.jit(lambda f, r: ckern.delta_encode(f, r)[0]), frame, prev
    )
    _, mask = ckern.delta_encode(frame, prev)
    # the f32 XOR path ships 32-bit residuals (lossless); the quantized
    # wire width is priced by the model/ref.encode_frame, not here
    enc_bytes = cref.encoded_nbytes_exact(mask, bits=32, header_nbytes=64)
    rows.append((
        f"kernel/codec_delta_encode_{mode}",
        t_delta * 1e6,
        f"bytes_per_s={raw_bytes / t_delta:.2e};"
        f"wire_ratio={enc_bytes / raw_bytes:.3f};interpret={interp}",
    ))
    t_q = time_fn(
        jax.jit(lambda f: ckern.quantize_pack(f, 0.0, 2.0, bits=8)), frame
    )
    rows.append((
        f"kernel/codec_quantize_pack_{mode}",
        t_q * 1e6,
        f"bytes_per_s={raw_bytes / t_q:.2e};pack_ratio=0.25;interpret={interp}",
    ))
    return rows


def main() -> None:
    """Standalone entry: CSV to stdout + BENCH_kernel.json artifact.

    The JSON mirrors the CSV rows (name, us_per_call, the derived
    throughput string) so bench runs on two checkouts diff as data."""
    try:
        from benchmarks.common import emit, write_bench_json
    except ModuleNotFoundError:
        from common import emit, write_bench_json

    rows = bench()
    print("name,us_per_call,derived")
    emit(rows)
    write_bench_json(
        "kernel",
        {
            "rows": [
                {"name": n, "us_per_call": round(us, 2), "derived": d}
                for n, us, d in rows
            ]
        },
    )


if __name__ == "__main__":
    main()
