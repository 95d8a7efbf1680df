"""Smoke run of the served tracker path on one TPU chip.

    python3 chip_smoke.py

Tracks a 30-frame rendered sequence at the paper's full width
(``hardware.PAPER_TRACKER_CFG``: 128x128 working camera, 64 particles x
30 generations, 48 spheres per hypothesis) through ``tracker.Tracker``,
once with the default jnp objective and once with the Pallas
``render_score`` kernel (``use_kernel=True``), and checks:

* both paths: no NaN, and mean position error against the ground truth
  under 3 cm (the bar of tests/test_tracker.py);
* the kernel path's compiled step holds a Mosaic kernel
  (``tpu_custom_call``), i.e. nothing fell back to the interpreter;
* for one paper-width population, the kernel's scores match
  ``kernels.ref.render_score`` at highest matmul precision within
  ``ref.score_atol`` (the tolerance of tests/test_kernels.py);
* the jnp depth render hits and misses the same pixels as one at highest
  matmul precision.

Everything runs in this one process. Frame times are smoke timings (wall
seconds around one ``Tracker.step``, after ``block_until_ready``), not
benchmark numbers. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A failed check raises, so the script exits non-zero without that line;
it also exits non-zero before any work when JAX's device is not a TPU.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import handmodel, objective, tracker  # noqa: E402
from repro.core.camera import BACKGROUND_DEPTH  # noqa: E402
from repro.data import rgbd  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.sim import hardware  # noqa: E402

NUM_FRAMES = 30
MAX_MEAN_POS_ERR = 0.03  # meters


def log(msg: str) -> None:
    print(msg, flush=True)


def track(cfg: tracker.TrackerConfig, frames, truth) -> dict:
    """Track frames[1:] from truth[0] through ``Tracker.step``."""
    trk = tracker.Tracker(cfg, h0=truth[0], seed=0)
    t0 = time.perf_counter()
    # the same jitted step Tracker.step calls: its first step reuses this
    compiled = trk._step.lower(trk.key, trk.h, frames[1]).compile()
    compile_s = time.perf_counter() - t0

    frame_s, pos_err = [], []
    for i in range(1, frames.shape[0]):
        t0 = time.perf_counter()
        h, score = trk.step(frames[i])
        h.block_until_ready()
        frame_s.append(time.perf_counter() - t0)
        if not (np.isfinite(score) and bool(jnp.all(jnp.isfinite(h)))):
            raise FloatingPointError(f"frame {i}: non-finite pose or score")
        pos_err.append(float(jnp.linalg.norm(h[:3] - truth[i, :3])))
    return {
        "compile_s": compile_s,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "first_frame_s": frame_s[0],
        "median_frame_s": statistics.median(frame_s[1:]),
        "mean_pos_err_m": float(np.mean(pos_err)),
        "max_pos_err_m": float(np.max(pos_err)),
    }


def kernel_vs_reference(cfg: tracker.TrackerConfig, frame, h) -> dict:
    """Kernel scores of one tracker-drawn population vs the jnp oracle."""
    lo = handmodel.parameter_lower_bounds(h, cfg.pos_range, cfg.quat_range)
    hi = handmodel.parameter_upper_bounds(h, cfg.pos_range, cfg.quat_range)
    u = jax.random.uniform(
        jax.random.PRNGKey(1), (cfg.pso.num_particles, handmodel.NUM_PARAMS)
    )
    hs = jax.vmap(handmodel.normalize_configuration)(lo + u * (hi - lo))
    spheres = jax.vmap(handmodel.pack_spheres)(hs)
    rays = cfg.camera.rays_flat()
    d_o, mask = tracker.stage_preprocess(cfg, h, frame)
    d_o, mask = d_o.reshape(-1), mask.reshape(-1)

    got = np.asarray(ops.render_score(spheres, rays, d_o, mask))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(ref.render_score)(spheres, rays, d_o, mask))
    atol = ref.score_atol(mask)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=atol)
    return {
        "particles": int(got.shape[0]),
        "max_abs_diff": float(np.max(np.abs(got - want))),
        "atol": atol,
    }


def render_precision(camera, truth) -> dict:
    """Depth of every ground-truth pose: library default vs highest
    matmul precision. Hit/miss must agree pixel for pixel."""
    rays = camera.rays_flat()
    spheres = jax.vmap(handmodel.pack_spheres)(truth)

    def render(s):
        return jax.vmap(lambda one: objective.sphere_depth(rays, one))(s)

    default = jax.jit(render)(spheres)
    with jax.default_matmul_precision("highest"):
        highest = jax.jit(render)(spheres)
    hit_d = default < BACKGROUND_DEPTH
    hit_h = highest < BACKGROUND_DEPTH
    both = hit_d & hit_h
    out = {
        "frames": int(truth.shape[0]),
        "hit_pixels": int(jnp.sum(hit_h)),
        "hit_miss_flips": int(jnp.sum(hit_d != hit_h)),
        "max_depth_diff_m": float(
            jnp.max(jnp.where(both, jnp.abs(default - highest), 0.0))
        ),
    }
    if out["hit_miss_flips"]:
        raise AssertionError(f"default-precision render flips pixels: {out}")
    return out


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU; JAX's default device is "
            f"{dev.platform} ({dev.device_kind})"
        )
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    log(f"compile cache: {compile_cache.enable()}")

    cfg = hardware.PAPER_TRACKER_CFG
    frames, truth = rgbd.render_sequence(
        rgbd.SequenceConfig(camera=cfg.camera, num_frames=NUM_FRAMES)
    )
    log(f"sequence: {frames.shape[0]} frames of {frames.shape[1:]} depth")

    for use_kernel in (False, True):
        name = "kernel" if use_kernel else "jnp"
        res = track(dataclasses.replace(cfg, use_kernel=use_kernel),
                    frames, truth)
        log(f"track[{name}] (smoke timings, not benchmark numbers): "
            f"{json.dumps(res)}")
        if res["mean_pos_err_m"] >= MAX_MEAN_POS_ERR:
            raise AssertionError(f"track[{name}] mean position error {res}")
        if use_kernel and not res["tpu_custom_call"]:
            raise AssertionError("kernel path compiled without a Mosaic kernel")

    log(f"kernel_vs_reference: "
        f"{json.dumps(kernel_vs_reference(cfg, frames[1], truth[0]))}")
    log(f"render_precision: {json.dumps(render_precision(cfg.camera, truth))}")

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
