"""Quickstart: track a synthetic hand sequence end to end on CPU.

    PYTHONPATH=src python examples/quickstart.py

Builds the 27-DoF generative tracker (paper §3.1), renders a synthetic
RGBD sequence with known ground truth, tracks it frame by frame with PSO,
and reports position/articulation error — the core loop the paper runs
natively on its server/laptop.
"""

import time

import jax.numpy as jnp
import numpy as np

from repro.core import pso, tracker
from repro.core.camera import Camera
from repro.data import rgbd
from repro.launch import compile_cache


def main() -> None:
    cam = Camera(width=64, height=64, fx=60.0, fy=60.0, cx=31.5, cy=31.5)
    seq_cfg = rgbd.SequenceConfig(
        num_frames=45, camera=cam, fast_burst=(25, 32),
        position_amplitude=0.05, curl_amplitude=0.7,
    )
    print("rendering synthetic RGBD sequence (the 'pre-recorded video')...")
    frames, truth = rgbd.render_sequence(seq_cfg)

    cfg = tracker.TrackerConfig(
        camera=cam,
        pso=pso.PSOConfig(num_particles=48, num_generations=20),
        smoothing=0.1,
    )
    t = tracker.Tracker(cfg, h0=truth[0])

    print(f"tracking {frames.shape[0]} frames "
          f"({cfg.pso.num_particles} particles x {cfg.pso.num_generations} generations)...")
    pos_errs, ang_errs, times = [], [], []
    for i in range(1, frames.shape[0]):
        t0 = time.perf_counter()
        h, score = t.step(frames[i])
        times.append(time.perf_counter() - t0)
        pos_errs.append(float(jnp.linalg.norm(h[:3] - truth[i][:3])))
        ang_errs.append(float(jnp.mean(jnp.abs(h[7:] - truth[i][7:]))))
        if i % 10 == 0:
            print(f"  frame {i:3d}: E_D={score:.4f} "
                  f"pos_err={pos_errs[-1] * 100:.2f}cm")

    print("\nresults:")
    print(f"  mean position error : {np.mean(pos_errs) * 100:.2f} cm")
    print(f"  mean angle error    : {np.degrees(np.mean(ang_errs)):.2f} deg")
    print(f"  mean loop time      : {np.mean(times[2:]) * 1e3:.1f} ms "
          f"({1 / np.mean(times[2:]):.1f} fps on this CPU)")
    print("  (the paper's GTX 1080M server runs the equivalent loop at >40 fps)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
