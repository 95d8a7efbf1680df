"""The comparison that decides ``correct``.

Every client's frames that the timed path tracked are read on their own,
and each number is the worst client's; the configuration's ``limits``
name the numbers that are compared (``readings`` lists them all):

* ``score_gap_median``: the median, over the frames, of the gap between
  the score the program reported and the reference's E_D of the same
  hypothesis. ``Tracker.step`` reports the swarm's best score with the
  smoothed pose, so the reference solves the best hypothesis back from
  the pose and the one before (``recover_best``). Scores of the
  population evaluation are what this checks, at the cell's sizes, on the
  hypothesis the program chose. The median and not the widest gap: the
  pose comes back rounded to float32, and the hypothesis solved back from
  it flips a silhouette pixel in about one frame of a hundred, which moves
  that frame's score by T / |B|; the widest gap reads that flip on sound
  runs and on the control alike.
* ``pos_err_mean_m``: the mean distance between the tracked and the true
  hand positions, over frames and hands.
* ``score_over_truth_mean``: the mean, over the frames, of the program's
  best score less the reference's E_D of the true pose: how far above the
  truth the search stopped, which a smaller search leaves higher.

The reference is the configuration's reference module (``ref``, the
contract in ``bench/reference.py``); it runs on the device after the
window, in chunks of frames.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 16  # frames per reference call


def reference_scores(ref, config: dict, hs: np.ndarray, frames: np.ndarray,
                     h_prev: np.ndarray) -> np.ndarray:
    """E_D of hypothesis hs[i] (G, NUM_PARAMS) against frames[i] (G, H, W),
    over the bounding box B of the previous pose h_prev[i], in chunks of
    CHUNK frames."""
    rays = ref.camera_rays(config["camera"])
    half = config["tracker"]["bbox_half_width"]

    @jax.jit
    def chunk(h, d_o, hp):
        def one(h1, d1, hp1):
            return ref.score(h1, d1, ref.box_mask(d1, hp1, half), rays)
        return jax.vmap(one)(h, d_o, hp)

    g = hs.shape[0]
    pad = (-g) % CHUNK
    hs = np.concatenate([hs, np.repeat(hs[-1:], pad, 0)])
    d_o = frames.reshape(g, -1)
    d_o = np.concatenate([d_o, np.repeat(d_o[-1:], pad, 0)])
    h_prev = np.concatenate([h_prev, np.repeat(h_prev[-1:], pad, 0)])
    out = [chunk(hs[i:i + CHUNK], d_o[i:i + CHUNK], h_prev[i:i + CHUNK])
           for i in range(0, g + pad, CHUNK)]
    return np.asarray(jnp.concatenate(out))[:g]


def readings(ref, config: dict, tracked: list[dict]) -> tuple[dict, dict]:
    """The numbers that can be compared, each the worst client's, and the
    per-client readings behind them.

    Each entry of ``tracked`` holds one client's ``poses`` (n + 1,
    NUM_PARAMS), the first being the pose tracking started from,
    ``scores`` (n,), ``frames`` (n, H, W) and ``truth`` (n, NUM_PARAMS).
    Every client is read on its own, so a fault in one client's slot
    moves that client's numbers in full and is not diluted by the
    others'. The numbers:

    * ``score_gap_median``: median over frames of |program's score -
      reference's E_D of the solved-back best hypothesis|.
    * ``pos_err_mean_m``: mean distance of the tracked from the true hand
      positions, over frames and hands.
    * ``score_over_truth_mean`` and ``_median``: the program's best score
      less the reference's E_D of the true pose on the same observation,
      over frames: how far the search stopped above the truth."""
    best, truth, frames, prev, sizes = [], [], [], [], []
    for t in tracked:
        poses = t["poses"]
        best.append(ref.recover_best(poses[1:], poses[:-1], config["tracker"]["smoothing"]))
        truth.append(t["truth"])
        frames.append(t["frames"])
        prev.append(poses[:-1])
        sizes.append(len(t["scores"]))
    if not sum(sizes):
        return {}, {}
    # Each hypothesis the program chose, and the true pose, scored against
    # the same observation over the same bounding box.
    scored = reference_scores(ref, config, np.concatenate(best + truth),
                              np.concatenate(frames * 2), np.concatenate(prev * 2))
    n = sum(sizes)
    ref_best = np.split(scored[:n], np.cumsum(sizes)[:-1])
    ref_truth = np.split(scored[n:], np.cumsum(sizes)[:-1])
    per_client = {k: [] for k in ("score_gap_median", "pos_err_mean_m",
                                  "score_over_truth_mean", "score_over_truth_median")}
    gaps = []
    for t, rb, rt in zip(tracked, ref_best, ref_truth):
        scores = t["scores"].astype(np.float64)
        gap = np.abs(scores - rb)
        gaps.append(gap)
        err = np.linalg.norm(ref.positions(t["poses"][1:]) - ref.positions(t["truth"]),
                             axis=-1)
        per_client["score_gap_median"].append(float(np.median(gap)))
        per_client["pos_err_mean_m"].append(float(np.mean(err)))
        per_client["score_over_truth_mean"].append(float(np.mean(scores - rt)))
        per_client["score_over_truth_median"].append(float(np.median(scores - rt)))
    gaps = np.concatenate(gaps)
    numbers = {k: max(v) for k, v in per_client.items()}
    q90, q99 = np.quantile(gaps, [0.9, 0.99])
    detail = {"score_gap_p90": float(q90), "score_gap_p99": float(q99),
              "score_gap_max": float(np.max(gaps))}
    detail.update({f"client_{k}": v for k, v in per_client.items()})
    return numbers, detail


def compare(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that has a limit, beside it; correct when every one is
    within. A limit whose number was not read, or is not a number, fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, math.nan)
        checks[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, checks
