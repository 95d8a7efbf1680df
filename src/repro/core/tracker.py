"""The 4-stage generative 3D hand tracker (paper §3.1, Fig. 2).

Per frame, the optimization happens in 4 consecutive steps, each an
offloadable unit (Multi-Step) or fused into one (Single-Step):

  1. ``preprocess`` — extract the bounding box B around the previous
     solution, mask the observed depth map.
  2. ``spawn``      — initialize the particle swarm around h_t ("particles
     are initialized around the solution of the previous frame").
  3. ``optimize``   — run the PSO generations; the population evaluation
     is the GPGPU-heavy part (Pallas kernel or vmapped reference).
  4. ``refine``     — select the global best, renormalize the quaternion,
     apply temporal smoothing; emit h_{t+1}.

The serial frame dependency (Fig. 3 category A) lives *outside* this
module: ``track_frame`` maps (key, h_t, frame) -> (key', h_{t+1}, score),
and whoever drives it (``Tracker``, sim/runtime.py) feeds each frame's
key and pose to the next.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import handmodel, objective, pso
from repro.core.camera import Camera
from repro.core.stages import CLIENT, DataItem, Stage, StagedComputation


@dataclasses.dataclass(frozen=True)
class TrackerConfig:
    camera: Camera = dataclasses.field(default_factory=Camera)
    pso: pso.PSOConfig = dataclasses.field(default_factory=pso.PSOConfig)
    pos_range: float = 0.10  # search-box half width around h_t, meters
    quat_range: float = 0.25
    smoothing: float = 0.15  # exponential temporal smoothing on h
    bbox_half_width: float = 0.25  # meters around previous depth (B)
    use_kernel: bool = False  # route evaluation through the Pallas kernel


def _make_eval_fn(
    cfg: TrackerConfig, d_o: jnp.ndarray, mask: jnp.ndarray
) -> pso.EvalFn:
    if cfg.use_kernel:
        from repro.kernels import ops as kernel_ops

        rays = cfg.camera.rays_flat()

        def eval_fn(hs: jnp.ndarray) -> jnp.ndarray:
            with jax.named_scope("tracker.evaluate"):
                spheres = jax.vmap(handmodel.pack_spheres)(hs)
                scores = kernel_ops.render_score(
                    spheres, rays, d_o.reshape(-1), mask.reshape(-1)
                )
                return objective.penalized(scores, spheres)

        return eval_fn

    def eval_fn(hs: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope("tracker.evaluate"):
            return objective.batched_objective(hs, d_o, cfg.camera, mask)

    return eval_fn


# ---------------------------------------------------------------------------
# The four stages as standalone jittable functions
# ---------------------------------------------------------------------------


def stage_preprocess(
    cfg: TrackerConfig, h_prev: jnp.ndarray, depth: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stage 1: ROI/bounding-box extraction. Returns (depth, mask): B is
    the union of each hand's depth band around its own previous depth."""
    mask = functools.reduce(jnp.logical_or, [
        objective.bounding_box_mask(depth, hand[2], cfg.bbox_half_width)
        for hand in handmodel.hands(h_prev)])
    return depth, mask


def stage_spawn(
    cfg: TrackerConfig, key: jax.Array, h_prev: jnp.ndarray,
    eval_fn: pso.EvalFn,
) -> Tuple[pso.SwarmState, jnp.ndarray, jnp.ndarray]:
    """Stage 2: swarm initialization around h_t. Returns (state, lo, hi)."""
    lo = handmodel.parameter_lower_bounds(h_prev, cfg.pos_range, cfg.quat_range)
    hi = handmodel.parameter_upper_bounds(h_prev, cfg.pos_range, cfg.quat_range)
    state = pso.init_swarm(key, h_prev, lo, hi, eval_fn, cfg.pso)
    return state, lo, hi


def stage_optimize(
    cfg: TrackerConfig,
    state: pso.SwarmState,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    eval_fn: pso.EvalFn,
) -> pso.SwarmState:
    """Stage 3: the PSO generations — the GPGPU-heavy step."""

    def body(_, st):
        return pso.swarm_step(
            st, lo, hi, eval_fn, cfg.pso,
            project_fn=handmodel.normalize_configuration,
        )

    return jax.lax.fori_loop(0, cfg.pso.num_generations, body, state)


def stage_refine(
    cfg: TrackerConfig, state: pso.SwarmState, h_prev: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stage 4: decode the solution + temporal smoothing."""
    h = handmodel.normalize_configuration(state.global_best)
    h = (1.0 - cfg.smoothing) * h + cfg.smoothing * h_prev
    h = handmodel.normalize_configuration(h)
    return h, state.global_best_score


# ---------------------------------------------------------------------------
# Fused per-frame step (Single-Step granularity)
# ---------------------------------------------------------------------------


def _track(cfg: TrackerConfig, key: jax.Array, h_prev: jnp.ndarray,
           depth: jnp.ndarray, wrap_eval: Callable[[pso.EvalFn], pso.EvalFn]):
    """One frame's four stages, each under its own ``jax.named_scope``
    (``tracker.preprocess`` ... ``tracker.refine``), so a device trace
    reads by stage. The carried key is split here, on the device: the
    frame draws from ``sub`` and hands ``key_next`` to the next frame,
    the same stream as ``jax.random.split`` on the host."""
    key_next, sub = jax.random.split(key)
    with jax.named_scope("tracker.preprocess"):
        d_o, mask = stage_preprocess(cfg, h_prev, depth)
    eval_fn = wrap_eval(_make_eval_fn(cfg, d_o, mask))
    with jax.named_scope("tracker.spawn"):
        state, lo, hi = stage_spawn(cfg, sub, h_prev, eval_fn)
    with jax.named_scope("tracker.optimize"):
        state = stage_optimize(cfg, state, lo, hi, eval_fn)
    with jax.named_scope("tracker.refine"):
        h_next, score = stage_refine(cfg, state, h_prev)
    return key_next, h_next, score


def make_track_frame(cfg: TrackerConfig) -> Callable:
    """Build the jitted (key, h_prev, depth) -> (key_next, h_next, score)
    step: one device program a frame, which splits the carried key itself
    and returns the key for the next frame beside the pose and score."""

    @jax.jit
    def track_frame(key: jax.Array, h_prev: jnp.ndarray, depth: jnp.ndarray):
        return _track(cfg, key, h_prev, depth, lambda eval_fn: eval_fn)

    return track_frame


def make_track_frame_sharded(cfg: TrackerConfig, mesh, axis: str = "model"):
    """Distributed variant: the particle population is sharded over a mesh
    axis (the paper's GPGPU parallel axis mapped onto TPU devices). The
    same (key, h_prev, depth) -> (key_next, h_next, score) step."""

    @jax.jit
    def track_frame(key: jax.Array, h_prev: jnp.ndarray, depth: jnp.ndarray):
        return _track(cfg, key, h_prev, depth,
                      lambda eval_fn: pso.sharded_eval(eval_fn, mesh, axis))

    return track_frame


class StepPhases:
    """Where ``Tracker.step`` spends host time, always counted.

    Each step is a ``tracker.step`` profiler span that carries the frame
    number, with one child span per phase: ``tracker.dispatch``, the one
    launch of the jitted step, and ``tracker.wait``, the one host wait
    for the pose and score together. The spans are written only while
    ``jax.profiler`` traces, into the trace that holds the device's
    operations. The counters cost one ``perf_counter`` read per phase:
    frames stepped, the host seconds of each phase summed over them, and
    the phase split of the slowest step, the first (compiling) step
    included; a caller that wants them from a later frame on replaces
    ``Tracker.phases`` with a new ``StepPhases``."""

    PHASES = ("dispatch", "wait")

    def __init__(self):
        self.frames = 0
        self.seconds = dict.fromkeys(self.PHASES, 0.0)
        self.slowest = dict.fromkeys(self.PHASES, 0.0)
        self._split: dict = {}
        self._mark = 0.0

    @contextlib.contextmanager
    def step(self):
        self._split = {}
        self._mark = time.perf_counter()
        with jax.profiler.TraceAnnotation("tracker.step", frame=self.frames):
            yield
        for name, s in self._split.items():
            self.seconds[name] += s
        if sum(self._split.values()) > sum(self.slowest.values()):
            self.slowest = self._split
        self.frames += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        with jax.profiler.TraceAnnotation(f"tracker.{name}"):
            yield
        now = time.perf_counter()
        self._split[name] = now - self._mark
        self._mark = now


class Tracker:
    """Stateful convenience wrapper holding h_t across frames.

    After a step, ``key`` and ``h`` are the device arrays the step
    returned, the inputs of the next: the PRNG key is carried on the
    device and split inside the step, never on the host."""

    def __init__(self, cfg: TrackerConfig, h0: Optional[jnp.ndarray] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.h = h0 if h0 is not None else handmodel.default_pose()
        self.key = jax.random.PRNGKey(seed)
        self._step = make_track_frame(cfg)
        self.phases = StepPhases()

    def step(self, depth: jnp.ndarray) -> Tuple[jax.Array, float]:
        """Track one frame: one launch of the jitted step, then one host
        wait that fetches the pose and score together, under the spans
        ``tracker.step`` > ``tracker.dispatch``, ``tracker.wait``. Returns
        the pose, the device array ``h`` whose host copy that wait
        already holds (``np.asarray`` of it copies nothing more off an
        accelerator), and the score as a float."""
        with self.phases.step():
            with self.phases.phase("dispatch"):
                self.key, self.h, score = self._step(self.key, self.h, depth)
            with self.phases.phase("wait"):
                _, score = jax.device_get((self.h, score))
        return self.h, float(score)


# ---------------------------------------------------------------------------
# Byte/FLOP-annotated staged description (for the offload engine)
# ---------------------------------------------------------------------------


def _eval_flops_per_generation(cfg: TrackerConfig, hands: int = 1) -> float:
    """Analytic FLOP count of one population evaluation of poses of
    ``hands`` hands.

    Per (particle, pixel, sphere): dot products + discriminant + sqrt
    ~= 14 fused ops; the min-reduction and scoring add ~3 per (particle,
    pixel). See kernels/render_score.py for the exact expression the
    kernel evaluates."""
    n = cfg.pso.num_particles
    p = cfg.camera.num_pixels
    s = hands * handmodel.NUM_SPHERES
    fk_flops = n * hands * 600.0 * 5  # forward kinematics per particle (tiny)
    return n * p * (s * 14.0 + 3.0) + fk_flops


def build_staged(
    cfg: TrackerConfig, frame_nbytes: Optional[int] = None,
    h0: Optional[jnp.ndarray] = None,
) -> StagedComputation:
    """The Fig. 2 pipeline with measured byte sizes and analytic FLOPs,
    for poses as wide as ``h0`` (one hand where it is not given, as in
    ``Tracker``).

    ``frame_nbytes`` overrides the size of the sensor frame that crosses
    the network (the paper ships RGB + depth at sensor resolution while
    hypotheses are rendered at a reduced working resolution; see
    sim/hardware.py PAPER_FRAME_BYTES)."""
    cam = cfg.camera
    hands = handmodel.num_hands(h0) if h0 is not None else 1
    n, d = cfg.pso.num_particles, hands * handmodel.NUM_PARAMS
    frame_bytes = (
        frame_nbytes if frame_nbytes is not None else cam.num_pixels * 4
    )
    # ROI items are at the tracker's *working* resolution regardless of
    # the sensor frame size that crosses the network.
    roi_bytes = cam.num_pixels * 4
    mask_bytes = cam.num_pixels  # bool mask
    h_bytes = d * 4
    swarm_bytes = (3 * n * d + 2 * n + d + 1 + 2) * 4  # SwarmState payload

    gens = cfg.pso.num_generations
    eval_flops = _eval_flops_per_generation(cfg, hands)

    sources = (
        DataItem("frame_depth", frame_bytes, CLIENT),
        DataItem("h_prev", h_bytes, CLIENT),
        DataItem("rng_key", 8, CLIENT),
    )
    stages = (
        Stage(
            name="preprocess",
            flops=cam.num_pixels * 4.0,
            inputs=("frame_depth", "h_prev"),
            outputs=(
                DataItem("roi_depth", roi_bytes),
                DataItem("roi_mask", mask_bytes),
            ),
            parallel_fraction=0.5,
        ),
        Stage(
            name="spawn",
            # init includes one population evaluation (scores of gen 0)
            flops=n * d * 8.0 + eval_flops,
            inputs=("rng_key", "h_prev", "roi_depth", "roi_mask"),
            outputs=(DataItem("swarm_state", swarm_bytes),),
            parallel_fraction=0.95,
        ),
        Stage(
            name="optimize",
            flops=gens * (eval_flops + n * d * 12.0),
            inputs=("swarm_state", "roi_depth", "roi_mask"),
            outputs=(DataItem("swarm_final", swarm_bytes),),
            parallel_fraction=0.98,
        ),
        Stage(
            name="refine",
            flops=d * 30.0,
            inputs=("swarm_final", "h_prev"),
            outputs=(DataItem("h_next", h_bytes), DataItem("score", 4)),
            parallel_fraction=0.0,
        ),
    )
    comp = StagedComputation(
        name="hand_tracker_frame",
        sources=sources,
        stages=stages,
        results=("h_next", "score"),
    )
    comp.validate()
    return comp
