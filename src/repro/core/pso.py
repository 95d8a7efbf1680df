"""Particle Swarm Optimization (paper §3.1, "PSO").

Canonical Clerc–Kennedy constriction PSO [21 in the paper]: particles keep
a position and velocity; each is pulled towards its personal best and the
swarm's global best. "PSO does not require training and does not need to
compute the gradient" — the objective is consumed as a black box
``(N, D) -> (N,)`` population evaluator, which is exactly the part the
paper runs on the GPGPU (and the part this framework offloads / shards).

The whole optimization is a single ``jax.lax.fori_loop`` over generations,
so one jit'd call performs the full per-frame search — this is the paper's
"Single-Step" granularity. The tracker can also drive generations in
chunks from the host for "Multi-Step" offload experiments.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

EvalFn = Callable[[jnp.ndarray], jnp.ndarray]  # (N, D) -> (N,)


@dataclasses.dataclass(frozen=True)
class PSOConfig:
    num_particles: int = 64
    num_generations: int = 30
    # Clerc-Kennedy constriction coefficients (paper ref [21]).
    inertia: float = 0.7298
    cognitive: float = 1.49618
    social: float = 1.49618
    # Fraction of the search-box size used to cap |velocity|.
    velocity_clip: float = 0.5
    # Re-randomize this fraction of the worst particles each generation
    # (stochastic restart — keeps the swarm exploring under fast motion).
    restart_fraction: float = 0.0


class SwarmState(NamedTuple):
    positions: jnp.ndarray  # (N, D)
    velocities: jnp.ndarray  # (N, D)
    personal_best: jnp.ndarray  # (N, D)
    personal_best_score: jnp.ndarray  # (N,)
    global_best: jnp.ndarray  # (D,)
    global_best_score: jnp.ndarray  # ()
    key: jax.Array


def init_swarm(
    key: jax.Array,
    center: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    eval_fn: EvalFn,
    config: PSOConfig,
) -> SwarmState:
    """Particles initialized uniformly in [lo, hi] around `center`; particle
    0 is pinned to `center` itself (the previous frame's solution), which
    guarantees tracking never regresses below the motion-continuity prior.
    """
    n = config.num_particles
    d = center.shape[-1]
    key, kpos, kvel = jax.random.split(key, 3)
    span = hi - lo
    positions = lo + jax.random.uniform(kpos, (n, d), dtype=center.dtype) * span
    positions = positions.at[0].set(center)
    velocities = (
        jax.random.uniform(kvel, (n, d), dtype=center.dtype) - 0.5
    ) * span * 0.1
    scores = eval_fn(positions)
    best_idx = jnp.argmin(scores)
    return SwarmState(
        positions=positions,
        velocities=velocities,
        personal_best=positions,
        personal_best_score=scores,
        global_best=positions[best_idx],
        global_best_score=scores[best_idx],
        key=key,
    )


def swarm_step(
    state: SwarmState,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    eval_fn: EvalFn,
    config: PSOConfig,
    project_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
) -> SwarmState:
    """One PSO generation: velocity update, move, clamp, evaluate, rebest."""
    key, k1, k2, k3 = jax.random.split(state.key, 4)
    n, d = state.positions.shape
    r1 = jax.random.uniform(k1, (n, d), dtype=state.positions.dtype)
    r2 = jax.random.uniform(k2, (n, d), dtype=state.positions.dtype)
    vel = (
        config.inertia * state.velocities
        + config.cognitive * r1 * (state.personal_best - state.positions)
        + config.social * r2 * (state.global_best[None, :] - state.positions)
    )
    span = hi - lo
    vmax = config.velocity_clip * span
    vel = jnp.clip(vel, -vmax, vmax)
    pos = jnp.clip(state.positions + vel, lo, hi)
    if project_fn is not None:
        pos = project_fn(pos)

    if config.restart_fraction > 0.0:
        n_restart = max(1, int(n * config.restart_fraction))
        worst = jnp.argsort(state.personal_best_score)[-n_restart:]
        fresh = lo + jax.random.uniform(k3, (n_restart, d), dtype=pos.dtype) * span
        pos = pos.at[worst].set(fresh)

    scores = eval_fn(pos)
    improved = scores < state.personal_best_score
    pbest = jnp.where(improved[:, None], pos, state.personal_best)
    pbest_score = jnp.where(improved, scores, state.personal_best_score)
    gidx = jnp.argmin(pbest_score)
    gbest_score = pbest_score[gidx]
    gbest = pbest[gidx]
    return SwarmState(pos, vel, pbest, pbest_score, gbest, gbest_score, key)


def run(
    key: jax.Array,
    center: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    eval_fn: EvalFn,
    config: PSOConfig,
    project_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full PSO search. Returns (best_position (D,), best_score ())."""
    state = init_swarm(key, center, lo, hi, eval_fn, config)

    def body(_, st):
        return swarm_step(st, lo, hi, eval_fn, config, project_fn)

    state = jax.lax.fori_loop(0, config.num_generations, body, state)
    return state.global_best, state.global_best_score


def run_chunked(
    key: jax.Array,
    center: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    eval_fn: EvalFn,
    config: PSOConfig,
    num_chunks: int,
    project_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Tuple[SwarmState, ...]]:
    """PSO split into `num_chunks` host-visible pieces (Multi-Step offload:
    each chunk is a separately offloadable method whose swarm state crosses
    the client<->server boundary). Returns intermediate states for byte
    accounting by the offload engine."""
    gens = config.num_generations
    per = max(1, gens // num_chunks)
    state = init_swarm(key, center, lo, hi, eval_fn, config)
    states = []

    @jax.jit
    def chunk(st):
        def body(_, s):
            return swarm_step(s, lo, hi, eval_fn, config, project_fn)

        return jax.lax.fori_loop(0, per, body, st)

    for _ in range(num_chunks):
        state = chunk(state)
        states.append(state)
    return state.global_best, state.global_best_score, tuple(states)


def sharded_eval(
    eval_fn: EvalFn, mesh: jax.sharding.Mesh, axis: str = "model"
) -> EvalFn:
    """Wrap a population evaluator so particles are sharded over a mesh
    axis — the paper's GPGPU parallelism mapped onto the TPU mesh. Each
    device evaluates N/devices particles; scores are all-gathered (tiny:
    N floats), so the only collective in the PSO loop is O(N) bytes.
    """
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        eval_fn,
        mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=P(axis),
        check_vma=False,
    )
