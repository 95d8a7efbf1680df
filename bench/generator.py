"""The one traffic generator: client sequences from a traffic file and a seed.

A traffic file (``bench/traffic/<mix>.json``) gives the number of
clients, their loop discipline and the motion of the hand. Each client
gets its own ground-truth trajectory and its own depth sequence, drawn
from ``seed + client``; every seed gives the same number of frames at
the same sizes, so a seed changes what the hand does, not how much work
the tracker does.

The hand is the configuration's reference module (``ref``, the
contract in ``bench/reference.py``): its ``trajectory`` turns the traffic
file's ``motion`` into true poses, and its ``render`` makes the
observations on the device, a fixed number of frames to a call of one
compiled program, with Gaussian depth noise.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

RENDER_CHUNK = 32  # frames per call of the renderer


@dataclasses.dataclass(frozen=True)
class ClientSequence:
    """One client's input: frames (T, H, W) on the host and the truth
    (T, NUM_PARAMS)."""

    seed: int
    truth: np.ndarray
    frames: np.ndarray

    def index(self, k: int) -> int:
        """Sequence index of the client's k-th frame. Past the end the
        sequence plays backwards, so the pose never jumps."""
        n = self.truth.shape[0]
        j = k % (2 * n - 2)
        return j if j < n else 2 * n - 2 - j


def _render_fn(ref, camera: dict, noise_std: float):
    rays = ref.camera_rays(camera)
    shape = (camera["height"], camera["width"])

    @jax.jit
    def render_chunk(truth, key):
        depth = jax.lax.map(lambda h: ref.render(h, rays), truth, batch_size=8)
        noise = noise_std * jax.random.normal(key, depth.shape, jnp.float32)
        return (depth + noise).reshape((truth.shape[0],) + shape)

    return render_chunk


def render(ref, camera: dict, noise_std: float, truth: np.ndarray, seed: int) -> np.ndarray:
    """Noisy depth frames (T, H, W) of the poses ``truth`` (T, NUM_PARAMS),
    on the host. They are rendered on the device RENDER_CHUNK frames to a
    call, by one compiled program, so that what the inputs hold on the
    device stays small beside what the tracker holds."""
    render_chunk = _render_fn(ref, camera, noise_std)
    n = truth.shape[0]
    padded = np.concatenate([truth, np.repeat(truth[-1:], (-n) % RENDER_CHUNK, 0)])
    key = jax.random.PRNGKey(seed)
    chunks = [np.asarray(render_chunk(jnp.asarray(padded[i:i + RENDER_CHUNK]),
                                      jax.random.fold_in(key, i)))
              for i in range(0, padded.shape[0], RENDER_CHUNK)]
    return np.concatenate(chunks)[:n]


def build(ref, camera: dict, traffic: dict, seed: int) -> list[ClientSequence]:
    """Every client's sequence for this seed: ``sequence_frames`` frames each."""
    motion = traffic["motion"]
    n = traffic["sequence_frames"]
    clients = []
    for c in range(traffic["clients"]):
        client_seed = seed + c
        rng = np.random.default_rng(client_seed)
        offset = int(rng.integers(motion["burst_every"])) + c * traffic["client_phase_shift"]
        truth = ref.trajectory(motion, n, offset)
        frames = render(ref, camera, motion["noise_std"], truth, client_seed)
        clients.append(ClientSequence(client_seed, truth, frames))
    return clients
