"""Pallas render_score kernel vs pure-jnp oracle: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import handmodel as hm
from repro.core.camera import Camera
from repro.kernels import ops, ref


def _assert_scores_close(a, b, mask):
    """Kernel vs oracle comparison within ``ref.score_atol``: one
    silhouette-pixel hit flip per particle."""
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=ref.score_atol(mask))


def _inputs(n_particles, w, h, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    cam = Camera(width=w, height=h, fx=w * 0.9, fy=w * 0.9,
                 cx=(w - 1) / 2, cy=(h - 1) / 2)
    ks = jax.random.split(key, n_particles)
    hs = jnp.stack([
        hm.default_pose(0.4).at[0].add(0.02 * i).at[7 + i % 20].add(0.1 * i)
        for i in range(n_particles)
    ])
    spheres = jax.vmap(hm.pack_spheres)(hs).astype(dtype)
    rays = cam.rays_flat().astype(dtype)
    from repro.core import objective
    d_o = objective.render_depth(hs[n_particles // 2], cam).reshape(-1)
    mask = (d_o < 5.0)
    return spheres, rays, d_o.astype(dtype), mask


@pytest.mark.parametrize("n", [1, 7, 8, 13, 32])
@pytest.mark.parametrize("wh", [(16, 16), (40, 24), (64, 64)])
def test_kernel_matches_ref_shapes(n, wh):
    spheres, rays, d_o, mask = _inputs(n, *wh)
    a = ops.render_score(spheres, rays, d_o, mask)
    b = ref.render_score(spheres, rays, d_o, mask)
    _assert_scores_close(a, b, mask)


@pytest.mark.parametrize("block_n,block_p", [(2, 128), (8, 512), (4, 256)])
def test_kernel_block_shapes(block_n, block_p):
    spheres, rays, d_o, mask = _inputs(10, 48, 32)
    a = ops.render_score(spheres, rays, d_o, mask,
                         block_n=block_n, block_p=block_p)
    b = ref.render_score(spheres, rays, d_o, mask)
    _assert_scores_close(a, b, mask)


def test_kernel_bf16_spheres_close():
    """bf16 inputs: kernel and oracle agree (both upcast internally)."""
    spheres, rays, d_o, mask = _inputs(8, 32, 32)
    a = ops.render_score(spheres.astype(jnp.bfloat16), rays, d_o, mask)
    b = ref.render_score(spheres.astype(jnp.bfloat16), rays, d_o, mask)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_kernel_empty_mask_zero_scores():
    spheres, rays, d_o, _ = _inputs(4, 24, 24)
    zero_mask = jnp.zeros_like(d_o, dtype=bool)
    a = ops.render_score(spheres, rays, d_o, zero_mask)
    np.testing.assert_allclose(np.asarray(a), np.zeros(4), atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 24), st.integers(8, 48), st.integers(8, 40))
def test_kernel_matches_ref_property(n, w, h):
    spheres, rays, d_o, mask = _inputs(n, w, h)
    a = ops.render_score(spheres, rays, d_o, mask)
    b = ref.render_score(spheres, rays, d_o, mask)
    _assert_scores_close(a, b, mask)


def test_tracker_kernel_path_matches_reference_path():
    """TrackerConfig(use_kernel=True) must track identically-shaped output
    and closely-matching objective values to the vmapped reference."""
    import jax
    from repro.core import pso, tracker
    cam = Camera(width=32, height=32, fx=30., fy=30., cx=15.5, cy=15.5)
    base = dict(camera=cam, pso=pso.PSOConfig(num_particles=16, num_generations=5))
    from repro.core import objective
    h0 = hm.default_pose(0.45)
    depth = objective.render_depth(h0, cam)
    key = jax.random.PRNGKey(0)
    for use_kernel in (False, True):
        cfg = tracker.TrackerConfig(use_kernel=use_kernel, **base)
        step = tracker.make_track_frame(cfg)
        key1, h1, score = step(key, h0.at[0].add(0.02), depth)
        assert h1.shape == (27,) and key1.shape == key.shape
        assert not bool(jnp.isnan(score))
