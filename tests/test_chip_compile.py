"""The served path compiles for a TPU v5e at paper width.

Nothing runs here: the TPU compiler that ships with JAX compiles for a
chip that is described, not attached, and refuses what the chip would
refuse (block shapes off Mosaic's tiling, unsupported in-kernel ops, too
much VMEM). The topology is described inside a fixture, never at import,
so every test worker collects the same tests and only the one that runs
this file loads the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.core import handmodel, tracker
from repro.kernels import render_score
from repro.sim import hardware

CFG = hardware.PAPER_TRACKER_CFG
N = CFG.pso.num_particles
P = CFG.camera.num_pixels
S = handmodel.NUM_SPHERES


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels in their TPU mode: lowering asks the resolver, and this
    process's backend is the CPU. Traces are cleared on both sides so no
    interpret-mode trace leaks in, and no TPU-mode trace leaks out."""
    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_render_score_sums_compiles_at_paper_width(one_chip, compiled_kernels):
    compiled = jax.jit(render_score.render_score_sums).lower(
        _spec(one_chip, N, S, 4), _spec(one_chip, P, 3),
        _spec(one_chip, P), _spec(one_chip, P),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_render_score_sums_batched_compiles_at_b4(one_chip, compiled_kernels):
    b = 4
    compiled = jax.jit(render_score.render_score_sums_batched).lower(
        _spec(one_chip, b, N, S, 4), _spec(one_chip, b, P, 3),
        _spec(one_chip, b, P), _spec(one_chip, b, P),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_paper_track_frame_compiles(one_chip, compiled_kernels, use_kernel):
    step = tracker.make_track_frame(
        dataclasses.replace(CFG, use_kernel=use_kernel)
    )
    cam = CFG.camera
    compiled = step.lower(
        _spec(one_chip, 2, dtype=jnp.uint32),
        _spec(one_chip, handmodel.NUM_PARAMS),
        _spec(one_chip, cam.height, cam.width),
    ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
