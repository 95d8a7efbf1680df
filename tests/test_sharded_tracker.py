"""The paper's GPGPU axis mapped onto the TPU mesh: particle-parallel
PSO evaluation via shard_map, and the sharded tracker lowering."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The subprocess compiles the full sharded tracker step on 8 fake CPU
# devices, which can take minutes on a loaded CI container.  The
# workload below is the smallest that still exercises every contract
# (sharded eval parity, collective lowering, execution); the timeout is
# env-tunable for slow runners.
SUBPROC_TIMEOUT = int(os.environ.get("REPRO_SUBPROC_TIMEOUT", "600"))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.core import handmodel, objective, pso, tracker
from repro.core.camera import Camera
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(data=2, model=4)
cam = Camera(width=24, height=24, fx=22.0, fy=22.0, cx=11.5, cy=11.5)
h0 = handmodel.default_pose(0.45)
depth = objective.render_depth(h0, cam)

# 1) sharded population eval == local eval
def eval_local(hs):
    return objective.batched_objective(hs, depth, cam)

key = jax.random.PRNGKey(0)
lo = handmodel.parameter_lower_bounds(h0)
hi = handmodel.parameter_upper_bounds(h0)
hs = lo + jax.random.uniform(key, (8, 27)) * (hi - lo)
with mesh:
    sharded = pso.sharded_eval(eval_local, mesh, "model")
    a = jax.jit(sharded)(hs)
b = eval_local(hs)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6)
print("SHARDED_EVAL_OK")

# 2) the full sharded tracker step lowers + compiles on the mesh
cfg = tracker.TrackerConfig(
    camera=cam, pso=pso.PSOConfig(num_particles=8, num_generations=2)
)
with mesh:
    step = tracker.make_track_frame_sharded(cfg, mesh, "model")
    lowered = step.lower(key, h0, depth)
    compiled = lowered.compile()
    txt = compiled.as_text()
# particles are sharded -> the swarm argmin/gather needs collectives
has_coll = any(k in txt for k in ("all-gather", "all-reduce", "collective-permute", "all-to-all"))
print("LOWERED_OK collectives=%s" % has_coll)
key1, h1, score = step(key, h0.at[0].add(0.02), depth)
assert h1.shape == (27,) and not bool(jnp.isnan(score))
# the step splits the carried key itself, as the one-device step does
np.testing.assert_array_equal(np.asarray(key1), np.asarray(jax.random.split(key)[0]))
print("EXECUTED_OK")
"""


def test_sharded_tracker_on_8_fake_devices():
    """Runs in a subprocess: needs its own XLA device-count flag.

    A compile that outlives ``REPRO_SUBPROC_TIMEOUT`` is a slow runner,
    not a product regression — skip (with the knob named in the reason,
    so it is actionable in the CI log) instead of erroring the tier-1
    run.  A nonzero exit or missing marker still FAILS: only the
    timeout is environmental."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", SCRIPT],
            capture_output=True, text=True, timeout=SUBPROC_TIMEOUT,
            # the child inherits the caller's environment, pinned to the
            # CPU backend: its 8 devices are virtual host devices
            env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
    except subprocess.TimeoutExpired:
        pytest.skip(
            f"sharded-tracker subprocess exceeded REPRO_SUBPROC_TIMEOUT="
            f"{SUBPROC_TIMEOUT}s (slow runner; raise the env var to "
            f"run it to completion)"
        )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARDED_EVAL_OK" in proc.stdout
    assert "LOWERED_OK collectives=True" in proc.stdout
    assert "EXECUTED_OK" in proc.stdout
