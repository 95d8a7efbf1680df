"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before any jax initialization.

Mesh shapes (from the mandate):
  single-pod:  (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

The ``pod`` axis doubles as the *edge tier* axis for the tiered-serving
experiments (serving/edge.py): client pod / server pod, with the offload
traffic crossing pods as DCN collectives.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices) -> Mesh:
    """``jax.make_mesh`` with Auto axis types: the compiler propagates
    shardings, so a gather from a sharded array (the swarm's argmin
    pick) lowers with a collective instead of a sharding-type error."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np

    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh needs {n} devices but only {len(devices)} exist — run "
            "under dryrun.py (it forces 512 host platform devices)"
        )
    return _auto_mesh(shape, axes, devices[:n])


def make_host_mesh(
    data: Optional[int] = None, model: Optional[int] = None
) -> Mesh:
    """A small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if data is None or model is None:
        model = 1
        data = n
    assert data * model == n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"), jax.devices())


def mesh_device_count(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
