"""Pallas TPU kernels for the paper's GPGPU hot spots.

* ``render_score`` — fused particle render + E_D scoring (the population
  evaluation the paper offloads). ``ops`` is the jit'd wrapper, ``ref``
  the pure-jnp oracle.
* ``pso_update`` — fused Clerc-Kennedy swarm velocity/position update
  (``pso_ref`` oracle).

Both kernels also ship *batched* variants with a leading client axis
(``render_score_sums_batched`` / ``pso_update_batched``) — one fused
launch evaluates B clients' swarms, the edge-batching amortization the
fleet simulator (``repro.cluster``) prices with its
``BatchServiceModel``.  B=1 reproduces the unbatched kernels
bit-for-bit (tests/test_batching.py).

Every kernel entry point takes ``interpret=None`` by default, which
``resolve_interpret`` turns into the platform's mode: Mosaic-compiled on
a TPU, the Pallas interpreter on the CPU backend.
"""

from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """True only where the default backend is the CPU: there Pallas can
    only interpret. A TPU always runs the compiled kernel."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; ``None`` follows the platform."""
    return default_interpret() if interpret is None else bool(interpret)
