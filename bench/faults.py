"""The control and the faults that ``correct`` has to refuse.

Each is planted in the program under the timed path, in this process,
by ``plant(name, ref)``, which returns a function that takes it out
again; ``ref`` is the cell's reference module (``Cell.reference``).
``bench/calibrate.py`` reads them on the chip at a cell's own sizes; the
tests in ``bench/tests/test_correct.py`` drive whole runs with each.

* ``control``: the reference put in the program's place, the population
  evaluation computed by the reference's ``score`` with the
  ``<ray, centre>`` product at ``high`` precision (``dot_high``), one step
  below the ``highest`` that the configurations state.
* ``state_unchanged``: a step that returns its state unchanged.
* ``answer_altered``: the pose altered by 5 mm where it is produced.
* ``half_the_pixels``: half of the pixels left out of the score, the mean
  taken over the rest.
* ``half_swarm``: a population evaluation that scores only the first half
  of the particles; the rest read +inf, so they never become a best and
  the swarm searches with half its particles.
* ``client_slot``: with several clients, the second client's hypotheses
  scored against the frame that the third client sent last, as a batching
  bug that mixes up two client slots would.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp


def control_objective(ref, hs, d_o, camera, mask):
    """E_D of each hypothesis by the reference ``ref`` at ``high``
    precision; past ``ref``, the signature of
    ``repro.core.objective.batched_objective``."""
    rays = ref.camera_rays(vars(camera))
    d_o, mask = d_o.reshape(-1), mask.reshape(-1)
    return jax.vmap(lambda h: ref.score(h, d_o, mask, rays, ref.dot_high))(hs)


def _patch(target, attr, value):
    saved = getattr(target, attr)
    setattr(target, attr, value)
    jax.clear_caches()

    def undo():
        setattr(target, attr, saved)
        jax.clear_caches()

    return undo


def _objective_fault(wrap):
    from repro.core import objective

    return _patch(objective, "batched_objective", wrap(objective.batched_objective))


def _step_fault(wrap):
    from repro.core import tracker

    return _patch(tracker.Tracker, "step", wrap(tracker.Tracker.step))


def _control(ref):
    return _objective_fault(lambda _: functools.partial(control_objective, ref))


def _half_the_pixels():
    def wrap(objective):
        def faulty(hs, d_o, camera, mask):
            flat = mask.reshape(-1)
            kept = flat.at[flat.shape[0] // 2:].set(False)
            return objective(hs, d_o, camera, kept.reshape(mask.shape))
        return faulty
    return _objective_fault(wrap)


def _half_swarm():
    def wrap(objective):
        def faulty(hs, d_o, camera, mask):
            half = hs.shape[0] // 2
            scores = objective(hs[:half], d_o, camera, mask)
            return jnp.concatenate([scores, jnp.full((hs.shape[0] - half,), jnp.inf,
                                                     scores.dtype)])
        return faulty
    return _objective_fault(wrap)


def _state_unchanged():
    def wrap(step):
        def faulty(self, depth):
            h, key = self.h, self.key
            _, score = step(self, depth)
            self.h, self.key = h, key
            return h, score
        return faulty
    return _step_fault(wrap)


def _answer_altered():
    def wrap(step):
        def faulty(self, depth):
            h, score = step(self, depth)
            return h.at[0].add(0.005), score
        return faulty
    return _step_fault(wrap)


def _client_slot():
    """Trackers are numbered in the order they are built; tracker 1 gets
    the frame tracker 2 was given last (its own until tracker 2 has one)."""
    from repro.core import tracker

    order, last, lock = {}, {}, threading.Lock()
    init, step = tracker.Tracker.__init__, tracker.Tracker.step

    def numbered_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        with lock:
            order[id(self)] = len(order)

    def faulty(self, depth):
        slot = order.get(id(self))
        with lock:
            if slot == 2:
                last[2] = depth
            if slot == 1:
                depth = last.get(2, depth)
        return step(self, depth)

    undo_init = _patch(tracker.Tracker, "__init__", numbered_init)
    undo_step = _patch(tracker.Tracker, "step", faulty)

    def undo():
        undo_step()
        undo_init()

    return undo


FAULTS = {
    "control": _control,
    "state_unchanged": _state_unchanged,
    "answer_altered": _answer_altered,
    "half_the_pixels": _half_the_pixels,
    "half_swarm": _half_swarm,
    "client_slot": _client_slot,
}


def plant(name: str, ref):
    """Plant the fault of that name in a cell whose reference module is
    ``ref``; returns the function that undoes it."""
    return _control(ref) if name == "control" else FAULTS[name]()
