"""``correct`` separates the program from its control and from planted
faults, at a size the CPU holds.

Each test drives a whole run of ``harness.run_cell`` (inputs from the
seed, warm-up, a one-second closed-loop window, the reference check),
skipping only the harness's look for a chip, with the configuration's
own limits. The control is the reference at ``high`` precision put in
the program's place; the faults are planted under the timed path.
"""

import copy
import dataclasses
import time

import jax
import pytest

from bench import faults, harness

SEED = 2**31 + 21
CACHE_OPTIONS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(autouse=True)
def restore_compile_cache():
    """A run turns JAX's persistent compilation cache on, as every run of
    the benchmark does; later tests in this process get it back off."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _tiny(name):
    cell = harness.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["camera"] = {"width": 32, "height": 32, "fx": 27.5, "fy": 27.5, "cx": 15.5, "cy": 15.5}
    cfg["pso"] = {"num_particles": 32, "num_generations": 10}
    return dataclasses.replace(cell, chips=1, config=cfg)


@pytest.fixture(scope="module")
def tiny_cell():
    """paper128.solo with its limits, at a 32x32 camera and a swarm of 32
    particles x 10 generations: the control's gap grows with the search
    that exploits it, and reads 1.3e-5 to 1.6e-5 here (2.5x the limit)."""
    return _tiny("paper128.solo")


@pytest.fixture(scope="module")
def tiny_clients4():
    """paper128.clients4 at the same small sizes: four trackers, four threads."""
    return _tiny("paper128.clients4")


def _run(cell):
    return harness.run_cell(cell, SEED, 1.0, False, time.perf_counter())


def test_program_is_correct(tiny_cell):
    r = _run(tiny_cell)
    assert r["attempted"] > 5 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {m["name"] for m in tiny_cell.end_to_end}


def test_control_is_not_correct(tiny_cell):
    undo = faults.plant("control", tiny_cell.reference)
    try:
        r = _run(tiny_cell)
    finally:
        undo()
    c = r["checks"]["score_gap_median"]
    assert not r["correct"] and c["value"] > c["limit"], c


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered", "half_the_pixels"])
def test_fault_is_not_correct(tiny_cell, fault):
    undo = faults.plant(fault, tiny_cell.reference)
    try:
        r = _run(tiny_cell)
    finally:
        undo()
    assert not r["correct"], r["checks"]


def test_four_clients_are_correct(tiny_clients4):
    r = _run(tiny_clients4)
    assert len(r["frames"]) == 4 and min(r["frames"]) > 3 and r["failed"] == 0
    assert r["correct"], r["checks"]
    assert len(r["not_compared"]["client_score_gap_median"]) == 4


def test_one_client_slot_fault_is_not_correct(tiny_clients4):
    """One client of four scored against another's frame: that client's
    own numbers fail, though three quarters of the frames are sound."""
    undo = faults.plant("client_slot", tiny_clients4.reference)
    try:
        r = _run(tiny_clients4)
    finally:
        undo()
    c = r["checks"]["score_gap_median"]
    assert not r["correct"] and c["value"] > c["limit"], r["checks"]
    medians = r["not_compared"]["client_score_gap_median"]
    assert sum(m > c["limit"] for m in medians) == 1, medians
