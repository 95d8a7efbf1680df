"""The harness finds every part of a cell by name, and refuses to run
anywhere but on the chip."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import time

import numpy as np
import pytest

from bench import generator, harness, reference, scopes, trace

SPEC = json.loads(harness.SPEC.read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = [c["name"] for c in SPEC["configs"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TINY = {"width": 24, "height": 20, "fx": 20.0, "fy": 20.0, "cx": 11.5, "cy": 9.5}


def test_spec_has_exactly_the_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines_use_the_allowed_characters():
    configs = [c["name"] for c in SPEC["configs"]]
    metrics = [m["name"] for m in METRICS]
    for group in (configs, CELLS, metrics):
        assert len(set(group)) == len(group), group
    names = configs + CELLS + metrics + [w["traffic"] for w in SPEC["workloads"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    lines = [w["why"] for w in SPEC["workloads"]] + [c["source"] for c in SPEC["configs"]]
    lines += [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"]
    for line in lines:
        assert 1 <= len(line) <= 200 and "\n" not in line and "\t" not in line, line


def test_bounds_and_sources_keep_to_the_contract():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = harness.load_cell(name)
    w = next(w for w in SPEC["workloads"] if w["name"] == name)
    assert harness.traffic_file(w["traffic"]).is_file()
    conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert conf["file"].startswith("bench/")
    assert cell.config["name"] == conf["name"] and cell.config["reduced"] == conf["reduced"]
    assert cell.traffic["clients"] >= 1 and cell.traffic["loop"] == "closed"
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_reader(m["name"]))
    for m in cell.per_layer:
        assert m["moves"] in reported


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    assert harness.metric_file(metric).is_file()
    assert callable(harness.load_reader(metric))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no.such_cell")


def _cell_of(config: str) -> str:
    return next(w["name"] for w in SPEC["workloads"] if w["config"] == config)


@pytest.mark.parametrize("config", CONFIGS)
def test_the_configuration_s_reference_is_its_hand_model(config):
    """``"reference": "bench/reference.py"`` is the module that the
    generator, the check and the faults were written against."""
    cell = harness.load_cell(_cell_of(config))
    assert cell.config["reference"] == "bench/reference.py"
    assert cell.reference is reference


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("hand", [{"num_params": 54, "num_spheres": 48},
                                  {"num_params": 27, "num_spheres": 96}],
                         ids=["pose_width", "spheres"])
def test_a_hand_the_reference_does_not_have_is_refused_at_load(tmp_path, config, hand):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    conf = json.loads((harness.ROOT / entry["file"]).read_text())
    conf["hand"] = hand
    (tmp_path / entry["file"]).parent.mkdir(parents=True)
    (tmp_path / entry["file"]).write_text(json.dumps(conf))
    shutil.copy(harness.SPEC, tmp_path / "BENCHMARK.json")
    with pytest.raises(ValueError, match="parameters"):
        harness.load_cell(_cell_of(config), tmp_path / "BENCHMARK.json")


@pytest.mark.parametrize("path", ["bench/reference.json", "src/repro/core/handmodel.py"])
def test_a_reference_outside_the_benchmark_s_modules_is_refused(path):
    with pytest.raises(ValueError, match="not a module under bench"):
        harness.reference_module({"name": "x", "reference": path,
                                  "hand": {"num_params": 27, "num_spheres": 48}})


def _fake_run(with_trace: bool) -> harness.Run:
    summary = trace.TraceSummary(window_s=2.0, busy_s=1.5, devices=1,
                                 device_ops=[["fusion", 1.5]], idle_gaps=[["step", 0.5]])
    by_scope = scopes.ScopeSummary(
        device_scopes=[["tracker.evaluate", 1.2], ["tracker.optimize", 0.2],
                       ["jit__threefry_split", 0.1]],
        program_gaps=[["step>tracker.wait", 0.2], ["fetch+step>tracker.key_split", 0.1],
                      ["step>tracker.dispatch", 0.05], ["step>tracker.step", 0.05],
                      ["host", 0.1]],
        clock_lag=[], step_runs=100)
    return harness.Run(
        setup_s=7.5, client_frames=[40, 60], client_seconds=[2.0, 3.0],
        latencies_s=np.arange(1, 101) * 1e-3, frame_flops=1e9,
        peaks={"flops_per_s_bf16": 1e12}, trace=summary if with_trace else None,
        scopes=by_scope if with_trace else None)


def test_readers_compute_from_the_run():
    run = _fake_run(True)
    read = {m["name"]: harness.load_reader(m["name"])(run) for m in METRICS}
    assert read["frames_per_s"] == pytest.approx(40 / 2.0 + 60 / 3.0)
    assert read["frame_ms_p50"] == pytest.approx(50.5)
    assert read["frame_ms_p95"] == pytest.approx(95.05)
    assert read["setup_s"] == 7.5
    assert read["device_idle_share"] == pytest.approx(25.0)
    assert read["step_device_ms"] == pytest.approx(15.0)
    assert read["frame_mfu"] == pytest.approx(100 * 1e9 * 50 / 1e12)
    assert read["eval_device_ms"] == pytest.approx(1e3 * 1.2 / 100)
    assert read["step_host_gap_ms"] == pytest.approx(1e3 * (0.2 + 0.1 + 0.05) / 100)


def test_scope_readers_read_nothing_where_the_trace_lacks_their_scopes():
    run = _fake_run(True)
    run.scopes = scopes.ScopeSummary(device_scopes=[["unscoped", 1.5]],
                                     program_gaps=[["step", 0.4], ["host", 0.1]], clock_lag=[],
                                     step_runs=100)
    for metric in ("eval_device_ms", "step_host_gap_ms"):
        assert harness.load_reader(metric)(run) is None, metric


def test_device_readers_read_nothing_without_a_trace():
    run = _fake_run(False)
    for m in SPEC["per_layer"]:
        assert harness.load_reader(m["name"])(run) is None, m["name"]


class _Step:
    """A tracker whose every step takes ``seconds``."""

    def __init__(self, seconds):
        self.seconds = seconds

    def step(self, depth):
        time.sleep(self.seconds)
        return np.zeros(reference.NUM_PARAMS, np.float32), 0.5


@pytest.mark.parametrize("count, seconds, frames", [
    (None, 0.05, 1),  # untraced: until a pose lands after the window's end
    (None, 0.0, None),  # untraced, the window open: past any count
    (7, 0.0, 7),  # traced: the frame count ends it first
    (7, 0.05, 1),  # traced: the window's end comes first
])
def test_a_client_loop_ends_at_the_window_or_its_count(count, seconds, frames):
    seq = generator.ClientSequence(0, np.zeros((4, reference.NUM_PARAMS), np.float32),
                                   np.zeros((4, 2, 2), np.float32))
    client = harness._Client(seq, _Step(seconds))
    t_end = time.perf_counter() + (0.02 if seconds else 0.3)
    client.loop(1, t_end, count)
    if frames is None:
        assert len(client.latencies) > 7 and client.last_done >= t_end
    else:
        assert len(client.latencies) == frames
    assert len(client.poses) == len(client.latencies) == len(client.loop_gaps) + 1
    assert client.last_done > 0


def _command(cwd, env):
    return subprocess.run(
        SPEC["command"] + ["--workload", CELLS[0], "--seed", str(2**31 + 5),
                           "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _command(harness.ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_command_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(harness.SPEC, tmp_path / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _command(tmp_path, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


@pytest.mark.parametrize("traffic", sorted({w["traffic"] for w in SPEC["workloads"]}))
def test_traffic_is_deterministic_in_the_seed(traffic):
    mix = json.loads(harness.traffic_file(traffic).read_text())
    a = generator.build(reference, TINY, mix, 2**31 + 11)
    b = generator.build(reference, TINY, mix, 2**31 + 11)
    c = generator.build(reference, TINY, mix, 2**31 + 12)
    assert len(a) == mix["clients"]
    assert a[0].frames.shape == (mix["sequence_frames"], TINY["height"], TINY["width"])
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x.frames, y.frames)
        np.testing.assert_array_equal(x.truth, y.truth)
        assert x.frames.shape == z.frames.shape  # a seed changes the motion, not the work
        assert not np.array_equal(x.frames, z.frames)


# sha256 over each client's truth and frames, in turn, of the sequences
# that ``generator.build`` made before the hand model came from the
# configuration's reference module, on the CPU at TINY, seed 2**31 + 11
DIGESTS_BEFORE = {
    "solo": "407dd455459eb056487400c33e11def5445b2ccbeb42f9d2d7ef3f29618d9057",
    "clients4": "fc4be652b42f9331c453d0ddf4a627cef9da0711f3d2cb91925d0ae5a3100233",
}


@pytest.mark.parametrize("traffic", sorted(DIGESTS_BEFORE))
def test_traffic_through_the_cell_s_reference_is_bit_identical(traffic):
    cell = harness.load_cell(next(w["name"] for w in SPEC["workloads"]
                                  if w["traffic"] == traffic))
    mix = json.loads(harness.traffic_file(traffic).read_text())
    digest = hashlib.sha256()
    for seq in generator.build(cell.reference, TINY, mix, 2**31 + 11):
        digest.update(seq.truth.tobytes())
        digest.update(seq.frames.tobytes())
    assert digest.hexdigest() == DIGESTS_BEFORE[traffic]


def test_sequence_plays_back_without_a_jump():
    mix = json.loads(harness.traffic_file("solo").read_text())
    truth = reference.trajectory(mix["motion"], 50, offset=3)
    seq = generator.ClientSequence(0, truth, np.zeros((50, 1, 1), np.float32))
    path = np.stack([truth[seq.index(k)] for k in range(200)])
    steps = np.abs(np.diff(path[:, :3], axis=0)).max()
    assert steps <= np.abs(np.diff(truth[:, :3], axis=0)).max() + 1e-7
    assert [seq.index(k) for k in (48, 49, 50, 51, 98, 99)] == [48, 49, 48, 47, 0, 1]


def test_bursts_recur_every_period():
    mix = json.loads(harness.traffic_file("solo").read_text())["motion"]
    truth = reference.trajectory(mix, 3 * mix["burst_every"], offset=0)
    speed = np.linalg.norm(np.diff(truth[:, :3], axis=0), axis=1)
    every, length = mix["burst_every"], mix["burst_length"]
    fast = [speed[p * every + every - length:(p + 1) * every - 1].mean() for p in range(3)]
    slow = [speed[p * every:p * every + every - length - 1].mean() for p in range(3)]
    assert all(f > 1.5 * s for f, s in zip(fast, slow))
