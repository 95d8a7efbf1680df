"""Run one benchmark cell once: inputs from the seed, warm-up, window, check.

Everything a cell is made of is found by name. ``BENCHMARK.json`` names
the cell's configuration (its ``file``) and traffic mix
(``bench/traffic/<traffic>.json``); the configuration names its hand
model's reference module (its ``"reference"``, a module of the ``bench``
package with the contract of ``bench/reference.py``); each metric is read
by ``bench/metrics/<metric>.py``, whose ``read(run)`` returns the number
or None where the run holds nothing for it to read.

The timed path is the program's served entry, ``tracker.Tracker.step``:
one client's frame in, one pose out. Each client is a host thread that
submits its next frame once the previous pose is on the host (a closed
loop). The benchmark never chooses the objective's path: it builds the
``TrackerConfig`` from the configuration's sizes and leaves the rest to
the program.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, flops, generator, scopes, trace

ROOT = Path(__file__).resolve().parent.parent
SPEC = ROOT / "BENCHMARK.json"
BENCH = ROOT / "bench"
# A traced run's window ends after this many frames, split evenly over
# the clients, or at the run's seconds, whichever comes first: a count
# of frames, so that a faster program keeps the trace as large. On a TPU
# v5e the profiler keeps the device's events of about 970 frames of the
# 128x128 step and drops the rest, which would read as idle (the run
# fails where it did: ``check_trace_kept``); and writing the trace takes
# some 0.2 to 0.3 s a frame, inside the 360 s a traced run may take.
TRACE_FRAMES = 300


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    reference: types.ModuleType  # the configuration's hand model


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_file(traffic: str) -> Path:
    return BENCH / "traffic" / f"{traffic}.json"


def metric_file(metric: str) -> Path:
    return BENCH / "metrics" / f"{metric}.py"


def reference_module(config: dict) -> types.ModuleType:
    """The module that the configuration's ``"reference"`` file is, as a
    module of the ``bench`` package (``bench/reference.py`` is
    ``bench.reference``), refused where its pose width or sphere count is
    not the configuration's ``"hand"``."""
    path = Path(config["reference"])
    if path.suffix != ".py" or path.parts[0] != BENCH.name:
        raise ValueError(f"{config['name']}: reference {path} is not a module under bench/")
    module = importlib.import_module(".".join(path.with_suffix("").parts))
    hand = config["hand"]
    if (module.NUM_PARAMS, module.NUM_SPHERES) != (hand["num_params"], hand["num_spheres"]):
        raise ValueError(
            f"{config['name']}: hand {hand} but {module.__name__} has "
            f"{module.NUM_PARAMS} parameters and {module.NUM_SPHERES} spheres")
    return module


def load_cell(name: str, spec_path: Path = SPEC) -> Cell:
    """The cell of that name, with its configuration and traffic loaded."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path.name}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((spec_path.parent / configs[w["config"]]["file"]).read_text())
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=json.loads(traffic_file(w["traffic"]).read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        reference=reference_module(config),
    )


def load_reader(metric: str):
    """``read`` of bench/metrics/<metric>.py."""
    path = metric_file(metric)
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def tracker_config(config: dict):
    """The program's TrackerConfig at the configuration file's sizes."""
    from repro.core import pso, tracker
    from repro.core.camera import Camera

    return tracker.TrackerConfig(
        camera=Camera(**config["camera"]),
        pso=pso.PSOConfig(**config["pso"]),
        **config["tracker"],
    )


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""

    setup_s: float
    client_frames: list  # frames completed per client in the window
    client_seconds: list  # per client: window start to its last completion
    latencies_s: np.ndarray  # every window frame, submit to pose on the host
    frame_flops: float
    peaks: dict
    trace: trace.TraceSummary | None = None
    scopes: scopes.ScopeSummary | None = None  # of the program's scopes and spans

    @property
    def frames(self) -> int:
        return sum(self.client_frames)


@dataclasses.dataclass
class _Client:
    """One client's closed loop and what it recorded."""

    seq: generator.ClientSequence
    trk: object
    poses: list = dataclasses.field(default_factory=list)  # pose on the host per frame
    scores: list = dataclasses.field(default_factory=list)
    indices: list = dataclasses.field(default_factory=list)  # sequence index per frame
    latencies: list = dataclasses.field(default_factory=list)
    loop_gaps: list = dataclasses.field(default_factory=list)  # pose on host to next submit
    last_done: float = 0.0
    failed: int = 0

    def track(self, k: int) -> tuple[float, float]:
        """Track the client's k-th frame. Returns the moments the frame was
        submitted to the tracker and its pose was on the host."""
        with jax.profiler.TraceAnnotation("fetch"):
            i = self.seq.index(k)
            frame = self.seq.frames[i]
        with jax.profiler.TraceAnnotation("step"):
            submitted = time.perf_counter()
            h, score = self.trk.step(frame)
            pose = np.asarray(h)
            done = time.perf_counter()
        if not (math.isfinite(score) and np.all(np.isfinite(pose))):
            self.failed += 1
        self.poses.append(pose)
        self.scores.append(score)
        self.indices.append(i)
        return submitted, done

    def loop(self, first: int, t_end: float, count: int | None = None) -> None:
        """Closed loop from frame ``first`` until a pose lands after t_end,
        or until ``count`` frames are done."""
        k, last_done = first, None
        while True:
            submitted, done = self.track(k)
            self.latencies.append(done - submitted)
            if last_done is not None:
                self.loop_gaps.append(submitted - last_done)
            last_done = done
            k += 1
            if done >= t_end or k - first == count:
                self.last_done = done
                return

    def tracked(self) -> dict:
        idx = np.asarray(self.indices)
        return {
            "poses": np.concatenate([self.seq.truth[:1], np.stack(self.poses)]),
            "scores": np.asarray(self.scores),
            "frames": self.seq.frames[idx],
            "truth": self.seq.truth[idx],
        }


class CompileCounter:
    """Counts JAX traces and compiles while ``active``."""

    PREFIX = "/jax/core/compile"

    def __init__(self):
        self.count = 0
        self.active = False
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event.startswith(self.PREFIX):
            with self._lock:
                self.count += 1


def peak_memory_bytes(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    peaks = [s["peak_bytes_in_use"] for s in stats if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def compiled_step(cfg, client: _Client):
    """The client's frame step compiled again after the window: a load
    from the persistent cache of the program the window ran."""
    from repro.core import tracker

    frame = client.seq.frames[0]
    return tracker.make_track_frame(cfg).lower(client.trk.key, client.trk.h, frame).compile()


def compiled_memory(compiled) -> dict | None:
    """What the compiled frame step holds on the device, by XLA's own
    account: its arguments, outputs, temporaries and code. The peak of the
    process also holds the benchmark's inputs; this is the tracker's part."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "code_bytes": m.generated_code_size_in_bytes,
    }


def read_trace(trace_dir: Path, hlo_text: str):
    """``trace.reduce`` and ``scopes.reduce`` of the trace the profiler
    wrote under trace_dir, the program's scopes taken from the compiled
    step's HLO text; (None, None) where it wrote none."""
    from jax.profiler import ProfileData

    path = trace.find_xplane(trace_dir)
    if path is None:
        return None, None
    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    return trace.reduce(planes), scopes.reduce(planes, scopes.op_scopes(hlo_text))


def check_trace_kept(scope_summary: scopes.ScopeSummary | None, frames: int) -> None:
    """Raise where the trace lost frame steps of the window: the profiler
    dropped device events, and every device reading would count them as
    idle."""
    if scope_summary is not None and scope_summary.step_runs < frames:
        raise RuntimeError(
            f"the trace kept the device operations of {scope_summary.step_runs} of the "
            f"window's {frames} frame steps: the profiler dropped events, so its "
            f"device readings would be wrong; trace fewer frames (TRACE_FRAMES)")


def profile_options() -> jax.profiler.ProfileOptions:
    """Device operations and the benchmark's own spans; no Python tracer."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return options


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             process_start: float) -> dict:
    """One run of the cell; returns the result line as a dict.

    ``process_start`` is the ``time.perf_counter()`` reading at which the
    process started."""
    from repro.core import tracker
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache_dir}")

    devices = jax.devices()[: cell.chips]
    dev = devices[0]
    peaks = flops.peaks(dev.device_kind) if dev.platform == "tpu" else {}
    cfg = tracker_config(cell.config)
    log(f"setup: devices at {time.perf_counter() - process_start:.3f} s")
    seqs = generator.build(cell.reference, cell.config["camera"], cell.traffic, seed)
    log(f"setup: inputs at {time.perf_counter() - process_start:.3f} s")
    clients = [
        _Client(s, tracker.Tracker(cfg, h0=jnp.asarray(s.truth[0]), seed=s.seed))
        for s in seqs
    ]
    warm = cell.traffic["warmup_frames"]
    for c in clients:
        for k in range(1, warm + 1):
            c.track(k)

    count = math.ceil(TRACE_FRAMES / len(clients)) if traced else None
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if traced else None
    try:
        if traced:
            jax.profiler.start_trace(str(trace_dir), profiler_options=profile_options())
        counter.active = True
        t_start = time.perf_counter()
        setup_s = t_start - process_start
        with jax.profiler.TraceAnnotation("bench_window"):
            with concurrent.futures.ThreadPoolExecutor(len(clients)) as pool:
                futures = [pool.submit(c.loop, warm + 1, t_start + seconds, count)
                           for c in clients]
                for f in futures:
                    f.result()
        counter.active = False
        if traced:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: written in {time.perf_counter() - t_stop:.3f} s")
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)

    memory_peak = peak_memory_bytes(devices)
    step = compiled_step(cfg, clients[0])
    step_memory = compiled_memory(step)
    summary = scope_summary = None
    if traced:
        t_read = time.perf_counter()
        summary, scope_summary = read_trace(trace_dir, step.as_text())
        log(f"trace: read and reduced in {time.perf_counter() - t_read:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        check_trace_kept(scope_summary, sum(len(c.latencies) for c in clients))
    del step

    run = Run(
        setup_s=setup_s,
        client_frames=[len(c.latencies) for c in clients],
        client_seconds=[c.last_done - t_start for c in clients],
        latencies_s=np.concatenate([np.asarray(c.latencies) for c in clients]),
        frame_flops=flops.frame_flops(cell.config),
        peaks=peaks,
        trace=summary,
        scopes=scope_summary,
    )
    attempted = run.frames
    failed = sum(c.failed for c in clients)
    # The longest frame and the longest wait between a pose and the next
    # submit: a stall of the runtime shows in the first and in
    # frames_per_s, not in the latency percentiles.
    stalls = {
        "frame_ms_max": 1e3 * float(np.max(run.latencies_s)),
        "loop_gap_ms_max": 1e3 * max((g for c in clients for g in c.loop_gaps), default=0.0),
    }
    tracked = [c.tracked() for c in clients]
    for c in clients:
        c.trk = None  # free the program's state before the reference runs
    del clients

    numbers, detail = check.readings(cell.reference, cell.config, tracked)
    ok, checks = check.compare(numbers, cell.config["limits"])
    unchecked = {k: v for k, v in numbers.items() if k not in checks}
    unchecked.update(detail)
    correct = ok and failed == 0 and counter.count == 0

    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
        if scope_summary is not None:
            result["breakdown"]["device_scopes"] = scope_summary.device_scopes[:trace.TOP]
            result["breakdown"]["program_gaps"] = scope_summary.program_gaps[:trace.TOP]
    result.update({
        "workload": cell.name,
        "seed": seed,
        "frames": run.client_frames,
        "compiles_in_window": counter.count,
        "stalls": stalls,
        "step_memory": step_memory,
        "not_compared": unchecked,
        "checks": checks,
    })
    return result
