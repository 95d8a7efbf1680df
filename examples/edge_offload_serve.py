"""END-TO-END DRIVER — the paper's main scenario, served.

    PYTHONPATH=src python examples/edge_offload_serve.py

A weak laptop client receives 30 fps RGBD frames and must hand-track in
real time. We *execute* the tracker (bit-exact JAX computation) for every
deployment the paper evaluates — native on both machines, wrapped, and
offloaded over Ethernet/Wi-Fi with Forced/Auto policies — while a
simulated clock charges network/wrapper/compute time and applies the
Fig. 3 frame-drop rule. Reproduces Figs. 4 and 5 and couples deployment
speed to tracking quality (dropped frames => wider search => worse
tracking), which the paper describes but could not quantify.
"""

import numpy as np

from repro.core import offload, pso, tracker
from repro.core.camera import Camera
from repro.core.offload import Policy
from repro.data import rgbd
from repro.launch import compile_cache
from repro.sim import hardware, runtime


def main() -> None:
    # Working resolution/budget trimmed so the full 12-deployment grid
    # executes in minutes on a laptop-class CPU; the *simulated* tiers
    # still model the paper's hardware (sim/hardware.py anchors).
    cam = Camera(width=48, height=48, fx=45.0, fy=45.0, cx=23.5, cy=23.5)
    seq_cfg = rgbd.SequenceConfig(num_frames=36, camera=cam, fast_burst=(18, 26))
    frames, truth = rgbd.render_sequence(seq_cfg)
    tcfg = tracker.TrackerConfig(
        camera=cam, pso=pso.PSOConfig(num_particles=32, num_generations=10),
        smoothing=0.0,
    )
    tiers = hardware.paper_tiers()

    print(f"{'deployment':44s} {'fps':>6s} {'drop%':>6s} {'pos_err_cm':>10s}")

    # clock charges the PAPER-scale workload; the reduced tracker runs
    # for quality measurement (see executed_run's timing_comp)
    paper_comp = hardware.paper_staged()

    def report(name, env, policy, gran):
        res = runtime.executed_run(
            tcfg, env, policy, frames, truth, gran, timing_comp=paper_comp
        )
        print(f"{name:44s} {res.sim.fps:6.1f} "
              f"{res.sim.stats.drop_rate * 100:6.1f} "
              f"{res.mean_pos_error * 100:10.2f}")

    # Fig. 4: local deployments
    for machine in ("server", "laptop"):
        for wrapped in (False, True):
            env = offload.Environment(
                client=tiers[machine], server=tiers["server"],
                link=hardware.links.GIGABIT_ETHERNET,
                wrapper=hardware.paper_wrapper(), wrapped=wrapped,
            )
            tag = "wrapped" if wrapped else "native"
            report(f"local/{machine}/{tag}", env, Policy.LOCAL, "single_step")

    # Fig. 5: offloaded deployments
    for net in ("gigabit_ethernet", "wifi_802.11"):
        env = hardware.paper_environment(net)
        for pol in (Policy.FORCED, Policy.AUTO):
            for gran in ("single_step", "multi_step"):
                report(f"offload/{net}/{pol.value}/{gran}", env, pol, gran)

    print("\npaper anchors: server native >40fps; laptop native ~13fps;"
          " forced+single+ethernet ~10fps; auto ~10-11fps everywhere")


if __name__ == "__main__":
    compile_cache.enable()
    main()
