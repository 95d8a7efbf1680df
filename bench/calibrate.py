"""Readings that the limits of ``correct`` are set from: the program's, the
control's and the planted faults', over many seeds, in one process.

    python3 bench/calibrate.py --workload paper128.solo --seconds 5 \
        --seeds 1 2 3 --faults control half_swarm --fault-seeds 4 5 6

The program runs on ``--seeds``; then each fault of ``bench/faults.py``
is planted in turn and runs on ``--fault-seeds``. A sound limit passes
every seed of the program and fails the control. The benchmark's own runs
never run this. Prints one JSON line per run.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench import faults

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[], choices=sorted(faults.FAULTS))
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    runs = [(None, s) for s in args.seeds]
    runs += [(f, s) for f in args.faults for s in args.fault_seeds]
    planted, undo = None, None
    for fault, seed in runs:
        if fault != planted:
            if undo:
                undo()
            undo = faults.plant(fault, cell.reference) if fault else None
            planted = fault
        r = harness.run_cell(cell, seed, args.seconds, False, PROCESS_START)
        print(json.dumps({
            "workload": cell.name, "fault": fault, "seed": seed,
            "correct": r["correct"], "attempted": r["attempted"], "failed": r["failed"],
            "frames": r["frames"], "step_memory": r["step_memory"],
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
            "not_compared": r["not_compared"], "checks": r["checks"],
        }), flush=True)
    if undo:
        undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
