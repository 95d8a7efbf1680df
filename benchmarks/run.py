"""Benchmark harness. One module per paper table/figure + framework
tables. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only substring]
"""

from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    calibrate,
    edge_llm,
    fig3_framedrop,
    fig4_overhead,
    fig5_network,
    fleet_bench,
    kernel_bench,
    pso_throughput,
    roofline_bench,
    topology_bench,
)
from benchmarks.common import emit
from repro.launch import compile_cache

MODULES = [
    ("fig3", fig3_framedrop),
    ("fig4", fig4_overhead),
    ("fig5", fig5_network),
    ("pso", pso_throughput),
    ("kernel", kernel_bench),
    ("calibrate", calibrate),
    ("roofline", roofline_bench),
    ("edge_llm", edge_llm),
    ("topology", topology_bench),
    ("fleet", fleet_bench),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        try:
            emit(mod.bench())
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,exception", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    compile_cache.enable()
    main()
