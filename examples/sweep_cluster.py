"""Multi-scenario scheduling sweep: every policy under every perturbation.

Runs the (policy x seed x scenario) grid from `repro.core.sweep` on a
small simulated data center — baseline replay, preemption, machine-failure
bursts, straggler-heavy, and hotspot-latency scenarios — and prints the
average-application-performance table (the paper's Fig. 5 metric, one
column per policy). The grid shares one latency plane; scenario
perturbations derive cached copies.

Run:  PYTHONPATH=src python examples/sweep_cluster.py
Optionally save the full JSON:  ... sweep_cluster.py /tmp/sweep.json
Shard the grid across processes:  REPRO_SWEEP_WORKERS=4 ... sweep_cluster.py
"""

import os
import sys

from repro.core.scenarios import SCENARIOS
from repro.core.sweep import SweepSpec, run_sweep
from repro.runtime import enable_compilation_cache


def main() -> None:
    spec = SweepSpec(
        n_machines=128,
        machines_per_rack=16,
        racks_per_pod=4,
        duration_s=240,
        policies=("random", "load_spreading", "nomora"),
        seeds=(0, 1),
        scenarios=tuple(SCENARIOS),
    )
    n = len(spec.cells())
    print(f"=== sweep: {n} cells on {spec.n_machines} machines ===")
    for name, s in SCENARIOS.items():
        print(f"  {name:18s} {s.description}")
    workers = int(os.environ.get("REPRO_SWEEP_WORKERS", "1"))
    result = run_sweep(spec, progress=print, workers=workers)
    print()
    print("average application performance area (%, higher is better):")
    print(result.table("avg_app_perf_area"))
    print()
    print("p90 placement latency (s):")
    print(result.table("placement_latency_s_p90"))
    print(f"\nsweep wall time: {result.wall_s:.1f}s")
    if len(sys.argv) > 1:
        result.save(sys.argv[1])
        print(f"saved JSON to {sys.argv[1]}")


if __name__ == "__main__":
    enable_compilation_cache()
    main()
