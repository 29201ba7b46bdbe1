"""Trace-scale replay: stream a synthesized Google-shaped trace through
the simulator without ever materializing the job list.

Builds a chunked `core.trace.synth_trace` cursor (hourly windows, each a
pure function of (seed, window index)), replays it with bounded streaming
metrics (`SimConfig(streaming_metrics=True)`), and prints the paper's §6
summary metrics. The paper-scale run is
``--machines 12500 --hours 24`` (see benchmarks/trace_scale.py for the
committed peak-RSS / wall gates at that size); the defaults replay a
2-pod cluster for 30 minutes so the example finishes in seconds.

Run:  PYTHONPATH=src python examples/replay_trace.py
      PYTHONPATH=src python examples/replay_trace.py --machines 1536 --hours 2

To replay a slice of the real Google cluster-data v2 trace instead, point
`core.trace.CsvTraceCursor` at local ``task_events`` CSV shards.
"""

import argparse

from repro.core import latency, topology
from repro.core.simulator import SimConfig, Simulator
from repro.core.trace import synth_trace
from repro.runtime import enable_compilation_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--machines", type=int, default=768)
    ap.add_argument("--hours", type=float, default=0.5)
    ap.add_argument("--policy", default="random",
                    help="nomora | random | load_spreading | ...")
    ap.add_argument("--utilisation", type=float, default=0.6)
    ap.add_argument("--window-s", type=int, default=3600)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    duration_s = int(args.hours * 3600)
    topo = topology.Topology(
        n_machines=args.machines, machines_per_rack=48, racks_per_pod=16,
        slots_per_machine=8,
    )
    print(f"=== trace replay: {args.machines} machines, {duration_s}s, "
          f"policy={args.policy} ===")
    plane = latency.LatencyPlane.synthesize(topo, duration_s=duration_s,
                                            seed=args.seed)
    cursor = synth_trace(
        topo, duration_s, seed=args.seed, window_s=args.window_s,
        target_utilisation=args.utilisation,
    )
    print(f"cursor: {cursor.n_windows} windows of {args.window_s}s, "
          f"~{cursor.n_jobs_hint} jobs / ~{cursor.n_tasks_hint} tasks expected")
    cfg = SimConfig(policy=args.policy, seed=args.seed, streaming_metrics=True)
    sim = Simulator(cursor, plane, cfg)
    metrics = sim.run()
    s = metrics.summary()
    print(f"admitted: {sim.jt.n} jobs / {sim.tt.n} tasks")
    for key in (
        "avg_app_perf_area", "jobs_measured", "tasks_placed", "rounds",
        "placement_latency_s_p50", "placement_latency_s_p90",
        "response_time_s_p50", "response_time_s_p90",
    ):
        print(f"  {key:28s} {s[key]:.4f}")


if __name__ == "__main__":
    enable_compilation_cache()
    main()
