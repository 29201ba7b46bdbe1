"""Export a Perfetto-loadable scheduler trace from an instrumented replay.

Replays the migration-controller end-to-end scenario (64-machine fat
tree, a drifting rack hotspot degrading jobs mid-run, the continuous
controller reacting through the what-if lanes) with the telemetry plane
enabled, then writes:

- ``scheduler_trace.json`` — Chrome trace-event JSON: one nested slice
  tree per scheduling round (``sim.round`` -> build_state / solver /
  apply / perf_sample phases, plus the fused window dispatch with its
  reconstructed per-round sub-slices) and counter tracks (queue depth,
  free slots, migrated %, degraded jobs, ...). Load it at
  https://ui.perfetto.dev or chrome://tracing.
- ``migration_audit.jsonl`` — the structured migration audit log: one
  record per controller round (degraded jobs, per-lane true costs,
  chosen lane, budget spend, reverts).

Run:  REPRO_OBS=1 PYTHONPATH=src python examples/export_trace.py [outdir]

(The script enables telemetry itself, so plain
``PYTHONPATH=src python examples/export_trace.py`` works too.)
"""

import os
import sys

from repro import obs
from repro.runtime import enable_compilation_cache
from repro.core import latency, simulator, topology, workload
from repro.core.policy import PolicyParams


def build_scenario():
    topo = topology.Topology(
        n_machines=64, machines_per_rack=8, racks_per_pod=4,
        slots_per_machine=4,
    )
    events = latency.LatencyEvents(
        hotspots=(
            latency.DriftingHotspot(
                start_s=30.0, end_s=220.0, rack0=0,
                drift_racks_per_s=8.0 / 240.0, width_racks=2,
                multiplier=6.0,
            ),
        )
    )
    plane = latency.LatencyPlane.synthesize(
        topo, duration_s=240, seed=0, events=events
    )
    wl = workload.synth_workload(
        topo, duration_s=240, seed=1, target_utilisation=0.35
    )
    cfg = simulator.SimConfig(
        policy="nomora", backend="auction_windowed", seed=11,
        migration_interval_s=15, migration_controller=True,
        qos_threshold=0.95, qos_window=2, qos_hold_s=30.0,
        whatif_betas=(0.0, 100.0 / 3600.0),
        params=PolicyParams(preemption=True, beta_scale=0.0),
    )
    return wl, plane, cfg


def main(outdir: str = ".") -> None:
    wl, plane, cfg = build_scenario()
    with obs.scope() as tel:
        metrics = simulator.Simulator(wl, plane, cfg).run()

        trace_path = os.path.join(outdir, "scheduler_trace.json")
        audit_path = os.path.join(outdir, "migration_audit.jsonl")
        obs.export.save_chrome_trace(trace_path, tel)
        n_audit = obs.export.save_audit_jsonl(audit_path, tel)

        doc = obs.export.to_chrome_trace(tel)
        problems = obs.export.validate_chrome_trace(doc)
        summary = obs.export.summarize(tel)

    s = metrics.summary()
    print(f"replay: {int(s['rounds'])} rounds, "
          f"{int(s['tasks_placed'])} tasks placed, "
          f"{int(s['tasks_migrated'])} migrated, "
          f"{int(s['controller_rounds'])} controller rounds")
    print(f"trace:  {trace_path} "
          f"({len(doc['traceEvents'])} events, "
          f"{len(obs.export.counter_track_names(doc))} counter tracks, "
          f"{'valid' if not problems else problems})")
    print(f"audit:  {audit_path} ({n_audit} controller-round records)")
    top = sorted(
        summary["spans"].items(), key=lambda kv: -kv[1]["total_s"]
    )[:8]
    for name, st in top:
        print(f"  span {name:35s} x{st['count']:<6d} {st['total_s']*1e3:9.2f} ms")
    if problems:
        sys.exit(1)


if __name__ == "__main__":
    enable_compilation_cache()
    main(sys.argv[1] if len(sys.argv) > 1 else ".")
