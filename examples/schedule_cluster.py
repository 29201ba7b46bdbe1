"""Cluster-scheduling example: NoMora places a fleet of LM jobs, reacts to
a machine failure (re-placement = the paper's migration mechanism), and
emits NoMora-ordered host lists for JAX mesh construction.

Run:  PYTHONPATH=src python examples/schedule_cluster.py
"""

import numpy as np

from repro.core import latency, simulator, topology, workload
from repro.core.policy import PolicyParams
from repro.launch.schedule import ARCH_KIND, schedule_ml_jobs
from repro.runtime import enable_compilation_cache


def failure_demo():
    print("=== failure recovery via re-placement ===")
    topo = topology.Topology(
        n_machines=96, machines_per_rack=16, racks_per_pod=3, slots_per_machine=4
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=200, seed=3)
    jobs = [
        workload.ml_job(i, "qwen3-1.7b", "train", n_hosts=6, duration_s=180,
                        arrival_s=float(i))
        for i in range(6)
    ]
    wl = workload.Workload(jobs=jobs, duration_s=200, topo=topo)
    cfg = simulator.SimConfig(
        policy="nomora",
        params=PolicyParams(preemption=True, beta_scale=0.0),
        failures=((60, 0), (60, 1), (60, 2)),  # kill 3 machines at t=60
        migration_interval_s=20,
        seed=0,
    )
    sim = simulator.Simulator(wl, plane, cfg)
    m = sim.run()
    placed = [t for rec in sim.jobs.values() for t in rec.tasks if t.machine >= 0]
    on_dead = [t for t in placed if t.machine in sim.dead]
    print(f"  tasks running at end: {len(placed)}; on failed machines: {len(on_dead)}")
    print(f"  migrations (incl. failure recovery): {m.tasks_migrated}")
    assert not on_dead, "tasks must not remain on failed machines"


if __name__ == "__main__":
    enable_compilation_cache()
    print("=== NoMora-scheduled ML fleet ===")
    placements, metrics = schedule_ml_jobs(n_machines=128, n_jobs=8, duration_s=240)
    s = metrics.summary()
    print(f"  jobs: {len(placements)}; avg app perf area {s['avg_app_perf_area']:.1f}%")
    for jid, p in sorted(placements.items())[:4]:
        print(f"  job {jid} ({p['arch']}, {ARCH_KIND.get(p['arch'])}): "
              f"root m{p['root']}, mean RTT {p['mean_rtt_us']:.0f}us")
    failure_demo()
