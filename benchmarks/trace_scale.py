"""Trace-scale replay gate: peak RSS + wall clock (ISSUE-3 acceptance).

Replays a synthesized Google-shaped trace (`core.trace.synth_trace`,
chunked windows — the job list is never materialized) through the
vectorized simulator with streaming metrics
(`SimConfig(streaming_metrics=True)`, bounded accumulators instead of
full in-memory series) and asserts the replay stays under a committed
peak-RSS and wall-clock gate.

The replay runs in a **subprocess** so ``ru_maxrss`` measures this replay
alone, not whatever benchmark ran earlier in the harness process. On an
accelerator that child needs the chip, which one process holds at a time,
so the parent must not have touched JAX's devices (`_run_child` refuses
otherwise). Run it on its own with

    PYTHONPATH=src python -m benchmarks.trace_scale

(whose parent never imports the scheduler); `benchmarks/run.py` runs it
before importing any other module. The
paper-scale configuration (``REPRO_BENCH_SCALE=paper``) is the paper's
evaluation setup: 12,500 machines (48/rack, 16 racks/pod), 24h, 0.6 slot
utilisation — ~10^5 jobs / ~10^6 tasks admitted from hourly windows. The
default ``small`` scale replays 2h on 1,536 machines so the gate runs in
the 1-core container harness; gates are committed per scale.

Results land in benchmarks/results/trace_scale.json; regenerate
deliberately before committing.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SCALE = os.environ.get("REPRO_BENCH_SCALE", "small")

# Per-scale result files: the committed ``small`` baseline (the 1-core CI
# gate) is trace_scale.json; larger scales write alongside it instead of
# clobbering it, so paper-scale evidence and the CI gate can coexist.
RESULTS_PATH = os.path.join(
    os.path.dirname(__file__),
    "results",
    "trace_scale.json" if SCALE == "small" else f"trace_scale_{SCALE}.json",
)

# scale -> (machines, machines/rack, racks/pod, duration_s, utilisation,
#           peak-RSS gate MB, wall gate s). RSS gates are ~2x headroom over
# measured (streaming metrics keep the replay flat; an accidental return
# to exact series or a dense O(M^2) matrix blows straight through them).
CONFIGS = {
    "small": (1_536, 48, 16, 7_200, 0.6, 1_024, 300),
    "medium": (4_000, 48, 16, 21_600, 0.6, 1_536, 900),
    "paper": (12_500, 48, 16, 86_400, 0.6, 3_072, 3_600),
}

POLICY = "random"  # heuristic backend: the gate measures replay machinery,
# not solver cost (solver scaling is benchmarks/round_pipeline.py's claim)

# NoMora-policy trace cell (ROADMAP follow-up, unlocked by the persistent
# windowed round): the full cost-model + auction round per simulated
# second through ``backend="auction_windowed"``. Smaller M sweep than the
# replay-machinery gate — the paper's 12,500 at 24h does not fit the
# 1-core time box; the cell pins solver-in-the-loop replay cost and RSS
# at cluster scale rather than the paper's full grid.
NOMORA_BACKEND = "auction_windowed"
NOMORA_CONFIGS = {
    "small": (4_000, 48, 16, 3_600, 0.6, 2_048, 300),
    "medium": (8_000, 48, 16, 10_800, 0.6, 2_560, 1_500),
    "paper": (12_500, 48, 16, 21_600, 0.6, 3_072, 3_600),
}
WINDOW_S = 3_600
SEED = 42


def _child_main(payload: dict) -> None:
    """Run one replay and print a JSON result line (subprocess entry)."""
    import resource

    import numpy as np  # noqa: F401  (keep import cost inside the measurement)

    from repro.core import latency, topology
    from repro.core.simulator import SimConfig, Simulator
    from repro.core.trace import synth_trace

    topo = topology.Topology(
        n_machines=payload["machines"],
        machines_per_rack=payload["mpr"],
        racks_per_pod=payload["rpp"],
        slots_per_machine=8,
    )
    t0 = time.perf_counter()
    plane = latency.LatencyPlane.synthesize(
        topo, duration_s=payload["duration_s"], seed=SEED
    )
    plane_s = time.perf_counter() - t0
    cursor = synth_trace(
        topo,
        payload["duration_s"],
        seed=SEED,
        window_s=WINDOW_S,
        target_utilisation=payload["util"],
    )
    cfg = SimConfig(
        policy=payload.get("policy", POLICY),
        backend=payload.get("backend"),
        seed=SEED,
        fixed_algo_s=0.0,
        streaming_metrics=True,
    )
    if payload.get("obs"):
        # Instrumented replay: deterministic counters ride back in the
        # result line as the cell's ``telemetry`` section. The wall gates
        # have ample headroom for the <5% instrumented overhead
        # (benchmarks/obs_overhead.py pins the bound).
        from repro import obs

        obs.set_enabled(True)
        obs.reset()
    t0 = time.perf_counter()
    sim = Simulator(cursor, plane, cfg)
    metrics = sim.run()
    replay_s = time.perf_counter() - t0
    summary = metrics.summary()
    telemetry = None
    if payload.get("obs"):
        telemetry = obs.deterministic_counters(obs.counters())
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # ru_maxrss is kilobytes on Linux but bytes on macOS.
    peak_mb = peak / 1024.0**2 if sys.platform == "darwin" else peak / 1024.0
    print(
        json.dumps(
            {
                "peak_rss_mb": peak_mb,
                "plane_s": plane_s,
                "replay_s": replay_s,
                "jobs_admitted": int(sim.jt.n),
                "tasks_admitted": int(sim.tt.n),
                "tasks_placed": int(summary["tasks_placed"]),
                "rounds": int(summary["rounds"]),
                "avg_app_perf_area": summary["avg_app_perf_area"],
                "response_time_s_p90": summary["response_time_s_p90"],
                "telemetry": telemetry,
            }
        )
    )


def _run_child(payload: dict) -> dict:
    from repro.runtime import require_chip_free

    require_chip_free("trace_scale")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.trace_scale", "--child", json.dumps(payload)],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if out.returncode != 0:
        # Surface the child's traceback (an OOM kill or import error would
        # otherwise reach the harness as a bare CalledProcessError).
        raise RuntimeError(
            f"trace replay child exited {out.returncode}:\n{out.stderr}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _run_cell(name, configs, policy, backend, obs_on=False):
    machines, mpr, rpp, duration_s, util, rss_gate_mb, wall_gate_s = configs[SCALE]
    payload = {
        "machines": machines,
        "mpr": mpr,
        "rpp": rpp,
        "duration_s": duration_s,
        "util": util,
    }
    if policy != POLICY:
        payload["policy"] = policy
    if backend is not None:
        payload["backend"] = backend
    if obs_on:
        payload["obs"] = True
    res = _run_child(payload)
    rss_ok = res["peak_rss_mb"] <= rss_gate_mb
    wall_ok = res["replay_s"] <= wall_gate_s
    label = policy if backend is None else f"{policy}:{backend}"
    return {
        "cell": name,
        "config": payload
        | {"policy": label, "window_s": WINDOW_S, "seed": SEED},
        "gates": {"peak_rss_mb": rss_gate_mb, "replay_wall_s": wall_gate_s},
        "measured": res,
        "rss_gate_ok": rss_ok,
        "wall_gate_ok": wall_ok,
    }


def run():
    cells = [
        _run_cell("replay_machinery", CONFIGS, POLICY, None),
        # The solver-in-the-loop cell replays instrumented: its result's
        # ``telemetry`` section pins the solver/round counter profile at
        # trace scale (the RSS/wall gates keep their headroom — the
        # instrumented overhead bound is benchmarks/obs_overhead.py's).
        _run_cell(
            "nomora_policy", NOMORA_CONFIGS, "nomora", NOMORA_BACKEND,
            obs_on=True,
        ),
    ]
    result = {"scale": SCALE, "cells": cells}
    with open(RESULTS_PATH, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    rows = []
    for cell in cells:
        res, cfg = cell["measured"], cell["config"]
        rows.append(
            (
                f"trace_replay_{cell['cell']}_{cfg['machines']}m_{cfg['duration_s']}s",
                res["replay_s"] * 1e6,
                f"policy={cfg['policy']};peak_rss_mb={res['peak_rss_mb']:.0f};"
                f"gate_mb={cell['gates']['peak_rss_mb']};"
                f"tasks={res['tasks_placed']};jobs={res['jobs_admitted']}",
            )
        )
    # Gates asserted after the JSON lands so a miss keeps the measurements.
    for cell in cells:
        res = cell["measured"]
        assert cell["rss_gate_ok"], (
            f"{cell['cell']} peak RSS {res['peak_rss_mb']:.0f}MB exceeds the "
            f"{cell['gates']['peak_rss_mb']}MB gate — a full series/event "
            "list is back in memory?"
        )
        assert cell["wall_gate_ok"], (
            f"{cell['cell']} took {res['replay_s']:.0f}s "
            f"(gate {cell['gates']['replay_wall_s']}s)"
        )
    return rows


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        _child_main(json.loads(sys.argv[2]))
    else:
        for name, us, derived in run():
            print(f"{name},{us:.1f},{derived}")
