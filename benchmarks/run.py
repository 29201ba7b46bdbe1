"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Figures covered:
  Fig. 3  perf_models        - model fits (Eqs. 2-5) + cost mapping
  Fig. 5  placement_quality  - average application performance areas
  Fig. 6  algo_runtime       - solver runtime per round
  Fig. 7  migrations         - migrated-task percentage (preemption)
  (extra) migration_quality  - controller vs no-migration on dynamic planes
  Fig. 8  placement_latency  - submission -> placement latency (simulated)
  (extra) serving_latency    - wall-clock per-decision latency + saturation
  Fig. 9  response_time      - submission -> completion
  (extra) sweep_bench        - SoA engine speedup + multi-scenario sweep
  (extra) round_pipeline     - host-numpy vs fused on-device round
  (extra) trace_scale        - trace replay peak-RSS / wall gates
  (extra) kernel_bench       - scheduler kernel microbenchmarks
  (extra) obs_overhead       - telemetry-plane zero-cost/overhead gates

After the module sweep, `compare` diffs the fresh results JSONs against
the committed baselines snapshotted before the run and exits non-zero on
gated regressions (see benchmarks/compare.py for the gate table).

REPRO_BENCH_SCALE={small,medium,paper} controls simulation size.
"""

from __future__ import annotations

import sys
import time
import traceback


def run_modules(modules) -> list:
    """Run each ``(name, module)`` and print its CSV rows; returns the
    names of the modules whose ``run()`` raised (each prints an
    ``<name>_ERROR`` row and its traceback, and the sweep carries on)."""
    failed = []
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{name}_ERROR,0,{type(e).__name__}: {e}")
            traceback.print_exc()
            failed.append(name)
            continue
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.1f},{derived}")
        print(f"{name}_wall_s,{(time.time()-t0)*1e6:.0f},total", file=sys.stderr)
    return failed


def main() -> None:
    from repro.runtime import enable_compilation_cache

    from . import compare, trace_scale

    enable_compilation_cache()
    # The committed results are the regression baseline; the modules
    # overwrite them in place, so snapshot first.
    baseline_dir = compare.snapshot_results()
    print("name,us_per_call,derived")
    # trace_scale replays in a child process (so ru_maxrss is that replay
    # alone), and a child cannot reach a chip this process holds. Importing
    # the scheduler initialises JAX's backend, so trace_scale runs before
    # the other modules are imported.
    failed = run_modules([("trace_scale", trace_scale)])
    from . import (
        algo_runtime,
        kernel_bench,
        migration_quality,
        migrations,
        obs_overhead,
        perf_models,
        placement_latency,
        placement_quality,
        response_time,
        round_pipeline,
        serving_latency,
        sweep_bench,
    )

    failed += run_modules([
        ("perf_models", perf_models),
        ("placement_quality", placement_quality),
        ("algo_runtime", algo_runtime),
        ("migrations", migrations),
        ("migration_quality", migration_quality),
        ("placement_latency", placement_latency),
        ("serving_latency", serving_latency),
        ("response_time", response_time),
        ("sweep_bench", sweep_bench),
        ("round_pipeline", round_pipeline),
        ("kernel_bench", kernel_bench),
        ("obs_overhead", obs_overhead),
    ])
    csv_rows, regressions = compare.run(baseline_dir)
    for row_name, us, derived in csv_rows:
        print(f"{row_name},{us:.1f},{derived}")
    if failed:
        print(f"benchmark modules failed: {', '.join(failed)}", file=sys.stderr)
    if regressions or failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
