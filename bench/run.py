"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` and the files it names under ``bench/``; per-metric
readers are ``bench/metrics/<name>.py`` (or ``<name before the first
dot>.py``), each with ``read(observation) -> float | None``.

A run builds the cluster, the latency plane and the jobs from ``--seed``,
fills the cluster, warms every round program the window can reach (all
of that is ``setup_s``), measures for ``--seconds``, then checks a
seed-drawn sample of the window's rounds against the plain reference
(`check.py`). ``--trace 1`` runs the same window under the JAX profiler
with the program's telemetry on and reports the per-layer metrics.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in
a traced run), with the compared numbers and their limits last under
``checks``. The run refuses anything but a TPU, and fewer chips than the
cell asks for, with a non-zero exit and no result line. JAX's persistent
compile cache is ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
KERNELS = ("costmap_pallas", "bid_top2_pallas")


class Refused(RuntimeError):
    """The run cannot measure here (no TPU, too few chips, no program)."""


@dataclasses.dataclass
class Observation:
    """What the metric readers read."""

    run: object  # harness.Run (host clock)
    setup_s: float
    n_machines: int
    peak: Optional[dict] = None
    spans: Optional[list] = None  # program spans of the window (traced run)
    counters: Optional[dict] = None  # program counters of the window
    device: Optional[dict] = None  # trace_reduce.reduce of the window


def reader(name: str):
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py for metric {name!r}")


def configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # Every program of a cell stays cached: no size cap from the host's
    # environment evicts the big window programs between runs.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(
            f"needs a TPU, but JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); the benchmark has no CPU path"
        )
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chip(s); JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _imports():
    sys.path[:0] = [p for p in (BENCH_DIR, os.path.join(ROOT, "src")) if p not in sys.path]
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no scheduler package at {os.path.join(ROOT, 'src')}")


def measure(cell, seed: int, seconds: float, trace: bool, compiles,
            backend_factory=None, window_ctx=contextlib.nullcontext,
            t_start: float = T_START):
    """Set up one cell from ``seed`` and run its window (inside
    ``window_ctx()``); returns ``(bench, run, setup_s)``."""
    import harness

    bench = harness.Bench(cell, seed, seconds, trace=trace, backend_factory=backend_factory)
    t_built = time.perf_counter()
    fill = bench.fill()
    t_filled = time.perf_counter()
    n_warm = bench.warm()
    # Set-up's objects (the job lists, the filled tables) are collected
    # once and frozen, so no full collection walks them inside the window.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    say(f"[setup] {setup_s:.2f} s: {bench.topo.n_machines} machines; start and build "
        f"{t_built - t_start:.2f} s; fill {t_filled - t_built:.2f} s "
        f"({len(bench.fill_jobs)} jobs, {fill['running_tasks']} tasks running, "
        f"{fill['pending_tasks']} pending); warm-up {time.perf_counter() - t_filled:.2f} s "
        f"({n_warm} round programs; {compiles.total} programs built so far, "
        f"persistent cache {compiles.hits} hits / {compiles.misses} misses); "
        f"{len(bench.window_jobs)} window jobs")
    with window_ctx():
        run = bench.run_window(compiles)
    say(f"[window] {run.window_s:.3f} s, {run.rounds} rounds, {run.sim_s:.1f} simulated s, "
        f"{len(bench.log.rounds)} solver rounds logged, "
        f"compiles in window: {run.compiles_in_window}; buckets (tasks, jobs): rounds "
        f"{dict(sorted(bench.buckets_used().items()))}")
    return bench, run, setup_s


def compare(bench, seed: int, precision: Optional[str] = None, against: str = "tables") -> dict:
    """The seed-drawn sample of the window's rounds against the reference,
    computed in ``precision`` (default: the configuration's)."""
    import check
    import reference as ref

    cell, tr = bench.cell, bench.cell.traffic
    picks = check.sample_rounds(
        bench.log.rounds, seed, int(tr["check_rounds"]), int(tr.get("check_migration_rounds", 0))
    )
    cluster = ref.Cluster.from_dict(cell.config["topology"])
    policy = ref.Policy.from_dict({**cell.config["solver"], **bench.policy})
    stated = cell.config["solver"]["precision"]
    return check.compare(bench.log.rounds, picks, cluster, policy, bench.plane.series,
                         bench.plane_seed, precision or stated, against, stated)


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    require_tpu: bool = True, backend_factory=None, cell=None,
) -> dict:
    """One run of one cell (``cell`` overrides the lookup of ``workload``
    in ``BENCHMARK.json``); returns the result object (the last line)."""
    _imports()
    import numpy as np

    import harness
    import kernel_cost
    import trace_reduce

    cell = cell if cell is not None else harness.Cell.load(ROOT, workload)
    jax = configure_jax()
    dev = device_info(jax, cell.chips, require_tpu)
    peak = kernel_cost.load_peak(dev["kind"]) if require_tpu else None
    compiles = harness.CompileCounter()
    from repro import obs

    trace_dir = os.path.join(ROOT, ".bench_runs", "trace")

    @contextlib.contextmanager
    def traced():
        """The window alone runs under the profiler and the program's
        telemetry; set-up runs with both off."""
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.set_enabled(True)
        obs.reset()
        jax.profiler.start_trace(trace_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            obs.set_enabled(False)

    bench, run, setup_s = measure(
        cell, seed, seconds, trace, compiles, backend_factory,
        traced if trace else contextlib.nullcontext,
    )
    spans = counters = device_red = None
    if trace:
        spans = list(obs.get().spans)
        counters = obs.counters()
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    if run.generator_lag_ms is not None and len(run.generator_lag_ms):
        lag = run.generator_lag_ms
        say(f"[generator] late by p50 {np.percentile(lag, 50):.3f} ms, "
            f"p99 {np.percentile(lag, 99):.3f} ms, max {lag.max():.3f} ms "
            f"over {len(lag)} wake-ups")
    if trace:
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            device_red = trace_reduce.reduce(trace_reduce.load_events(path), KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if device_red is not None:
            dev["busy_s"] = device_red["busy_s"]
            dev["window_s"] = device_red["window_s"]
            say(f"[trace] busy {device_red['busy_s']:.4f} s of {device_red['window_s']:.4f} s; "
                f"kernels {device_red['kernel_s']} calls {device_red['kernel_calls']}")

    # The program's state is released before the reference runs.
    del bench.backend, bench.sim.backend
    t_ref = time.perf_counter()
    res = compare(bench, seed)
    say(f"[check] {res['rounds']} rounds ({res['migration_rounds']} migration, "
        f"{res['tasks']} tasks) vs the plain reference in {time.perf_counter() - t_ref:.2f} s: "
        f"{res['mismatched_tasks']} mismatched tasks"
        + (f", first in logged round {res['first_bad_round']}" if res["mismatched_tasks"] else ""))

    tr = cell.traffic
    checks = {
        "mismatched_tasks": {"value": res["mismatched_tasks"], "max": 0},
        "rounds_compared": {"value": res["rounds"], "min": int(tr["check_min_rounds"])},
    }
    if tr.get("check_migration_rounds"):
        checks["migration_rounds"] = {"value": res["migration_rounds"], "min": 1}
    if run.mode == "serve":
        checks["unplaced_tasks"] = {"value": run.failed, "max": 0}
    correct = all(
        c["value"] <= c["max"] if "max" in c else c["value"] >= c["min"]
        for c in checks.values()
    )

    o = Observation(run=run, setup_s=setup_s, n_machines=bench.topo.n_machines,
                    peak=peak, spans=spans, counters=counters, device=device_red)
    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        v = reader(m["name"])(o)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": dev,
    }
    if trace and device_red is not None:
        result["breakdown"] = {
            "device_ops": device_red["device_ops"],
            "idle_gaps": device_red["idle_gaps"],
        }
    for name, c in checks.items():
        bound = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        say(f"check {name} {c['value']} {bound}")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except Refused as e:
        say(f"refused: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
