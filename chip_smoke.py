"""Bring-up smoke run of the NoMora scheduler on one TPU chip.

Drives the scheduler's main path once, at the paper's Google-cluster scale
(12,500 machines, 48 per rack, 16 racks per pod, 8 slots per machine),
through the entry points a user calls, and checks what comes out:

1. device  - JAX's first device must be a TPU; anything else exits non-zero.
2. kernels - the costmap Pallas kernel equals the LUT reference exactly, for
             all four performance models, on the 10 us grid, every half-step
             and the floats on both sides of it, and out-of-range latencies;
             the auction-bid Pallas kernel equals the jnp reference exactly
             on seeded (1024, 12500) inputs (plus a tie-heavy draw).
3. replay  - `Simulator` on ``auction_windowed`` replays the first 300 s of
             the 24 h synthesized trace with preemption on; the compiled
             window program must call both kernels (``tpu_custom_call``).
4. parity  - at least 20 recorded replay rounds, one of them a migration
             round, re-solved through ``auction_host`` (LUT Eq. 6, jnp
             auction: no kernel) place every task identically.
5. serving - `ScheduleService` on the same topology drains, compiles nothing
             after warmup, and replays with 0 mismatches against
             ``auction_host``. Its decision p50/p99 is a smoke reading, not
             a benchmark.

Every phase prints one line with its result and wall time. Any failed check
exits non-zero with no result line; on success the last line of standard
output is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py

It runs in one process and starts no other: the chip belongs to one process
at a time. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``<repo>/.jax_cache`` (`repro.runtime.enable_compilation_cache`).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

SRC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# The paper's evaluation cluster (benchmarks/trace_scale.py "paper").
PAPER_TOPOLOGY = dict(
    n_machines=12_500, machines_per_rack=48, racks_per_pod=16, slots_per_machine=8
)
TRACE_S = 86_400  # the synthesized 24 h trace ...
REPLAY_S = 300  # ... of which the replay runs the first 300 s
SEED = 42
BID_SHAPE = (1024, 12_500)  # the largest round bucket at paper scale
PARITY_ROUNDS = 24  # replay rounds recorded for the parity phase
MIN_PARITY_ROUNDS = 20


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(phase: str, result: str, t0: float) -> None:
    print(f"[{phase}] {result} ({time.perf_counter() - t0:.1f} s)", flush=True)


# --------------------------------------------------------------------- #
# 1. device


def phase_device() -> dict:
    """The device JAX runs on; refuses anything but a TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    _check(
        dev.platform == "tpu",
        f"needs a TPU, but JAX's first device is {dev.platform!r} "
        f"({dev.device_kind}); this smoke run has no CPU path",
    )
    return info


# --------------------------------------------------------------------- #
# 2. kernels


def grid_latencies():
    """(L,) f32 latencies: the 10 us LUT grid, every half-step and its two
    float neighbours, and out-of-range values."""
    import numpy as np

    grid = np.arange(0, 1001, 10, dtype=np.float32)
    half = np.arange(5, 1000, 10, dtype=np.float32)
    below = np.nextafter(half, np.float32(-np.inf))
    above = np.nextafter(half, np.float32(np.inf))
    outside = np.asarray([-1.0, 1000.5, 5000.0], np.float32)
    return np.concatenate([grid, half, below, above, outside])


def _bid_inputs(rng, shape, ties: bool):
    """Integer-valued f32 values and slot prices, as the solver makes them.
    ``ties`` draws from a narrow range so most rows have tied maxima."""
    import numpy as np

    T, C = shape
    hi = 16 if ties else 2**20
    values = rng.integers(-hi, 0, size=(T, C)).astype(np.float32)
    price1 = rng.integers(0, 2 if ties else 2**16, size=C).astype(np.float32)
    price2 = np.maximum(
        price1, rng.integers(0, 4 if ties else 2**17, size=C)
    ).astype(np.float32)
    return values, price1, price2


def phase_kernels(*, bid_shape=BID_SHAPE, seed: int = 0, interpret: bool = False):
    """Both scheduler kernels against their references; exact equality."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import perf_model
    from repro.kernels.auction_bid import kernel as bid_kernel
    from repro.kernels.auction_bid import ref as bid_ref
    from repro.kernels.costmap import kernel as cm_kernel
    from repro.kernels.costmap import ref as cm_ref

    n_models = len(perf_model.APP_MODEL_LIST)
    lat_row = grid_latencies()
    # Two rows per model fill the smallest round bucket (8 tasks).
    perf_idx = np.tile(np.arange(n_models, dtype=np.int32), 2)
    lat = np.tile(lat_row, (len(perf_idx), 1))
    got = np.asarray(
        cm_kernel.costmap_pallas(
            jnp.asarray(perf_idx), jnp.asarray(lat), interpret=interpret
        )
    )
    want = np.asarray(
        jax.jit(cm_ref.costmap_ref)(
            perf_model.perf_lut_table(), jnp.asarray(perf_idx), jnp.asarray(lat)
        )
    )
    bad = np.argwhere(got != want)
    _check(
        len(bad) == 0,
        "costmap kernel != LUT reference at "
        + "; ".join(
            f"{perf_model.APP_MODEL_LIST[perf_idx[r]].name} "
            f"lat={lat[r, c]!r}: {got[r, c]} vs {want[r, c]}"
            for r, c in bad[:8]
        )
        + f" ({len(bad)} cells)",
    )

    rng = np.random.default_rng(seed)
    bid_ref_jit = jax.jit(bid_ref.bid_top2_ref)
    bid_rows = {}
    for name, ties in (("seeded", False), ("ties", True)):
        args = [jnp.asarray(a) for a in _bid_inputs(rng, bid_shape, ties)]
        got = [
            np.asarray(x)
            for x in bid_kernel.bid_top2_pallas(*args, interpret=interpret)
        ]
        want = [np.asarray(x) for x in bid_ref_jit(*args)]
        rows = np.zeros(bid_shape[0], bool)
        for g, w in zip(got, want):
            rows |= g != w
        _check(
            not rows.any(),
            f"bid_top2 kernel != jnp reference on {int(rows.sum())} of "
            f"{bid_shape[0]} rows ({name} draw), first row {int(np.argmax(rows))}",
        )
        bid_rows[name] = bid_shape[0]
    return {
        "costmap_cells": int(lat.size),
        "latencies": int(lat_row.size),
        "models": n_models,
        "bid_rows": bid_rows,
    }


# --------------------------------------------------------------------- #
# 3. replay


class TracePrefix:
    """The first ``until_s`` seconds of a trace cursor, as a workload the
    simulator replays (the stream stops at the first later arrival)."""

    def __init__(self, cursor, until_s: int):
        self.topo = cursor.topo
        self.duration_s = int(until_s)
        self._cursor = cursor
        # Preallocation hints only; the simulator's tables grow on demand.
        self.n_jobs_hint = 8_192
        self.n_tasks_hint = 65_536

    @property
    def jobs(self):
        for job in self._cursor.jobs:
            if job.arrival_s >= self.duration_s:
                return
            yield job


def _kernel_calls(hlo_text: str) -> dict:
    """Which scheduler kernels a compiled program calls as TPU custom calls."""
    calls = [
        line
        for line in hlo_text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    return {
        "costmap": any("costmap_pallas" in line for line in calls),
        "auction_bid": any("bid_top2_pallas" in line for line in calls),
    }


def phase_replay(
    topo_kwargs=PAPER_TOPOLOGY,
    *,
    trace_s: int = TRACE_S,
    replay_s: int = REPLAY_S,
    record_rounds: int = PARITY_ROUNDS,
    interpret: bool = False,
):
    """Replay the trace prefix through the windowed device backend.

    Returns ``(info, sim, records)``; ``records`` are the first
    ``record_rounds`` solver rounds (exact inputs + placed columns)."""
    import jax

    from repro import obs
    from repro.core.latency import LatencyPlane
    from repro.core.policy import PolicyParams
    from repro.core.scheduler_backend import WindowedAuctionBackend
    from repro.core.serving import RoundRecorder
    from repro.core.simulator import SimConfig, Simulator
    from repro.core.topology import Topology
    from repro.core.trace import synth_trace

    topo = Topology(**topo_kwargs)
    plane = LatencyPlane.synthesize(topo, duration_s=trace_s, seed=SEED)
    cursor = synth_trace(
        topo, trace_s, seed=SEED, window_s=3600, target_utilisation=0.6
    )
    cfg = SimConfig(
        backend="auction_windowed",
        params=PolicyParams(preemption=True),
        seed=SEED,
        fixed_algo_s=0.0,
        streaming_metrics=True,
    )
    sim = Simulator(TracePrefix(cursor, replay_s), plane, cfg)
    if interpret:
        # Kernel bodies on the CPU, for rehearsals; the chip path leaves
        # kernel selection to the backend (Pallas on a TPU).
        sim.backend = WindowedAuctionBackend(
            cfg.params, topo, sim.lut, use_pallas=True, interpret=True
        )
    windowed = sim.backend
    recorder = RoundRecorder(windowed, record_rounds)
    sim.backend = recorder

    with obs.scope():
        t0 = time.perf_counter()
        metrics = sim.run()
        wall_s = time.perf_counter() - t0
        counters = obs.counters()
    compile_s = counters.get("jit.backend_compile_s", 0.0)

    # The smallest bucket the replay compiled: its R=1 window program is
    # the one every small round runs.
    key = min(windowed.programs)
    hlo = windowed.programs[key].lower_window().compile().as_text()
    calls = _kernel_calls(hlo)
    if not interpret:
        _check(
            all(calls.values()),
            f"compiled window program (bucket {key[0]} tasks) lacks a "
            f"tpu_custom_call for {[k for k, v in calls.items() if not v]}: "
            f"a jnp fallback ran instead of the Pallas kernel",
        )
    stats = jax.devices()[0].memory_stats() or {}
    info = {
        "machines": topo.n_machines,
        "simulated_s": replay_s,
        "rounds": int(metrics.rounds),
        "tasks_placed": int(metrics.tasks_placed),
        "tasks_migrated": int(metrics.tasks_migrated),
        "auction_iterations": int(counters.get("auction.iterations", 0)),
        "buckets": sorted({k[0] for k in windowed.programs}),
        "kernel_calls": calls,
        "compiles": int(counters.get("jit.backend_compiles", 0)),
        "compile_s": float(compile_s),
        "replay_wall_s": float(wall_s),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    return info, sim, recorder.records


# --------------------------------------------------------------------- #
# 4. parity


def phase_parity(records, sim, *, min_rounds: int = MIN_PARITY_ROUNDS) -> dict:
    """Re-solve recorded rounds through ``auction_host``; 0 mismatches."""
    from repro.core.serving import replay_mismatches

    n_migration = sum(bool((s.cur_machine >= 0).any()) for s, _ in records)
    _check(
        len(records) >= min_rounds,
        f"only {len(records)} rounds recorded (need {min_rounds})",
    )
    _check(n_migration >= 1, "no migration round among the recorded rounds")
    mismatches = replay_mismatches(records, sim.cfg.params, sim.topo, sim.lut)
    _check(
        mismatches == 0,
        f"{mismatches} of {len(records)} rounds placed differently from auction_host",
    )
    return {
        "rounds": len(records),
        "migration_rounds": n_migration,
        "tasks": int(sum(s.n_tasks for s, _ in records)),
        "max_tasks": int(max(s.n_tasks for s, _ in records)),
        "mismatches": mismatches,
    }


# --------------------------------------------------------------------- #
# 5. serving


def phase_serving(
    topo_kwargs=PAPER_TOPOLOGY,
    *,
    rate_jobs_s: float = 10.0,
    horizon_s: int = 60,
    batch_tasks: int = 128,
    record_rounds: int = 16,
    interpret: bool = False,
) -> dict:
    """A fixed-rate open-loop serving run; drains, warm, replays exactly."""
    from repro import obs
    from repro.core import perf_model
    from repro.core.policy import PolicyParams
    from repro.core.scheduler_backend import WindowedAuctionBackend
    from repro.core.serving import ScheduleService, ServingConfig

    cfg = ServingConfig(
        backend="auction_windowed",
        rate_jobs_s=rate_jobs_s,
        horizon_s=horizon_s,
        seed=SEED,
        plane_seed=SEED,
        batch_tasks=batch_tasks,
        record_rounds=record_rounds,
        **topo_kwargs,
    )
    backend = None
    if interpret:
        backend = WindowedAuctionBackend(
            cfg.params, cfg.topology(), perf_model.perf_lut_table(),
            use_pallas=True, interpret=True,
        )
    with obs.scope():
        rep = ScheduleService(cfg, shared_backend=backend).run()
    _check(rep.drained, f"serving run did not drain ({rep.saturated_reason})")
    _check(
        rep.jit_compiles_post_warmup == 0.0,
        f"{rep.jit_compiles_post_warmup:g} jit compiles after warmup",
    )
    _check(
        rep.replay_mismatches == 0,
        f"verify_replay: {rep.replay_mismatches} mismatching rounds vs auction_host",
    )
    return {
        "rate_jobs_s": rate_jobs_s,
        "horizon_s": horizon_s,
        "ticks": rep.ticks,
        "jobs_admitted": rep.jobs_admitted,
        "tasks_placed": rep.tasks_placed,
        "jit_compiles_post_warmup": rep.jit_compiles_post_warmup,
        "replay_mismatches": rep.replay_mismatches,
        "decision_p50_ms": rep.decision_p50_ms,
        "decision_p99_ms": rep.decision_p99_ms,
    }


# --------------------------------------------------------------------- #


def main() -> int:
    if not os.path.isdir(os.path.join(SRC_DIR, "repro")):
        print(
            f"chip_smoke: no repro package at {SRC_DIR}; run this script "
            f"from a checkout of the repository",
            file=sys.stderr,
        )
        return 2
    sys.path.insert(0, SRC_DIR)
    from repro.runtime import enable_compilation_cache

    t0 = time.perf_counter()
    cache_dir = enable_compilation_cache()
    dev = phase_device()
    _say(
        "device",
        f"{dev['platform']} {dev['kind']!r}, {dev['count']} device(s); "
        f"compile cache {cache_dir}",
        t0,
    )

    t0 = time.perf_counter()
    k = phase_kernels()
    _say(
        "kernels",
        f"costmap == LUT exactly on {k['latencies']} latencies x {k['models']} "
        f"models; bid_top2 == jnp on {k['bid_rows']} rows at {BID_SHAPE}",
        t0,
    )

    t0 = time.perf_counter()
    r, sim, records = phase_replay()
    _say(
        "replay",
        f"{r['machines']} machines, {r['simulated_s']} simulated s: "
        f"{r['rounds']} rounds, {r['tasks_placed']} tasks placed, "
        f"{r['tasks_migrated']} migrated, {r['auction_iterations']} auction "
        f"iterations, buckets {r['buckets']}; tpu_custom_call {r['kernel_calls']}; "
        f"{r['compiles']} compiles in {r['compile_s']:.1f} s, replay wall "
        f"{r['replay_wall_s']:.1f} s, "
        f"peak_bytes_in_use {r['peak_bytes_in_use']}",
        t0,
    )

    t0 = time.perf_counter()
    p = phase_parity(records, sim)
    _say(
        "parity",
        f"{p['rounds']} rounds ({p['migration_rounds']} migration, "
        f"{p['tasks']} tasks, max {p['max_tasks']}) vs auction_host: "
        f"{p['mismatches']} mismatches",
        t0,
    )
    del records, sim

    t0 = time.perf_counter()
    s = phase_serving()
    _say(
        "serving",
        f"rate {s['rate_jobs_s']} jobs/s for {s['horizon_s']} s: drained, "
        f"{s['tasks_placed']} tasks placed, {s['jit_compiles_post_warmup']:g} "
        f"compiles after warmup, {s['replay_mismatches']} verify_replay "
        f"mismatches; decision p50 {s['decision_p50_ms']:.2f} ms / p99 "
        f"{s['decision_p99_ms']:.2f} ms (smoke reading, not a benchmark)",
        t0,
    )
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 - any failure is a failed smoke run
        traceback.print_exc()
        code = 1
    sys.exit(code)
