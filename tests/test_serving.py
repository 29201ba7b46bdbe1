"""Serving-mode tests: open-loop arrivals, queue invariants, the warm-path
zero-recompile pin, backend protocol conformance, and SimConfig grouping."""

import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core.policy import PolicyParams
from repro.core.scenarios import SERVING_PRESETS, get_serving_preset
from repro.core.scheduler_backend import (
    BACKEND_NAMES,
    BackendCapabilityError,
    make_backend,
)
from repro.core.serving import (
    ScheduleService,
    ServingConfig,
    saturation_sweep,
    serve,
)
from repro.core.topology import Topology
from repro.core.trace import OpenLoopCursor, open_loop_trace

TOPO = Topology(n_machines=32, machines_per_rack=8, racks_per_pod=2,
                slots_per_machine=4)

SMOKE = ServingConfig(**{
    **get_serving_preset("smoke").config_kwargs,
    "slots_per_machine": 4,
})


# --------------------------------------------------------------------- #
# Open-loop arrival stream


def test_open_loop_deterministic_given_seed():
    a = open_loop_trace(TOPO, 120, 1.5, seed=7)
    b = open_loop_trace(TOPO, 120, 1.5, seed=7)
    ja = [(j.job_id, j.arrival_s, j.n_tasks, j.duration_s, j.perf_idx)
          for j in a.jobs]
    jb = [(j.job_id, j.arrival_s, j.n_tasks, j.duration_s, j.perf_idx)
          for j in b.jobs]
    assert ja == jb and len(ja) > 0
    # Re-iteration yields the same stream (the `jobs` property is fresh).
    assert ja == [(j.job_id, j.arrival_s, j.n_tasks, j.duration_s, j.perf_idx)
                  for j in a.jobs]
    c = open_loop_trace(TOPO, 120, 1.5, seed=8)
    assert ja != [(j.job_id, j.arrival_s, j.n_tasks, j.duration_s, j.perf_idx)
                  for j in c.jobs]


def test_open_loop_rate_and_horizon():
    cursor = open_loop_trace(TOPO, 400, 2.0, seed=3)
    jobs = list(cursor.jobs)
    # Poisson(800): 5-sigma band.
    assert 800 - 5 * np.sqrt(800) < len(jobs) < 800 + 5 * np.sqrt(800)
    arr = [j.arrival_s for j in jobs]
    assert arr == sorted(arr)
    assert all(0 <= a < 400 for a in arr)
    assert [j.job_id for j in jobs] == list(range(len(jobs)))


def test_open_loop_duration_scale_shrinks_durations():
    full = open_loop_trace(TOPO, 200, 1.0, seed=0)
    tenth = open_loop_trace(TOPO, 200, 1.0, seed=0, duration_scale=0.1)
    df = np.array([j.duration_s for j in full.jobs])
    dt = np.array([j.duration_s for j in tenth.jobs])
    assert np.all(dt <= df)
    assert np.all(dt >= 1.0)  # floor survives scaling
    # Same arrivals/task counts: only the duration marginal scales.
    assert [j.arrival_s for j in full.jobs] == [j.arrival_s for j in tenth.jobs]


def test_open_loop_windowing_is_prefix_free():
    """Any window's jobs are computable without generating its prefix."""
    cursor = OpenLoopCursor(topo=TOPO, duration_s=180, rate_jobs_s=1.0,
                            seed=5, window_s=60)
    w1_direct = cursor._window_jobs(1)
    streamed = [jobs for _lo, _hi, jobs in cursor.windows()]
    assert [(j.arrival_s, j.n_tasks) for j in streamed[1]] == [
        (j.arrival_s, j.n_tasks) for j in w1_direct
    ]


# --------------------------------------------------------------------- #
# Serving loop invariants


def test_serving_drains_at_sub_saturation():
    rep = serve(SMOKE, backend="load_spreading", rate_jobs_s=0.4)
    assert rep.drained and not rep.saturated
    assert rep.final_queue_depth == 0
    assert rep.tasks_placed > 0
    assert rep.jobs_admitted > 0
    assert rep.decision_p99_ms >= rep.decision_p50_ms >= 0.0


def test_serving_detects_saturation():
    rep = serve(
        SMOKE, backend="load_spreading", rate_jobs_s=20.0,
        duration_scale=1.0, queue_limit_tasks=128, max_drain_s=30,
    )
    assert rep.saturated
    assert rep.saturated_reason in ("queue_limit", "drain_timeout")
    assert not rep.drained


def test_serving_deterministic_placements():
    """Wall-clock stamps vary; the placement sequence must not."""
    a = ScheduleService(dataclasses.replace(SMOKE, backend="auction_host"))
    ra = a.run()
    b = ScheduleService(dataclasses.replace(SMOKE, backend="auction_host"))
    rb = b.run()
    assert ra.tasks_placed == rb.tasks_placed
    assert ra.ticks == rb.ticks
    assert np.array_equal(
        a.sim.tt.machine[: a.sim.tt.n], b.sim.tt.machine[: b.sim.tt.n]
    )


def test_saturation_sweep_orders_rates():
    cfg = dataclasses.replace(SMOKE, backend="random", max_drain_s=40,
                              queue_limit_tasks=200, duration_scale=1.0)
    reports, sustainable = saturation_sweep(
        cfg, [8.0, 0.3], share_backend=False
    )
    assert [r.rate_jobs_s for r in reports] == [0.3, 8.0]
    assert reports[0].drained and reports[1].saturated
    assert sustainable == 0.3


def test_serving_rejects_unservable_backend():
    with pytest.raises(ValueError, match="supports_serving"):
        ScheduleService(dataclasses.replace(SMOKE, backend="auction"))


def test_serving_warm_path_zero_recompiles_and_replay_parity():
    """The tentpole contract: after warmup, the pinned windowed program
    serves every decision without a single jit cache miss, and recorded
    serving rounds replay bit-identically through the per-round backend."""
    with obs.scope():
        svc = ScheduleService(dataclasses.replace(
            SMOKE, backend="auction_windowed", record_rounds=6,
            device_latency=True, warmup_rounds=3,
        ))
        rep = svc.run()
    assert rep.drained
    assert rep.jit_compiles_post_warmup == 0.0
    assert rep.replay_mismatches == 0
    assert len(svc.recorder.records) > 0


def test_serving_warm_path_zero_recompiles_host_rows():
    """The same contract on the host-row path (``device_latency=False``):
    rounds of 1 to ~25 jobs under the pinned 64-job bucket ship only their
    row bucket (8, 16 or 32 rows) and pad it on the device, and the pad
    programs `warm_serving` compiled serve every one of them."""
    from repro.core.auction import _bucket

    with obs.scope():
        svc = ScheduleService(dataclasses.replace(
            SMOKE, backend="auction_windowed", record_rounds=10_000,
            device_latency=False, warmup_rounds=1, rate_jobs_s=4.0,
        ))
        rep = svc.run()
        skipped = obs.counters()["h2d.latency_rows_skipped"]
    assert rep.drained
    assert rep.jit_compiles_post_warmup == 0.0
    assert rep.replay_mismatches == 0
    row_buckets = {_bucket(s.n_jobs, 8) for s, _cols in svc.recorder.records}
    assert row_buckets == {8, 16, 32}
    assert skipped > 0


# --------------------------------------------------------------------- #
# SchedulerBackend protocol conformance


@pytest.mark.parametrize("name", BACKEND_NAMES)
def test_backend_capability_flags(name):
    topo = Topology(n_machines=8, machines_per_rack=4, racks_per_pod=2,
                    slots_per_machine=2)
    b = make_backend(name, PolicyParams(), topo)
    for flag in ("supports_window", "supports_whatif", "supports_serving",
                 "supports_migration", "selects_movers", "needs_latency",
                 "caps_admission"):
        assert isinstance(getattr(b, flag), bool), (name, flag)

    if not b.supports_window:
        with pytest.raises(BackendCapabilityError):
            b.place_window([])
    if not b.supports_whatif:
        with pytest.raises(BackendCapabilityError):
            b.place_whatif(None, None, [])
        with pytest.raises(BackendCapabilityError):
            b.whatif_result(None, None, [])
    if not b.supports_serving:
        with pytest.raises(BackendCapabilityError):
            b.pin_serving(16, 8)
        with pytest.raises(BackendCapabilityError):
            b.warm_serving(np.full(8, 2, np.int32))
    else:
        b.pin_serving(16, 8)  # must not raise
    assert isinstance(BackendCapabilityError("x"), NotImplementedError)


def test_backend_capability_expectations():
    """Pin the capability matrix the simulator and serving loop rely on."""
    topo = Topology(n_machines=8, machines_per_rack=4, racks_per_pod=2,
                    slots_per_machine=2)
    caps = {
        name: make_backend(name, PolicyParams(), topo)
        for name in BACKEND_NAMES
    }
    assert caps["auction_windowed"].supports_window
    assert caps["auction_windowed"].supports_whatif
    assert caps["auction_windowed"].supports_serving
    assert not caps["auction"].supports_serving  # bucket tracks live tasks
    assert caps["auction_host"].supports_serving  # pure host
    for host in ("random", "load_spreading", "mcmf", "random_solver",
                 "spread_solver"):
        assert caps[host].supports_serving, host
        assert not caps[host].supports_window, host
        assert not caps[host].supports_whatif, host


# --------------------------------------------------------------------- #
# Serving presets


def test_serving_presets_build_configs():
    for name, preset in SERVING_PRESETS.items():
        cfg = ServingConfig(**preset.config_kwargs)
        assert cfg.topology().n_machines == cfg.n_machines
        assert get_serving_preset(name) is preset
    with pytest.raises(KeyError):
        get_serving_preset("nope")


# --------------------------------------------------------------------- #
# SimConfig grouped sub-configs


def test_simconfig_flat_kwargs_round_trip():
    """Every pre-grouping flat kwarg spelling still constructs and lands
    on the same field (the backward-compat contract of the regrouping)."""
    from repro.core.simulator import MetricsConfig, MigrationConfig, SimConfig

    flat_kwargs = dict(
        policy="nomora",
        solver="auction",
        backend="auction_host",
        round_interval_s=2,
        migration_interval_s=20,
        perf_sample_interval_s=30,
        seed=9,
        max_round_tasks=256,
        failures=((10, 3),),
        straggler_threshold=0.8,
        fixed_algo_s=0.0,
        streaming_metrics=True,
        perf_reservoir_k=4,
        whatif_betas=(0.0, 1.0),
        device_latency=False,
        migration_controller=False,
        qos_threshold=0.85,
        qos_window=3,
        qos_clear_margin=0.05,
        qos_hold_s=10.0,
        migration_budget=32,
    )
    cfg = SimConfig(**flat_kwargs)
    for k, v in flat_kwargs.items():
        assert getattr(cfg, k) == v, k

    # Grouped spelling reproduces the identical config.
    grouped = SimConfig(
        policy="nomora",
        solver="auction",
        backend="auction_host",
        round_interval_s=2,
        seed=9,
        max_round_tasks=256,
        failures=((10, 3),),
        device_latency=False,
        migration=MigrationConfig(
            interval_s=20,
            straggler_threshold=0.8,
            whatif_betas=(0.0, 1.0),
            controller=False,
            qos_threshold=0.85,
            qos_window=3,
            qos_clear_margin=0.05,
            qos_hold_s=10.0,
            budget=32,
        ),
        metrics=MetricsConfig(
            streaming=True,
            perf_reservoir_k=4,
            perf_sample_interval_s=30,
            fixed_algo_s=0.0,
        ),
    )
    assert grouped == cfg
    # Grouped read-back views match, and replace() keeps working.
    assert cfg.migration_cfg == grouped.migration_cfg
    assert cfg.metrics_cfg == grouped.metrics_cfg
    assert dataclasses.replace(cfg, seed=0).seed == 0
    assert dataclasses.replace(cfg, seed=0).migration_interval_s == 20
