"""Fused on-device cost pipeline vs the numpy host reference, bit for bit,
plus backend equivalence through the `SchedulerBackend` interface.

Tier-1 runs the jnp path; set REPRO_DEVICE_PARITY_PALLAS=1 to re-run the
suite through the Pallas costmap kernel body in interpret mode:

    REPRO_DEVICE_PARITY_PALLAS=1 PYTHONPATH=src \
        python -m pytest -m device_parity -q
"""

import os

import numpy as np
import pytest

from repro.core import auction, latency, perf_model, policy, topology
from repro.core.scheduler_backend import (
    AuctionBackend,
    MCMFBackend,
    RoundContext,
    make_backend,
)
from repro.core.simulator import SimConfig, Simulator

pytestmark = pytest.mark.device_parity

# Flip the costmap evaluation onto the Pallas kernel body (interpret mode
# on CPU); the jnp LUT path is the tier-1 default.
_PALLAS = os.environ.get("REPRO_DEVICE_PARITY_PALLAS", "") == "1"
_COSTMAP_KW = dict(use_pallas=True, interpret=True) if _PALLAS else {}

LUT = perf_model.perf_lut_table()

# Full racks and a partial last rack (52 = 6.5 racks of 8).
TOPO_FULL = topology.Topology(
    n_machines=64, machines_per_rack=8, racks_per_pod=4, slots_per_machine=4
)
TOPO_PARTIAL = topology.Topology(
    n_machines=52, machines_per_rack=8, racks_per_pod=3, slots_per_machine=4
)
PLANES = {
    topo.n_machines: latency.LatencyPlane.synthesize(topo, duration_s=20, seed=0)
    for topo in (TOPO_FULL, TOPO_PARTIAL)
}


def _state(rng, topo, T=14, J=3, preempt_running=False):
    plane = PLANES[topo.n_machines]
    roots = rng.integers(0, topo.n_machines, size=J)
    cur = np.full(T, -1, np.int64)
    run_s = np.zeros(T, np.float32)
    if preempt_running:
        cur[: T // 2] = rng.integers(0, topo.n_machines, size=T // 2)
        run_s[: T // 2] = rng.uniform(0, 7200, size=T // 2)
    return policy.RoundState(
        task_job=np.sort(rng.integers(0, J, size=T)),
        perf_idx=rng.integers(0, 4, size=T),
        root_machine=roots,
        root_latency=np.stack([plane.latency_from(int(m), 3) for m in roots]),
        wait_s=rng.uniform(0, 100, size=T).astype(np.float32),
        run_s=run_s,
        cur_machine=cur,
        free_slots=rng.integers(0, 4, size=topo.n_machines).astype(np.int32),
    )


FIELDS = ("w", "col_capacity", "d", "c_rack", "b", "a")


@pytest.mark.parametrize("topo", [TOPO_FULL, TOPO_PARTIAL], ids=["full", "partial"])
@pytest.mark.parametrize("preempt", [False, True], ids=["nopre", "pre"])
@pytest.mark.parametrize("seed", range(5))
def test_dense_costs_device_bit_identical(topo, preempt, seed):
    rng = np.random.default_rng(seed)
    T = int(rng.integers(3, 24))
    J = int(rng.integers(1, 5))
    state = _state(rng, topo, T=T, J=J, preempt_running=preempt)
    params = policy.PolicyParams(preemption=preempt)
    host = policy.dense_costs(state, topo, params, LUT)
    dev = policy.dense_costs_device(state, topo, params, LUT, **_COSTMAP_KW)
    for f in FIELDS:
        h = np.asarray(getattr(host, f))
        d = np.asarray(getattr(dev, f))
        assert h.shape == d.shape, f
        assert h.dtype == d.dtype, f
        assert np.array_equal(h, d), f"{f} diverged (seed={seed})"


def test_dense_costs_device_beta_zero_and_unsched_cap():
    rng = np.random.default_rng(42)
    state = _state(rng, TOPO_PARTIAL, T=12, J=2, preempt_running=True)
    for params in (
        policy.PolicyParams(preemption=True, beta_scale=0.0),
        policy.PolicyParams(unsched_capacity=1),
        policy.PolicyParams(p_m=120, p_r=125),
    ):
        host = policy.dense_costs(state, TOPO_PARTIAL, params, LUT)
        dev = policy.dense_costs_device(
            state, TOPO_PARTIAL, params, LUT, **_COSTMAP_KW
        )
        for f in FIELDS:
            assert np.array_equal(
                np.asarray(getattr(host, f)), np.asarray(getattr(dev, f))
            ), f


def test_padded_device_costs_slice_to_unpadded():
    """The backend's bucketed pipeline == exact-shape pipeline on real rows."""
    rng = np.random.default_rng(3)
    state = _state(rng, TOPO_FULL, T=11, J=3)
    params = policy.PolicyParams()
    exact = policy.device_round_costs(state, TOPO_FULL, params, LUT, **_COSTMAP_KW)
    padded = policy.device_round_costs(
        state, TOPO_FULL, params, LUT,
        n_pad_tasks=32, n_pad_jobs=8, **_COSTMAP_KW,
    )
    T = state.n_tasks
    for e, p in zip(exact, padded):
        assert np.array_equal(np.asarray(e), np.asarray(p)[:T])


@pytest.mark.parametrize("seed", range(4))
def test_device_solve_matches_host_solve(seed):
    """Same costs in => bit-identical assignment out of both solve paths,
    in the production config (inexact + tie jitter) and the exact one."""
    rng = np.random.default_rng(100 + seed)
    topo = TOPO_PARTIAL
    state = _state(rng, topo, T=int(rng.integers(4, 20)), J=2)
    params = policy.PolicyParams()
    host = policy.dense_costs(state, topo, params, LUT)
    M = topo.n_machines
    w_m, a, *_ = policy.device_round_costs(
        state, topo, params, LUT,
        n_pad_tasks=auction._bucket(state.n_tasks),
        n_pad_jobs=auction._bucket(state.n_jobs, 8),
        **_COSTMAP_KW,
    )
    for kwargs in (dict(tie_jitter=9, exact=False), dict(tie_jitter=0, exact=True)):
        res_h = auction.solve_transportation(
            host.w, host.col_capacity[:M], M, M + state.task_job,
            slots_per_machine=topo.slots_per_machine, **kwargs,
        )
        res_d = auction.solve_transportation_device(
            w_m, a, state.n_tasks, state.free_slots, M, state.task_job,
            slots_per_machine=topo.slots_per_machine, **kwargs,
        )
        assert np.array_equal(res_h.assigned_col, res_d.assigned_col)
        assert res_h.total_cost == res_d.total_cost
        assert res_h.iterations == res_d.iterations


@pytest.mark.parametrize("seed", range(3))
def test_backend_equivalence_auction_vs_mcmf(seed):
    """AuctionBackend (exact mode) and MCMFBackend reach the same optimum
    through the SchedulerBackend interface."""
    rng = np.random.default_rng(500 + seed)
    topo = TOPO_PARTIAL
    state = _state(rng, topo, T=10, J=2)
    params = policy.PolicyParams()
    ctx = RoundContext(
        rng=np.random.default_rng(0),
        task_counts=np.zeros(topo.n_machines, np.int64),
        n_ready=state.n_tasks,
    )
    auction_exact = AuctionBackend(
        params, topo, LUT, device=True, tie_jitter=0, exact=True, **_COSTMAP_KW
    )
    mcmf_backend = MCMFBackend(params, topo, LUT)
    pa = auction_exact.place(state, ctx)
    pm = mcmf_backend.place(state, ctx)
    assert pa.objective == pm.objective
    M = topo.n_machines
    for p in (pa, pm):
        machines = p.cols[(p.cols >= 0) & (p.cols < M)]
        counts = np.bincount(machines, minlength=M)
        assert np.all(counts <= state.free_slots)


def test_simulator_device_and_host_backends_bit_identical():
    """Full replays through backend='auction' vs 'auction_host' vs the
    persistent windowed program emit identical metrics — the fused and the
    device-resident rounds are drop-ins for the numpy one."""
    from repro.core.workload import synth_workload

    topo = topology.Topology(
        n_machines=32, machines_per_rack=8, racks_per_pod=2, slots_per_machine=4
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=90, seed=1)
    wl = synth_workload(topo, duration_s=90, seed=1, target_utilisation=0.6)
    metrics = {}
    for backend in ("auction", "auction_host", "auction_windowed"):
        cfg = SimConfig(
            policy="nomora", backend=backend, seed=5, fixed_algo_s=0.0,
            params=policy.PolicyParams(preemption=True, beta_scale=0.0),
            migration_interval_s=30,
        )
        metrics[backend] = Simulator(wl, plane, cfg).run()
    a = metrics["auction"]
    for other in ("auction_host", "auction_windowed"):
        b = metrics[other]
        assert a.tasks_placed == b.tasks_placed, other
        assert a.tasks_migrated == b.tasks_migrated, other
        assert a.rounds == b.rounds, other
        assert a.placement_latency_s == b.placement_latency_s, other
        assert a.response_time_s == b.response_time_s, other
        assert a.per_job_perf == b.per_job_perf, other


# --- Persistent device-resident round program (cross-round scan) ---------- #


def _window_states(rng, topo, R, free_slots_per_round=None, preempt=False):
    """R random rounds against one cluster (varying T/J per round)."""
    states = []
    for r in range(R):
        T = int(rng.integers(4, 20))
        J = int(rng.integers(1, 4))
        s = _state(rng, topo, T=T, J=J, preempt_running=preempt)
        if free_slots_per_round is not None:
            s.free_slots = free_slots_per_round[r].astype(np.int32)
        states.append(s)
    return states


@pytest.mark.parametrize(
    "solver_kw",
    [dict(tie_jitter=9, exact=False), dict(tie_jitter=0, exact=True)],
    ids=["production", "exact"],
)
@pytest.mark.parametrize("preempt", [False, True], ids=["nopre", "pre"])
def test_window_scan_bit_identical_to_sequential_rounds(solver_kw, preempt):
    """A scanned R-round window == R sequential per-round auction rounds,
    bit for bit (assignments, objectives, iteration counts) — the tentpole
    parity pin for `round_program.RoundProgram.advance`."""
    from repro.core.round_program import RoundProgram, stack_round_states

    rng = np.random.default_rng(7)
    topo = TOPO_PARTIAL
    R, Tp, Jp = 6, 32, 8
    states = _window_states(rng, topo, R, preempt=preempt)
    params = policy.PolicyParams(preemption=preempt)

    prog = RoundProgram(
        topo, params, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp,
        slots_per_machine=topo.slots_per_machine, **solver_kw, **_COSTMAP_KW,
    )
    window = stack_round_states(
        states, n_pad_tasks=Tp, n_pad_jobs=Jp, exact=solver_kw["exact"]
    )
    _, res = prog.advance(prog.init_state(states[0].free_slots), window)

    for r, s in enumerate(states):
        w_m, a, *_ = policy.device_round_costs(
            s, topo, params, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp, **_COSTMAP_KW
        )
        ref = auction.solve_transportation_device(
            w_m, a, s.n_tasks, s.free_slots, topo.n_machines, s.task_job,
            slots_per_machine=topo.slots_per_machine, **solver_kw,
        )
        assert np.array_equal(res.round_cols(r), ref.assigned_col), r
        assert res.round_objective(r) == ref.total_cost, r
        assert int(res.iterations[r]) == ref.iterations, r


def test_window_scan_chained_slots_matches_host_accounting():
    """chain_slots=True: the device-carried occupancy (debited by each
    round's placements, credited by per-round deltas) reproduces a host
    loop that applies the same slot accounting between sequential calls."""
    from repro.core.round_program import RoundProgram, stack_round_states

    rng = np.random.default_rng(11)
    topo = TOPO_FULL
    M = topo.n_machines
    R, Tp, Jp = 5, 32, 8
    free0 = rng.integers(1, 4, size=M).astype(np.int32)
    # Per-round exogenous deltas (retirements); round 0's row is consumed
    # as a delta on the seeded carry by place_window/advance contract.
    deltas = [np.zeros(M, np.int32)]
    for _ in range(R - 1):
        d = np.zeros(M, np.int32)
        d[rng.integers(0, M, size=3)] += 1
        deltas.append(d)
    states = _window_states(rng, topo, R, free_slots_per_round=deltas)
    params = policy.PolicyParams()

    prog = RoundProgram(
        topo, params, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp,
        slots_per_machine=topo.slots_per_machine, tie_jitter=9, exact=False,
        chain_slots=True, **_COSTMAP_KW,
    )
    window = stack_round_states(states, n_pad_tasks=Tp, n_pad_jobs=Jp)
    st, res = prog.advance(prog.init_state(free0), window)

    free = free0.copy()
    for r, s in enumerate(states):
        free = free + deltas[r]
        s.free_slots = free.copy().astype(np.int32)
        w_m, a, *_ = policy.device_round_costs(
            s, topo, params, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp, **_COSTMAP_KW
        )
        ref = auction.solve_transportation_device(
            w_m, a, s.n_tasks, s.free_slots, M, s.task_job,
            slots_per_machine=topo.slots_per_machine, tie_jitter=9, exact=False,
        )
        assert np.array_equal(res.round_cols(r), ref.assigned_col), r
        cols = ref.assigned_col
        np.subtract.at(free, cols[cols < M], 1)
    assert np.array_equal(np.asarray(st.free_slots), free)


def test_whatif_variants_bit_identical_to_per_round_calls():
    """The vmapped what-if axis: each of K `PolicyParams` lanes equals the
    per-round pipeline run standalone under that variant, and the ranking
    key (true cost) is minimised by the chosen variant."""
    from repro.core.round_program import RoundProgram

    rng = np.random.default_rng(13)
    topo = TOPO_PARTIAL
    state = _state(rng, topo, T=14, J=3, preempt_running=True)
    base = policy.PolicyParams(preemption=True)
    variants = [
        policy.PolicyParams(preemption=True, beta_scale=b)
        for b in (0.0, 100.0 / 3600.0, 400.0 / 3600.0)
    ] + [policy.PolicyParams(p_m=120, p_r=125)]
    Tp, Jp = 32, 8
    prog = RoundProgram(
        topo, base, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp,
        slots_per_machine=topo.slots_per_machine, tie_jitter=9, exact=False,
        **_COSTMAP_KW,
    )
    res = prog.what_if(state, variants)
    for k, p in enumerate(variants):
        w_m, a, *_ = policy.device_round_costs(
            state, topo, p, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp, **_COSTMAP_KW
        )
        ref = auction.solve_transportation_device(
            w_m, a, state.n_tasks, state.free_slots, topo.n_machines,
            state.task_job, slots_per_machine=topo.slots_per_machine,
            tie_jitter=9, exact=False,
        )
        assert np.array_equal(res.variant_cols(k), ref.assigned_col), k
        assert (
            int(res.per_task_cost[k, : state.n_tasks].astype(np.int64).sum())
            == ref.total_cost
        ), k
    best = res.best_variant()
    assert res.true_costs[best] == res.true_costs.min()


def test_windowed_backend_place_and_window_match_auction():
    """`WindowedAuctionBackend.place` == `AuctionBackend.place` per round,
    and `place_window` == the same R rounds placed sequentially."""
    from repro.core.scheduler_backend import WindowedAuctionBackend

    rng = np.random.default_rng(17)
    topo = TOPO_PARTIAL
    params = policy.PolicyParams(preemption=True)
    ctx = RoundContext(
        rng=np.random.default_rng(0),
        task_counts=np.zeros(topo.n_machines, np.int64),
        n_ready=0,
    )
    per_round = AuctionBackend(params, topo, LUT, device=True, **_COSTMAP_KW)
    windowed = WindowedAuctionBackend(params, topo, LUT, device=True, **_COSTMAP_KW)
    states = _window_states(rng, topo, 4, preempt=True)
    for s in states:
        pa = per_round.place(s, ctx)
        pw = windowed.place(s, ctx)
        assert np.array_equal(pa.cols, pw.cols)
        assert pa.objective == pw.objective
    batched = windowed.place_window(states)
    for s, p in zip(states, batched):
        ref = per_round.place(s, ctx)
        assert np.array_equal(ref.cols, p.cols)
        assert ref.objective == p.objective


@pytest.fixture(scope="module")
def row_pad_backends():
    """A windowed backend pinned at the (64, 32) bucket and an unpinned
    one, shared by the cases below so each bucket compiles once."""
    from repro.core.scheduler_backend import WindowedAuctionBackend

    params = policy.PolicyParams(preemption=True)
    pinned = WindowedAuctionBackend(
        params, TOPO_PARTIAL, LUT, device=True, **_COSTMAP_KW
    )
    pinned.pin_serving(64, 32)
    unpinned = WindowedAuctionBackend(
        params, TOPO_PARTIAL, LUT, device=True, **_COSTMAP_KW
    )
    return pinned, unpinned


@pytest.mark.parametrize(
    "J", [3, 8, 32], ids=["below_row_bucket", "at_row_bucket", "at_job_bucket"]
)
@pytest.mark.parametrize("R", [1, 3])
def test_host_latency_rows_padded_on_device(row_pad_backends, R, J):
    """Host latency rows ship at their row bucket Jr and are padded to the
    program's job bucket Jp on the device: the padded block is the host
    zero-padded block bit for bit, ``h2d.latency_rows_skipped`` reads
    R x (Jp - Jr), and a pinned backend places exactly as an unpinned one,
    which skips no rows."""
    from repro import obs
    from repro.core.round_program import stack_round_states

    pinned, unpinned = row_pad_backends
    topo = TOPO_PARTIAL
    M = topo.n_machines
    rng = np.random.default_rng(100 + 10 * R + J)
    states = [
        _state(rng, topo, T=40, J=j, preempt_running=True)
        for j in [J, max(1, J // 2), 1][:R]
    ]
    _key, prog = pinned._program(40, J)
    Tp, Jp = prog.n_pad_tasks, prog.n_pad_jobs
    assert (Tp, Jp) == (64, 32)
    Jr = auction._bucket(J, 8)

    window = stack_round_states(states, n_pad_tasks=Tp, n_pad_jobs=Jp)
    assert window.root_latency.shape == (R, Jr, M)
    host_block = np.zeros((R, Jp, M), np.float32)
    for r, s in enumerate(states):
        host_block[r, : s.n_jobs] = s.root_latency
    device_block = np.asarray(prog._window_arrays(window)[2])
    assert device_block.shape == host_block.shape
    assert np.array_equal(
        device_block.view(np.uint32), host_block.view(np.uint32)
    )

    with obs.scope():
        prog.advance(prog.init_state(states[0].free_slots), window)
        assert obs.counters()["h2d.latency_rows_skipped"] == R * (Jp - Jr)
        assert obs.counters()["h2d.upload_bytes"] == prog._window_upload_bytes(
            window
        )

    ctx = RoundContext(
        rng=np.random.default_rng(0),
        task_counts=np.zeros(M, np.int64),
        n_ready=0,
    )
    variants = [
        policy.PolicyParams(preemption=True, beta_scale=b)
        for b in (0.0, 100.0 / 3600.0)
    ]
    placed = {}
    for name, be in (("pinned", pinned), ("unpinned", unpinned)):
        with obs.scope():
            placed[name] = [be.place(s, ctx) for s in states] + [
                be.place_whatif(states[0], ctx, variants)
            ]
            placed[name + "_skipped"] = obs.counters().get(
                "h2d.latency_rows_skipped", 0.0
            )
    for a, b in zip(placed["pinned"], placed["unpinned"]):
        assert np.array_equal(a.cols, b.cols)
        assert a.objective == b.objective
    expected = sum(Jp - auction._bucket(s.n_jobs, 8) for s in states)
    assert placed["pinned_skipped"] == expected + Jp - Jr
    assert placed["unpinned_skipped"] == 0.0


def test_simulator_whatif_single_variant_matches_base():
    """whatif_betas with one variant equal to the configured beta is a
    no-op: the what-if dispatch returns the base placement bit for bit."""
    from repro.core.workload import synth_workload

    topo = topology.Topology(
        n_machines=32, machines_per_rack=8, racks_per_pod=2, slots_per_machine=4
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=90, seed=1)
    wl = synth_workload(topo, duration_s=90, seed=1, target_utilisation=0.6)

    def run(whatif_betas):
        cfg = SimConfig(
            policy="nomora", backend="auction_windowed", seed=5,
            fixed_algo_s=0.0,
            params=policy.PolicyParams(preemption=True, beta_scale=0.0),
            migration_interval_s=30, whatif_betas=whatif_betas,
        )
        return Simulator(wl, plane, cfg).run()

    base, single = run(()), run((0.0,))
    assert base.tasks_placed == single.tasks_placed
    assert base.tasks_migrated == single.tasks_migrated
    assert base.per_job_perf == single.per_job_perf
    # Multiple variants run through one dispatch and stay a valid replay.
    multi = run((0.0, 100.0 / 3600.0, 400.0 / 3600.0))
    assert multi.tasks_placed == base.tasks_placed


def test_make_backend_names_and_config_resolution():
    params = policy.PolicyParams()
    for name, cls_name in [
        ("auction", "AuctionBackend"),
        ("auction_host", "AuctionBackend"),
        ("mcmf", "MCMFBackend"),
        ("random", "RandomBackend"),
        ("load_spreading", "LoadSpreadingBackend"),
        ("random_solver", "RandomSolverBackend"),
        ("spread_solver", "SpreadSolverBackend"),
    ]:
        be = make_backend(name, params, TOPO_FULL, LUT)
        assert type(be).__name__ == cls_name
        assert be.name == name
    with pytest.raises(KeyError):
        make_backend("nope", params, TOPO_FULL, LUT)


# --------------------------------------------------------------------- #
# Device-resident latency oracle: bit parity + incremental uploads


def test_device_latency_oracle_bit_identical_on_dynamic_plane():
    from repro.core.latency_device import DeviceLatencyOracle

    topo = TOPO_FULL
    ev = latency.LatencyEvents(
        hotspots=(
            latency.DriftingHotspot(
                start_s=10.0, end_s=80.0, rack0=3,
                drift_racks_per_s=0.2, width_racks=2, multiplier=5.0,
            ),
        ),
        regime=latency.RegimeSchedule(times=(30.0, 60.0), frac=0.5),
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=90, seed=2, events=ev)
    oracle = DeviceLatencyOracle(plane)
    roots = [0, 17, 33, 63, 17]
    # Hotspot drift positions and both regime boundaries.
    for t in (0, 6, 29, 30, 31, 59, 60, 89):
        got = np.asarray(oracle.root_rows(roots, t))
        want = plane.latency_rows(roots, t)
        assert got.dtype == np.float32
        assert np.array_equal(got, want), t
    # The recurring upload is the 24-float column + rack mults + root ids,
    # never the (J, M) block.
    st = oracle.stats()
    assert st["round_uploads"] == 8
    assert st["floats_per_round"] < topo.n_machines  # << J * M
    # Decompositions are built once per (root, epoch), then cached.
    builds = st["decomp_builds"]
    np.asarray(oracle.root_rows(roots, 89))
    assert oracle.stats()["decomp_builds"] == builds


def test_device_latency_simulator_metrics_identical():
    """device_latency=True swaps the host (J, M) row build for the oracle;
    every placement and metric must stay bit-identical."""
    from repro.core.workload import synth_workload

    topo = topology.Topology(
        n_machines=32, machines_per_rack=8, racks_per_pod=2, slots_per_machine=4
    )
    ev = latency.LatencyEvents(
        hotspots=(
            latency.DriftingHotspot(
                start_s=20.0, end_s=80.0, rack0=0,
                drift_racks_per_s=0.05, width_racks=1, multiplier=4.0,
            ),
        )
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=90, seed=1, events=ev)
    wl = synth_workload(topo, duration_s=90, seed=1, target_utilisation=0.5)

    def run(dev):
        cfg = SimConfig(
            policy="nomora", backend="auction_windowed", seed=5,
            fixed_algo_s=0.0, device_latency=dev,
            params=policy.PolicyParams(preemption=True, beta_scale=0.0),
            migration_interval_s=30,
        )
        return Simulator(wl, plane, cfg).run()

    host, dev = run(False), run(True)
    assert host.per_job_perf == dev.per_job_perf
    assert host.tasks_placed == dev.tasks_placed
    assert host.tasks_migrated == dev.tasks_migrated
    sh, sd = host.summary(), dev.summary()
    assert sh.keys() == sd.keys()
    for k in sh:
        # NaN marks an empty series (repo convention); NaN != NaN, so
        # compare with equal_nan semantics.
        assert sh[k] == sd[k] or (np.isnan(sh[k]) and np.isnan(sd[k])), k


# --------------------------------------------------------------------- #
# Mover-mask what-if lanes (migration controller's solve axis)


def test_whatif_mask_lanes_pin_frozen_rows_and_outcomes():
    from repro.core.round_program import RoundProgram

    rng = np.random.default_rng(23)
    topo = TOPO_PARTIAL
    state = _state(rng, topo, T=14, J=3, preempt_running=True)
    params = policy.PolicyParams(preemption=True, beta_scale=0.0)
    Tp, Jp = 32, 8
    prog = RoundProgram(
        topo, params, LUT, n_pad_tasks=Tp, n_pad_jobs=Jp,
        slots_per_machine=topo.slots_per_machine, tie_jitter=9, exact=False,
        **_COSTMAP_KW,
    )
    T = state.n_tasks
    M = topo.n_machines
    # Ample capacity so frozen re-occupancy never clips a lane to zero.
    state.free_slots = np.full(M, 3, np.int32)
    running = state.cur_machine >= 0
    all_true = np.ones(T, bool)
    frozen_all = ~running  # freeze every running task
    half = all_true.copy()
    half[np.nonzero(running)[0][::2]] = False  # freeze every other runner
    masks = np.stack([all_true, frozen_all, half])
    res = prog.what_if(state, [params] * 3, active_masks=masks)

    # Lane with an all-True mask is bit-identical to the unmasked axis.
    ref = prog.what_if(state, [params])
    assert np.array_equal(res.variant_cols(0), ref.variant_cols(0))

    # Outcomes: frozen rows charge their stay cost, so lane totals are
    # comparable; the all-frozen lane's outcome is exactly the sum of
    # running rows' stay costs plus pending rows' placed/unscheduled cost
    # — its mover contribution is the no-migration baseline by construction.
    out = res.lane_outcomes()
    assert out.shape == (3,)
    true1 = res.per_task_true_cost[1, :T].astype(np.int64)
    stay1 = res.per_task_stay_cost[1, :T].astype(np.int64)
    assert out[1] == np.where(masks[1], true1, stay1).sum()
    assert (stay1[running] == np.where(masks[1], true1, stay1)[running]).all()

    # Capacity accounting: each lane solves against free_lane =
    # free_slots - (frozen runners re-occupying their slots), so active
    # placements never exceed it on any machine.
    for k in range(3):
        cols = res.variant_cols(k)
        lane_placed = masks[k] & (cols >= 0) & (cols < M)
        counts = np.bincount(cols[lane_placed], minlength=M)
        frozen_occ = np.bincount(
            state.cur_machine[running & ~masks[k]], minlength=M
        )
        assert (counts + frozen_occ <= state.free_slots).all(), k

    # Freezing movers changes the solve: the half-frozen lane must not
    # silently equal the all-active lane on the frozen rows' columns.
    assert not np.array_equal(res.variant_cols(2), res.variant_cols(0))
