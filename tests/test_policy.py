"""Policy cost-model invariants (paper §5.2), property-based."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import latency, perf_model, policy, topology

TOPO = topology.Topology(
    n_machines=64, machines_per_rack=8, racks_per_pod=4, slots_per_machine=4
)
PLANE = latency.LatencyPlane.synthesize(TOPO, duration_s=20, seed=0)
LUT = perf_model.perf_lut_table()


def _state(rng, T=6, J=2, preempt_running=False):
    roots = rng.integers(0, TOPO.n_machines, size=J)
    cur = np.full(T, -1, np.int64)
    run_s = np.zeros(T, np.float32)
    if preempt_running:
        cur[: T // 2] = rng.integers(0, TOPO.n_machines, size=T // 2)
        run_s[: T // 2] = rng.uniform(0, 7200, size=T // 2)
    return policy.RoundState(
        task_job=np.sort(rng.integers(0, J, size=T)),
        perf_idx=rng.integers(0, 4, size=T),
        root_machine=roots,
        root_latency=np.stack([PLANE.latency_from(int(m), 3) for m in roots]),
        wait_s=rng.uniform(0, 100, size=T).astype(np.float32),
        run_s=run_s,
        cur_machine=cur,
        free_slots=np.full(TOPO.n_machines, 4, np.int32),
    )


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_cost_hierarchy(seed):
    """d <= c_rack <= b for every task/machine (Eqs. 6, 8, 9)."""
    rng = np.random.default_rng(seed)
    state = _state(rng)
    dc = policy.dense_costs(state, TOPO, policy.PolicyParams())
    rack_of_m = np.arange(TOPO.n_machines) // TOPO.machines_per_rack
    assert np.all(dc.d <= dc.c_rack[:, rack_of_m])
    assert np.all(dc.c_rack <= dc.b[:, None])


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_effective_cost_chain(seed):
    """w = d if d<=p_m else c_rack if c_rack<=p_r else b (DESIGN.md §5.1)."""
    rng = np.random.default_rng(seed)
    state = _state(rng)
    params = policy.PolicyParams(p_m=105, p_r=110)
    dc = policy.dense_costs(state, TOPO, params)
    M = TOPO.n_machines
    rack_of_m = np.arange(M) // TOPO.machines_per_rack
    c_for_m = dc.c_rack[:, rack_of_m]
    expect = np.where(
        dc.d <= params.p_m, dc.d, np.where(c_for_m <= params.p_r, c_for_m, dc.b[:, None])
    )
    assert np.array_equal(dc.w[:, :M], expect)


def test_unscheduled_cost_escalates_with_wait():
    rng = np.random.default_rng(1)
    state = _state(rng)
    params = policy.PolicyParams(omega=2.0, gamma=1001)
    dc = policy.dense_costs(state, TOPO, params)
    expect = (2.0 * state.wait_s + 1001).astype(np.int32)
    assert np.array_equal(dc.a, expect)
    # gamma exceeds any machine cost (paper: gamma > all other costs).
    assert dc.a.min() >= dc.w[:, : TOPO.n_machines].max(
        where=dc.w[:, : TOPO.n_machines] < policy.INF_COST, initial=0
    )


def test_preemption_discount_applies_to_current_machine():
    rng = np.random.default_rng(2)
    state = _state(rng, preempt_running=True)
    p_on = policy.PolicyParams(preemption=True, beta_scale=100.0 / 3600.0)
    p_off = policy.PolicyParams(preemption=False)
    dc_on = policy.dense_costs(state, TOPO, p_on)
    dc_off = policy.dense_costs(state, TOPO, p_off)
    running = state.cur_machine >= 0
    cur = state.cur_machine[running]
    disc = dc_on.w[running, cur]
    nodisc = dc_off.w[running, cur]
    assert np.all(disc <= nodisc)
    assert np.all(disc >= 1)
    # beta=0 => no discount at all.
    dc_zero = policy.dense_costs(state, TOPO, policy.PolicyParams(preemption=True, beta_scale=0.0))
    assert np.array_equal(dc_zero.w, dc_off.w)


def test_threshold_monotonicity():
    """Smaller p_m/p_r => fewer (or equal) direct preference arcs."""
    rng = np.random.default_rng(3)
    state = _state(rng)
    lo = policy.dense_costs(state, TOPO, policy.PolicyParams(p_m=100, p_r=105))
    hi = policy.dense_costs(state, TOPO, policy.PolicyParams(p_m=120, p_r=130))
    n_lo = int((lo.d <= 100).sum())
    n_hi = int((hi.d <= 120).sum())
    assert n_lo <= n_hi
    # Effective costs can only improve (weakly) with wider preference lists.
    M = TOPO.n_machines
    assert np.all(hi.w[:, :M] <= lo.w[:, :M])


def test_costs_match_paper_examples():
    """Same-rack placements at low latency must cost exactly 100."""
    rng = np.random.default_rng(4)
    state = _state(rng)
    dc = policy.dense_costs(state, TOPO, policy.PolicyParams())
    for i in range(state.n_tasks):
        root = state.root_machine[state.task_job[i]]
        assert dc.d[i, root] == 100  # same-machine RTT ~2us -> perf 1.0


def test_baseline_policies_feasible(rng):
    free = rng.integers(0, 3, size=16).astype(np.int64)
    total = int(free.sum())
    out = policy.random_placement(rng, total + 5, free.copy())
    placed = out[out >= 0]
    assert len(placed) == total
    counts = np.bincount(placed, minlength=16)
    assert np.all(counts <= free)

    counts0 = rng.integers(0, 5, size=16).astype(np.int64)
    out2 = policy.load_spreading_placement(counts0, free.copy(), total)
    placed2 = out2[out2 >= 0]
    counts2 = np.bincount(placed2, minlength=16)
    assert np.all(counts2 <= free)


def test_host_reference_never_calls_the_kernels(monkeypatch):
    """On a TPU the host reference (`dense_costs`, ``auction_host``) must
    still run Eq. 6 through the LUT and bid through jnp, or it would check
    the device kernels against themselves."""
    import jax

    from repro.core.scheduler_backend import RoundContext, make_backend
    from repro.kernels.auction_bid import kernel as bid_kernel
    from repro.kernels.costmap import kernel as cm_kernel
    from repro.kernels.costmap import ops as costmap_ops

    def kernel_called(*_args, **_kwargs):
        raise AssertionError("the host reference called a Pallas kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cm_kernel, "costmap_pallas", kernel_called)
    monkeypatch.setattr(bid_kernel, "bid_top2_pallas", kernel_called)
    state = _state(np.random.default_rng(3), T=9, J=3, preempt_running=True)
    # Control: the auto-selecting op does take the kernel on a "TPU".
    with pytest.raises(AssertionError, match="Pallas kernel"):
        costmap_ops.costmap(
            LUT, state.perf_idx, state.root_latency[state.task_job]
        )
    params = policy.PolicyParams(preemption=True)
    costs = policy.dense_costs(state, TOPO, params, LUT)
    assert costs.d.shape == (9, TOPO.n_machines)
    ctx = RoundContext(
        rng=np.random.default_rng(0),
        task_counts=np.zeros(TOPO.n_machines, np.int64),
        n_ready=state.n_tasks,
    )
    placed = make_backend("auction_host", params, TOPO, LUT).place(state, ctx)
    assert len(placed.cols) == 9
