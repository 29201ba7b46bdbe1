"""The plain reference agrees with the scheduler where both are defined:
the cost table, the latency rows and whole rounds solved by the program's
host path (``auction_host``), with pending tasks, running movers and
tie-heavy costs."""

import numpy as np
import pytest

import reference as ref


def test_cost_table_matches_program_lut():
    from repro.core import perf_model

    prog = np.asarray(perf_model.perf_to_cost(perf_model.perf_lut_table()))
    assert np.array_equal(ref.cost_table(), prog)
    # The table does not sit on a rounding edge: float64 agrees.
    assert np.array_equal(ref.cost_table(np.float64), prog)


@pytest.mark.parametrize("shape", [(256, 16, 4), (12_500, 48, 16), (12_500, 192, 48)])
def test_latency_rows_match_plane(shape):
    from repro.core.latency import LatencyPlane
    from repro.core.topology import Topology

    n, per_rack, per_pod = shape
    topo = Topology(n, per_rack, per_pod, 8)
    plane = LatencyPlane.synthesize(topo, 8, seed=987654321)
    roots = np.random.default_rng(1).integers(0, n, 6)
    cluster = ref.Cluster(n, per_rack, per_pod, 8)
    got = ref.latency_rows(cluster, plane.series, 987654321, roots, 5)
    assert np.array_equal(got, plane.latency_rows(roots, 5))


def _round(rng, n_machines, n_tasks, n_jobs, movers, slots):
    task_job = np.sort(rng.integers(0, n_jobs, n_tasks))
    task_job = np.unique(task_job, return_inverse=True)[1]
    J = int(task_job.max()) + 1
    cur = np.full(n_tasks, -1, np.int64)
    cur[n_tasks - movers:] = rng.integers(0, n_machines, movers)
    return ref.RoundInputs(
        t=3.0,
        task_job=task_job.astype(np.int64),
        perf_idx=rng.choice([0, 1, 3], n_tasks).astype(np.int64),
        root_machine=rng.integers(0, n_machines, J).astype(np.int64),
        wait_s=rng.integers(0, 30, n_tasks).astype(np.float32),
        run_s=np.where(cur >= 0, rng.integers(0, 5000, n_tasks), 0).astype(np.float32),
        cur_machine=cur,
        free_slots=rng.integers(0, slots + 1, n_machines).astype(np.int64),
    )


@pytest.mark.parametrize("preemption", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rounds_match_auction_host(preemption, seed):
    from repro.core.latency import LatencyPlane
    from repro.core.policy import PolicyParams, RoundState
    from repro.core.scheduler_backend import RoundContext, make_backend
    from repro.core.topology import Topology

    rng = np.random.default_rng(seed)
    topo = Topology(192, 16, 4, 4)
    plane = LatencyPlane.synthesize(topo, 8, seed=seed)
    params = PolicyParams(preemption=preemption)
    host = make_backend("auction_host", params, topo)
    policy = ref.Policy(preemption=preemption)
    cluster = ref.Cluster(192, 16, 4, 4)
    for T, J, movers in ((7, 3, 0), (60, 9, 20 if preemption else 0), (300, 40, 0)):
        inp = _round(rng, 192, T, J, movers, 4)
        state = RoundState(
            task_job=inp.task_job, perf_idx=inp.perf_idx,
            root_machine=inp.root_machine,
            root_latency=plane.latency_rows(inp.root_machine, int(inp.t)),
            wait_s=inp.wait_s, run_s=inp.run_s, cur_machine=inp.cur_machine,
            free_slots=inp.free_slots.astype(np.int32),
        )
        ctx = RoundContext(np.random.default_rng(0), np.zeros(192, np.int64), T - movers)
        want = np.asarray(host.place(state, ctx).cols, np.int64)
        got = ref.place(inp, cluster, policy, plane.series, seed)
        assert np.array_equal(got, want), (T, J, int((got != want).sum()))


def test_bfloat16_changes_costs():
    import ml_dtypes

    assert (ref.cost_table(ml_dtypes.bfloat16) != ref.cost_table()).any()
