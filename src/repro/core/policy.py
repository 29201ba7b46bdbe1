"""NoMora scheduling policy (paper §5.2) + baseline policies (§6.1).

The policy's cost model, per round:

  d_{t,m}   = round2sig(1 / p(max latency(M_root, M_m))) * 100      (Eq. 6)
  c_{t,r}   = max_{m in r} d_{t,m}                                  (Eq. 8)
  b_t       = max_r c_{t,r}                                         (Eq. 9)
  a_t       = omega * wait_time + gamma                             (Eq. 10)
  preemption: the running task's arc to its current machine is discounted
  by beta (accumulated runtime), Eq. 7; beta=0 => migration decided purely
  on expected performance.

Preference arcs: a machine arc exists iff d <= p_m; a rack arc iff
c <= p_r; the cluster-aggregator arc always exists (cost b_t).

Because all aggregator arcs below the task level have cost 0 and capacities
that never bind beyond machine slots (DESIGN.md §5.1), the cheapest path
from task t to machine m costs exactly

  w(t,m) = d    if d <= p_m          (direct preference arc; d <= c <= b)
         = c_r  elif c_r <= p_r      (via rack aggregator)
         = b_t  otherwise            (via cluster aggregator)

The (T, M+J) matrix (last J columns are the per-job unscheduled
aggregators) is materialised by two interchangeable paths:

- `dense_costs` — the **host reference**: numpy end to end, Eq. 6 through
  the jnp LUT path on every platform (never the Pallas kernel). This is the
  oracle the parity suite and the explicit-graph MCMF (flow_network.py)
  consume.
- `dense_costs_device` / `device_round_costs` — the **fused on-device
  path**: one jitted jnp program running costmap (Pallas or jnp LUT) →
  rack segment-max (Eq. 8) → p_m/p_r/b thresholding → preemption-discount
  scatter (Eq. 7) → unscheduled costs (Eq. 10), returning device arrays
  that feed `auction.solve_transportation_device` with no host↔device
  round trip of the (T, M) matrix. `device_round_costs` takes
  pre-padded inputs (power-of-two task/job buckets, mirroring auction.py)
  so the scheduling hot loop compiles once per bucket instead of once per
  round shape. tests/test_policy_device.py asserts the two paths are
  bit-identical on every output (w, col_capacity, d, c_rack, b, a).

Both the auction solver and the reference MCMF consume the same
ingredients, and tests assert their optima agree. Backend selection
(auction-on-device, auction-on-host, MCMF, solver-driven baselines) lives
in core/scheduler_backend.py.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import perf_model
from .topology import Topology

INF_COST = np.int32(2**30)  # "no arc"

# NoMora machine-arc costs are bounded by construction: perf is clipped to
# >= 1e-2, so cost = round(10/p)*10 <= 10000 (perf_model.perf_to_cost).
# The single source for every host-side float32-exactness guard.
MAX_MACHINE_COST = 10_000


@dataclasses.dataclass(frozen=True)
class PolicyParams:
    """Cost-model parameters (paper §5.2 / §6)."""

    p_m: int = 105  # machine-arc preference threshold
    p_r: int = 110  # rack-arc preference threshold
    omega: float = 1.0  # wait-time escalation factor (per second)
    gamma: int = 1001  # unscheduled offset, > any arc cost (paper §6)
    preemption: bool = False
    beta_scale: float = 100.0 / 3600.0  # cost points per second already run
    unsched_capacity: Optional[int] = None  # None => N_i (DESIGN.md D1)


@dataclasses.dataclass
class RoundState:
    """One scheduling round's inputs (non-root tasks whose root is placed)."""

    task_job: np.ndarray  # (T,) round-local job index 0..J-1
    perf_idx: np.ndarray  # (T,) perf-model index per task
    root_machine: np.ndarray  # (J,) machine of each job's root
    root_latency: np.ndarray  # (J, M) RTT us from each root to every machine
    wait_s: np.ndarray  # (T,) task wait time alpha
    run_s: np.ndarray  # (T,) accumulated runtime beta (running tasks)
    cur_machine: np.ndarray  # (T,) current machine or -1
    free_slots: np.ndarray  # (M,) slots available to this round

    @property
    def n_tasks(self) -> int:
        return int(self.task_job.shape[0])

    @property
    def n_jobs(self) -> int:
        return int(self.root_machine.shape[0])

    @property
    def n_machines(self) -> int:
        return int(self.free_slots.shape[0])


def _rack_pad(n_machines: int, per_rack: int) -> int:
    return -(-n_machines // per_rack) * per_rack


@dataclasses.dataclass
class DenseCosts:
    """w(t, col): columns = machines ++ per-job unscheduled aggregators."""

    w: np.ndarray  # (T, M+J) int32; INF_COST where no arc
    col_capacity: np.ndarray  # (M+J,) int32
    d: np.ndarray  # (T, M) machine arc costs (pre-threshold), for tests
    c_rack: np.ndarray  # (T, R)
    b: np.ndarray  # (T,)
    a: np.ndarray  # (T,) unscheduled costs


def machine_costs(
    lut_table: jnp.ndarray,
    perf_idx: np.ndarray,
    task_root_latency: np.ndarray,
) -> np.ndarray:
    """d_{t,m} for every task x machine (Eq. 6) through the LUT reference.

    Always the jnp LUT path, never the Pallas kernel: this feeds the host
    reference (`dense_costs`, hence ``auction_host``, ``mcmf`` and
    `reference_sim`), which must stay independent of the kernel it checks
    on every platform — on a TPU an auto-selected kernel would make the
    reference compare the device with itself.
    """
    from repro.kernels.costmap import ops as costmap_ops

    return np.asarray(
        costmap_ops.costmap(
            lut_table,
            jnp.asarray(perf_idx),
            jnp.asarray(task_root_latency),
            use_pallas=False,
        )
    )


def dense_costs(
    state: RoundState,
    topo: Topology,
    params: PolicyParams,
    lut_table: Optional[jnp.ndarray] = None,
) -> DenseCosts:
    """Materialise the collapsed NoMora cost matrix for one round."""
    if lut_table is None:
        lut_table = perf_model.perf_lut_table()
    T, J, M = state.n_tasks, state.n_jobs, state.n_machines

    # Eq. 6 per task: latency row is the task's job's root row.
    task_lat = state.root_latency[state.task_job]  # (T, M)
    d = machine_costs(lut_table, state.perf_idx, task_lat)  # (T, M) int32

    # Eq. 8: worst machine per rack (pad partial racks with 0 so max ignores).
    per_rack = topo.machines_per_rack
    Mp = _rack_pad(M, per_rack)
    d_pad = np.zeros((T, Mp), np.int32)
    d_pad[:, :M] = d
    c_rack = d_pad.reshape(T, Mp // per_rack, per_rack).max(axis=2)  # (T, R)
    b = c_rack.max(axis=1)  # (T,) Eq. 9

    rack_of_m = np.arange(M) // per_rack
    c_for_m = c_rack[:, rack_of_m]  # (T, M)
    w_m = np.where(
        d <= params.p_m, d, np.where(c_for_m <= params.p_r, c_for_m, b[:, None])
    ).astype(np.int32)

    # Preemption (Eq. 7): discount the running task's current machine by beta.
    if params.preemption:
        running = state.cur_machine >= 0
        if running.any():
            disc = np.maximum(
                1,
                w_m[running, state.cur_machine[running]]
                - (state.run_s[running] * params.beta_scale).astype(np.int64),
            ).astype(np.int32)
            w_m[running, state.cur_machine[running]] = disc

    # Eq. 10 unscheduled-aggregator columns (one per job; own-job only).
    a = (params.omega * state.wait_s + params.gamma).astype(np.int32)
    w_u = np.full((T, J), INF_COST, np.int32)
    w_u[np.arange(T), state.task_job] = a

    w = np.concatenate([w_m, w_u], axis=1)

    tasks_per_job = np.bincount(state.task_job, minlength=J).astype(np.int32)
    unsched_cap = (
        tasks_per_job
        if params.unsched_capacity is None
        else np.minimum(tasks_per_job, params.unsched_capacity).astype(np.int32)
    )
    col_capacity = np.concatenate([state.free_slots.astype(np.int32), unsched_cap])
    return DenseCosts(w=w, col_capacity=col_capacity, d=d, c_rack=c_rack, b=b, a=a)


# --- Fused on-device cost pipeline -----------------------------------------


def apply_preemption_discount(w_m, cur_machine, run_s, preemption, beta_scale):
    """Eq. 7: discount each running task's current-machine arc by beta.

    One write per row at (t, cur) => no scatter conflicts. Pure and
    un-jitted — the single implementation shared by `cost_round_step` and
    the window program's round body (`core.round_program`), so the
    per-round and scanned paths cannot diverge.
    """
    T = cur_machine.shape[0]
    t_ids = jnp.arange(T, dtype=jnp.int32)
    running = cur_machine >= 0
    cur_safe = jnp.where(running, cur_machine, 0)
    beta_pts = (run_s * beta_scale).astype(jnp.int32)
    disc = jnp.maximum(1, w_m[t_ids, cur_safe] - beta_pts)
    apply = jnp.logical_and(preemption, running)
    return w_m.at[t_ids, cur_safe].set(
        jnp.where(apply, disc, w_m[t_ids, cur_safe])
    )


def cost_round_step(
    lut_table,  # (n_models, LUT_SIZE) f32
    task_job,  # (T,) i32
    perf_idx,  # (T,) i32
    root_latency,  # (J, M) f32
    wait_s,  # (T,) f32
    run_s,  # (T,) f32
    cur_machine,  # (T,) i32; -1 = not running
    p_m,  # i32 scalar
    p_r,  # i32 scalar
    omega,  # f32 scalar
    gamma,  # f32 scalar
    preemption,  # bool scalar
    beta_scale,  # f32 scalar
    *,
    per_rack: int,
    use_pallas: Optional[bool],
    interpret: bool,
):
    """Pure cost-model round step: Eqs. 6-10, ``inputs -> (w_m, a, d, c_rack, b)``.

    Un-jitted and host-callback-free, so it can be traced inside
    `jax.lax.scan` / `jax.vmap` bodies (`core.round_program.RoundProgram`
    scans it across a window of scheduling rounds and vmaps it over what-if
    parameter variants) as well as jitted standalone (`_device_cost_core`).

    Bit-compatible with the numpy `dense_costs` ops: all arithmetic is
    int32/float32 exactly as the host path computes it (numpy's weak-scalar
    promotion keeps float32 there too), so padded-then-sliced outputs match
    the host reference bit for bit (tests/test_policy_device.py). The beta
    discount assumes run_s * beta_scale < 2^31 (true for any replay: the
    host path's int64 headroom is never exercised either).
    """
    from repro.kernels.costmap import ops as costmap_ops

    T = task_job.shape[0]
    M = root_latency.shape[1]

    # None = auto-select exactly like the `costmap` op does for host calls.
    pallas = jax.default_backend() == "tpu" if use_pallas is None else use_pallas
    task_lat = root_latency[task_job]  # (T, M) gather, on device
    d = costmap_ops.costmap_step(
        lut_table, perf_idx, task_lat, use_pallas=pallas, interpret=interpret
    )  # (T, M) i32

    # Eq. 8: worst machine per rack (pad partial racks with 0; real costs
    # are >= 100 so the padding never wins the max).
    Mp = _rack_pad(M, per_rack)
    d_pad = jnp.zeros((T, Mp), jnp.int32).at[:, :M].set(d)
    c_rack = d_pad.reshape(T, Mp // per_rack, per_rack).max(axis=2)  # (T, R)
    b = c_rack.max(axis=1)  # (T,) Eq. 9

    rack_of_m = jnp.arange(M, dtype=jnp.int32) // per_rack
    c_for_m = c_rack[:, rack_of_m]  # (T, M)
    w_m = jnp.where(
        d <= p_m, d, jnp.where(c_for_m <= p_r, c_for_m, b[:, None])
    ).astype(jnp.int32)

    w_m = apply_preemption_discount(
        w_m, cur_machine, run_s, preemption, beta_scale
    )

    # Eq. 10 unscheduled cost per task.
    a = (omega * wait_s + gamma).astype(jnp.int32)
    return w_m, a, d, c_rack, b


# Jitted standalone round step (the per-round `AuctionBackend` path).
_device_cost_core = functools.partial(
    jax.jit, static_argnames=("per_rack", "use_pallas", "interpret")
)(cost_round_step)


def device_round_costs(
    state: RoundState,
    topo,
    params: PolicyParams,
    lut_table: jnp.ndarray,
    *,
    n_pad_tasks: Optional[int] = None,
    n_pad_jobs: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused device cost build: (w_m, a, d, c_rack, b) as device arrays.

    ``n_pad_tasks`` / ``n_pad_jobs`` pad the varying round dimensions to
    fixed buckets before entering the jit (rows >= T are garbage and must be
    masked inactive downstream); the machine dimension is naturally static
    per cluster. With no padding the outputs have exact (T, ...) shapes and
    are bit-identical to the host `dense_costs` fields.
    """
    T, J, M = state.n_tasks, state.n_jobs, state.n_machines
    Tp = T if n_pad_tasks is None else max(n_pad_tasks, T)
    Jp = J if n_pad_jobs is None else max(n_pad_jobs, J)

    task_job = np.zeros(Tp, np.int32)
    task_job[:T] = state.task_job
    perf_idx = np.zeros(Tp, np.int32)
    perf_idx[:T] = state.perf_idx
    wait_s = np.zeros(Tp, np.float32)
    wait_s[:T] = state.wait_s
    run_s = np.zeros(Tp, np.float32)
    run_s[:T] = state.run_s
    cur = np.full(Tp, -1, np.int32)
    cur[:T] = state.cur_machine
    root_lat = np.zeros((Jp, M), np.float32)
    root_lat[:J] = state.root_latency

    return _device_cost_core(
        lut_table,
        jnp.asarray(task_job),
        jnp.asarray(perf_idx),
        jnp.asarray(root_lat),
        jnp.asarray(wait_s),
        jnp.asarray(run_s),
        jnp.asarray(cur),
        jnp.int32(params.p_m),
        jnp.int32(params.p_r),
        jnp.float32(params.omega),
        jnp.float32(params.gamma),
        jnp.bool_(params.preemption),
        jnp.float32(params.beta_scale),
        per_rack=topo.machines_per_rack,
        # None = let the costmap op auto-select (Pallas on TPU, jnp LUT
        # elsewhere), exactly like the host path's kernel invocation.
        use_pallas=use_pallas,
        interpret=interpret,
    )


def dense_costs_device(
    state: RoundState,
    topo,
    params: PolicyParams,
    lut_table: Optional[jnp.ndarray] = None,
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> DenseCosts:
    """Device twin of `dense_costs`: same fields, jnp arrays, exact shapes.

    The parity reference API: every field is bit-identical to the numpy
    path (`np.asarray` the fields to compare). The scheduler hot path uses
    `device_round_costs` + `auction.solve_transportation_device` directly
    and never materialises the (T, M+J) concatenation or the aggregator
    capacities this builds for the flow-network view.
    """
    if lut_table is None:
        lut_table = perf_model.perf_lut_table()
    T, J, M = state.n_tasks, state.n_jobs, state.n_machines
    w_m, a, d, c_rack, b = device_round_costs(
        state, topo, params, lut_table, use_pallas=use_pallas, interpret=interpret
    )
    w_u = jnp.full((T, J), INF_COST, jnp.int32).at[
        jnp.arange(T), jnp.asarray(state.task_job)
    ].set(a)
    w = jnp.concatenate([w_m, w_u], axis=1)
    tasks_per_job = (
        jnp.zeros(J, jnp.int32).at[jnp.asarray(state.task_job)].add(1)
    )
    unsched_cap = (
        tasks_per_job
        if params.unsched_capacity is None
        else jnp.minimum(tasks_per_job, params.unsched_capacity).astype(jnp.int32)
    )
    col_capacity = jnp.concatenate(
        [jnp.asarray(state.free_slots.astype(np.int32)), unsched_cap]
    )
    return DenseCosts(
        w=w, col_capacity=col_capacity, d=d, c_rack=c_rack, b=b, a=a
    )


# --- Baseline policies (paper §6.1) ----------------------------------------


# Crossover between the seed per-task numpy scan (O(T*M) C-speed ops, wins
# on small rounds) and the tree/heap paths (O(M + T log M) Python-level
# ops, win once T*M is large). Both branches are bit-identical; parity
# tests force each explicitly.
DENSE_SCAN_OPS = 1 << 16


def random_placement(
    rng: np.random.Generator,
    n_tasks: int,
    free_slots: np.ndarray,
    *,
    dense_scan_ops: int = DENSE_SCAN_OPS,
) -> np.ndarray:
    """Random policy: tasks always schedule if resources are idle.

    Returns machine per task (-1 if the cluster is full). Sampling is uniform
    over free *slots*, updating availability as tasks land.

    Draw-for-draw identical to the seed per-task loop (one bounded
    ``rng.integers`` per placement with a shrinking bound): the bounds are
    deterministic, so all T draws batch into one generator call (numpy's
    bounded-integer routine consumes the stream per element exactly like T
    scalar calls, asserted in tests/test_policy.py). Selection of the k-th
    free slot then runs the seed cumsum scan for small rounds and a Fenwick
    tree (built in log M vectorised passes, O(log M) per draw) once T*M
    would dominate — the Google-trace regime (12,500 machines, 1k-task
    rounds) where the seed loop's O(T*M) was the bottleneck.
    """
    free = free_slots.astype(np.int64)
    out = np.full(n_tasks, -1, np.int64)
    total = int(free.sum())
    n = min(n_tasks, total)
    if n == 0:
        return out
    # Bounds shrink by exactly one per draw (every draw places a task).
    ks = rng.integers(0, np.arange(total, total - n, -1))
    M = len(free)

    if n * M <= dense_scan_ops:  # seed scan: C-speed cumsum per draw
        freec = free.copy()
        for t in range(n):
            m = int(np.searchsorted(np.cumsum(freec), int(ks[t]), side="right"))
            out[t] = m
            freec[m] -= 1
        return out

    # Fenwick tree over per-machine free-slot counts; selecting the k-th
    # free slot in machine order matches searchsorted(cumsum, k, 'right').
    size = 1
    while size < M:
        size *= 2
    tree_np = np.zeros(size + 1, np.int64)
    tree_np[1 : M + 1] = free
    step = 1
    while step < size:  # pairwise build: log M vectorised adds
        idx = np.arange(2 * step, size + 1, 2 * step)
        tree_np[idx] += tree_np[idx - step]
        step *= 2
    tree = tree_np.tolist()  # python ints: ~10x faster scalar indexing
    for t in range(n):
        rem = int(ks[t])
        pos = 0
        bit = size
        while bit:
            nxt = pos + bit
            if nxt <= size and tree[nxt] <= rem:
                rem -= tree[nxt]
                pos = nxt
            bit >>= 1
        out[t] = pos  # largest prefix <= k => machine owning slot k
        i = pos + 1
        while i <= size:
            tree[i] -= 1
            i += i & -i
    return out


def load_spreading_placement(
    task_counts: np.ndarray,
    free_slots: np.ndarray,
    n_tasks: int,
    *,
    dense_scan_ops: int = DENSE_SCAN_OPS,
) -> np.ndarray:
    """Load-spreading policy: each task goes to the least-loaded machine.

    Small rounds run the seed per-task masked argmin (C-speed over M);
    large rounds switch to a heap — O(M + T log M) instead of O(T*M),
    bit-identical output: (count, machine) tuples pop in the same order
    argmin ties break (lowest machine id among minima), and each machine
    keeps exactly one live heap entry so there is no stale state to
    reconcile.
    """
    free = free_slots.astype(np.int64).copy()
    out = np.full(n_tasks, -1, np.int64)
    n = min(n_tasks, int(free.sum()))

    if n * len(free) <= dense_scan_ops:  # seed scan
        counts = task_counts.astype(np.int64).copy()
        for t in range(n_tasks):
            avail = free > 0
            if not avail.any():
                break
            masked = np.where(avail, counts, np.iinfo(np.int64).max)
            m = int(np.argmin(masked))
            out[t] = m
            counts[m] += 1
            free[m] -= 1
        return out

    heap = [
        (int(task_counts[m]), m) for m in range(len(free)) if free[m] > 0
    ]
    heapq.heapify(heap)
    for t in range(n_tasks):
        if not heap:
            break
        c, m = heapq.heappop(heap)
        out[t] = m
        free[m] -= 1
        if free[m] > 0:
            heapq.heappush(heap, (c + 1, m))
    return out
