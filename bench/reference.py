"""Plain reference of one NoMora scheduling round, in numpy.

Independent of the scheduler under test: it imports nothing of ``repro``
and takes no table the program makes. From a round's inputs (which tasks,
their jobs' root machines, wait and run times, current machines, free
slots, the second ``t``) and the latency plane's measured series, it
computes the placement the paper's policy defines:

1. RTT rows from each job's root machine (the plane's pair recipe: tier by
   rack/pod, trace id and scale coefficient from a splitmix64 pair hash).
2. Eq. 6: latency rounded to the 10 us grid, the application's
   performance model (Eqs. 2-5, coefficients verbatim) at that grid
   point, cost = round(10 / p) * 10.
3. Eqs. 8-9: worst machine cost per rack and over the cluster; the
   preference thresholds p_m / p_r pick the cheapest arc to each machine.
4. Eq. 7: a running task's current machine discounted by its runtime.
5. Eq. 10: unscheduled cost omega * wait + gamma.
6. The transportation problem solved by the forward auction the
   configuration states: "similar objects" slot prices from zero, eps = 1
   on integer costs with a deterministic tie jitter in [0, 9), Jacobi
   rounds in which every unassigned task bids at once, the highest bid per
   machine winning (ties to the lowest task id, the best machine of a task
   being the lowest-indexed among equals).

All arithmetic is float32 / int32, as the configuration states.
``precision="bfloat16"`` computes the cost model (steps 1-5) in bfloat16
instead: the control that a correct comparison must fail.

It solves ``place`` rounds on the static plane, and implements neither of
a configuration's optional blocks: a ``plane`` block's hotspots, regime
shifts or storms change the rows of step 1, and a ``scheduler`` block
turns on rounds of other kinds (``check.load_reference`` refuses both).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

F32 = np.float32

KINDS = ("place",)  # the kinds of logged round this reference solves
BLOCKS = ()  # the optional configuration blocks it implements

# Paper Eqs. 2-5: (threshold_us, ascending polynomial coefficients), in
# the program's model order (memcached, strads, spark, tensorflow).
PERF_MODELS = (
    (40.0, (1.067, -3.093e-3, 4.084e-6, -1.898e-9)),
    (20.0, (1.009, -2.095e-3, 2.571e-6, -1.232e-9)),
    (200.0, (1.0199, -1.161e-4)),
    (40.0, (1.005, -5.146e-4, 5.837e-7, -3.46e-10)),
)
LUT_STEP_US = 10.0
LUT_SIZE = 101  # 0, 10, ..., 1000 us

# Latency plane pair recipe (paper §6).
TIER_SAME, TIER_RACK, TIER_POD, TIER_INTER = 0, 1, 2, 3
TRACES_PER_TIER = 6
SAME_MACHINE_RTT_US = 2.0
TIER_COEFF = {TIER_RACK: (0.5, 1.0), TIER_POD: (0.8, 1.2), TIER_INTER: (0.8, 1.2)}

NEG_VALUE = F32(-(2.0**40))
PRICE_LOCK = F32(2.0**40)
NEG_INF = F32(-(2.0**62))


@dataclasses.dataclass(frozen=True)
class Policy:
    p_m: int = 105
    p_r: int = 110
    omega: float = 1.0
    gamma: int = 1001
    preemption: bool = False
    beta_scale: float = 100.0 / 3600.0
    tie_jitter: int = 9
    eps: float = 1.0

    @classmethod
    def from_dict(cls, d: dict) -> "Policy":
        if d.get("exact", False):
            # Exact mode scales costs by (T + 1) before the auction; the
            # reference implements the unscaled auction only.
            raise ValueError("the reference implements the solver with exact = false")
        return cls(**{k: d[k] for k in d if k in cls.__dataclass_fields__})


@dataclasses.dataclass
class RoundInputs:
    """One round's inputs, as recorded from the timed path."""

    t: float
    task_job: np.ndarray  # (T,) round-local job index
    perf_idx: np.ndarray  # (T,)
    root_machine: np.ndarray  # (J,)
    wait_s: np.ndarray  # (T,) f32
    run_s: np.ndarray  # (T,) f32
    cur_machine: np.ndarray  # (T,) -1 = not running
    free_slots: np.ndarray  # (M,)


# ------------------------------------------------------------------ #
# Cost table (Eq. 6 on the 10 us grid)


def _to(x, dtype):
    return np.asarray(x).astype(dtype)


def cost_table(dtype=F32) -> np.ndarray:
    """(4, 101) int64 cost per model and 10 us grid step."""
    grid = _to(np.arange(LUT_SIZE) * LUT_STEP_US, dtype)
    rows = []
    for thr, coeffs in PERF_MODELS:
        poly = np.zeros_like(grid)
        power = np.ones_like(grid)
        for k, c in enumerate(coeffs):
            if k:
                power = (power * grid).astype(dtype)
            poly = (poly + (_to(c, dtype) * power).astype(dtype)).astype(dtype)
        p = np.where(grid < _to(thr, dtype), _to(1.0, dtype), poly).astype(dtype)
        p = np.clip(p, _to(1e-2, dtype), _to(1.0, dtype)).astype(dtype)
        inv = (_to(1.0, dtype) / p).astype(dtype)
        rows.append(np.round((inv * _to(10.0, dtype)).astype(dtype)).astype(np.int64) * 10)
    return np.stack(rows)


# ------------------------------------------------------------------ #
# Latency rows


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


@dataclasses.dataclass(frozen=True)
class Cluster:
    n_machines: int
    machines_per_rack: int
    racks_per_pod: int
    slots_per_machine: int

    @classmethod
    def from_dict(cls, d: dict) -> "Cluster":
        return cls(**{k: int(d[k]) for k in cls.__dataclass_fields__})


def latency_rows(
    cluster: Cluster, series: np.ndarray, plane_seed: int,
    roots: np.ndarray, t: float,
) -> np.ndarray:
    """(J, M) f32 RTT (us) from each root machine to every machine at
    second ``t``; ``series`` is the plane's (4, 6, T) measured series."""
    M = cluster.n_machines
    a = np.asarray(roots, np.int64)[:, None]
    b = np.arange(M, dtype=np.int64)[None, :]
    rack_a, rack_b = a // cluster.machines_per_rack, b // cluster.machines_per_rack
    pod_a, pod_b = rack_a // cluster.racks_per_pod, rack_b // cluster.racks_per_pod
    tiers = np.where(
        a == b, TIER_SAME,
        np.where(rack_a == rack_b, TIER_RACK,
                 np.where(pod_a == pod_b, TIER_POD, TIER_INTER)),
    )
    lo = np.minimum(a, b).astype(np.uint64)
    hi = np.maximum(a, b).astype(np.uint64)
    with np.errstate(over="ignore"):
        h = _splitmix64(lo * np.uint64(0x100000001B3) + hi + np.uint64(plane_seed))
    trace_id = ((h >> np.uint64(32)) % np.uint64(TRACES_PER_TIER)).astype(np.int64)
    u = (h & np.uint64(0xFFFFFFFF)).astype(np.float64) / 2**32
    c_lo = np.ones_like(u)
    c_hi = np.ones_like(u)
    for tier, (l, r) in TIER_COEFF.items():
        c_lo[tiers == tier] = l
        c_hi[tiers == tier] = r
    coeff = (c_lo + u * (c_hi - c_lo)).astype(F32)
    lat = series[tiers, trace_id, int(t)] * coeff
    lat[tiers == TIER_SAME] = SAME_MACHINE_RTT_US
    return lat.astype(F32)


# ------------------------------------------------------------------ #
# Cost model (Eqs. 6-10)


def machine_costs(
    lat: np.ndarray, perf_idx: np.ndarray, table: np.ndarray, dtype=F32
) -> np.ndarray:
    """Eq. 6 per task x machine: (T, M) int64."""
    q = (_to(lat, dtype) / _to(LUT_STEP_US, dtype)).astype(dtype)
    step = np.clip(np.round(q).astype(np.float64), 0, LUT_SIZE - 1).astype(np.int64)
    return table[np.asarray(perf_idx, np.int64)[:, None], step]


def round_costs(
    inp: RoundInputs, cluster: Cluster, policy: Policy, series: np.ndarray,
    plane_seed: int, precision: str = "float32",
):
    """``(w_m (T, M) int64, a (T,) int64)``: each task's cheapest arc cost
    to every machine, and its unscheduled cost."""
    dtype = F32
    if precision == "bfloat16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    table = cost_table(dtype)
    rows = latency_rows(cluster, series, plane_seed, inp.root_machine, inp.t)
    d = machine_costs(rows[np.asarray(inp.task_job, np.int64)], inp.perf_idx, table, dtype)
    T, M = d.shape
    per = cluster.machines_per_rack
    R = -(-M // per)
    d_pad = np.zeros((T, R * per), np.int64)
    d_pad[:, :M] = d
    c_rack = d_pad.reshape(T, R, per).max(axis=2)
    b = c_rack.max(axis=1)
    c_m = c_rack[:, np.arange(M) // per]
    w = np.where(d <= policy.p_m, d, np.where(c_m <= policy.p_r, c_m, b[:, None]))
    if policy.preemption:
        rows_i = np.nonzero(np.asarray(inp.cur_machine) >= 0)[0]
        cur = np.asarray(inp.cur_machine, np.int64)[rows_i]
        beta = (_to(inp.run_s, F32)[rows_i] * F32(policy.beta_scale)).astype(F32)
        w[rows_i, cur] = np.maximum(1, w[rows_i, cur] - beta.astype(np.int32))
    a = (_to(inp.wait_s, F32) * F32(policy.omega) + F32(policy.gamma)).astype(
        F32
    ).astype(np.int32).astype(np.int64)
    return w, a


# ------------------------------------------------------------------ #
# Auction


def jitter(n_rows: int, n_cols: int, tie_jitter: int) -> np.ndarray:
    """Deterministic per-(task row, machine) tie jitter in [0, tie_jitter)."""
    tt = np.arange(n_rows, dtype=np.uint64)[:, None]
    mm = np.arange(n_cols, dtype=np.uint64)[None, :]
    with np.errstate(over="ignore"):
        h = tt * np.uint64(0x9E3779B97F4A7C15) + mm * np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(29)
    return (h % np.uint64(tie_jitter)).astype(np.int64)


def auction(
    w: np.ndarray, a: np.ndarray, task_job: np.ndarray, free_slots: np.ndarray,
    slots_per_machine: int, policy: Policy,
) -> np.ndarray:
    """Column per task: a machine in [0, M), or ``M + job`` (unscheduled)."""
    T, M = w.shape
    if T == 0:
        return np.zeros(0, np.int64)
    wj = w + jitter(T, M, policy.tie_jitter) if policy.tie_jitter > 0 else w
    vm = (-wj).astype(F32)
    vu = (-a).astype(F32)
    job_col = M + np.asarray(task_job, np.int64)
    S = int(slots_per_machine)
    price = np.where(
        np.arange(S)[None, :] >= np.asarray(free_slots)[:, None], PRICE_LOCK, F32(0)
    ).astype(F32)
    owner = np.full((M, S), -1, np.int64)
    assigned = np.full(T, -1, np.int64)
    eps = F32(policy.eps)
    m_ids = np.arange(M)
    while True:
        bidders = np.nonzero(assigned < 0)[0]
        if not len(bidders):
            return assigned
        slot1 = np.argmin(price, axis=1)
        price1 = price[m_ids, slot1]
        masked = price.copy()
        masked[m_ids, slot1] = PRICE_LOCK
        price2 = masked.min(axis=1)
        v1 = vm[bidders] - price1[None, :]
        best_m = np.argmax(v1, axis=1)
        rows = np.arange(len(bidders))
        best_v = v1[rows, best_m]
        v1[rows, best_m] = NEG_INF
        second = np.maximum(
            v1.max(axis=1), vm[bidders, best_m] - price2[best_m]
        ).astype(F32)
        u = vu[bidders]
        to_unsched = u > best_v
        second_m = np.maximum(second, u)
        bid = (price1[best_m] + (best_v - second_m) + eps).astype(F32)
        # Unscheduled offers are taken at once (their price stays 0).
        assigned[bidders[to_unsched]] = job_col[bidders[to_unsched]]
        keep = ~to_unsched
        tasks, machines, bids = bidders[keep], best_m[keep], bid[keep]
        # Highest bid per machine; ties to the lowest task id.
        order = np.lexsort((tasks, -bids.astype(np.float64), machines))
        first = np.ones(len(order), bool)
        first[1:] = machines[order][1:] != machines[order][:-1]
        win = order[first]
        for task, m, b in zip(tasks[win], machines[win], bids[win]):
            s = slot1[m]
            prev = owner[m, s]
            if prev >= 0:
                assigned[prev] = -1
            owner[m, s] = task
            price[m, s] = b
            assigned[task] = m


def place(
    inp: RoundInputs, cluster: Cluster, policy: Policy, series: np.ndarray,
    plane_seed: int, precision: str = "float32",
    costs: Optional[tuple] = None, config: Optional[dict] = None,
) -> np.ndarray:
    """The round's placement columns, per the paper's policy. ``config``
    is the run's configuration, which this reference needs no more of."""
    w, a = costs if costs is not None else round_costs(
        inp, cluster, policy, series, plane_seed, precision
    )
    return auction(w, a, inp.task_job, inp.free_slots, cluster.slots_per_machine, policy)
