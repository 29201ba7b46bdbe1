"""Epsilon-scaling auction solver for the collapsed NoMora instance.

DESIGN.md §5.1 shows the NoMora flow network reduces exactly to a
transportation problem: assign each task one unit to a machine (capacity =
free slots) or to its job's unscheduled aggregator (effectively unbounded
capacity at cost a_t). We solve it with Bertsekas' auction algorithm in the
"similar objects" form (Bertsekas & Castanon 1989): one price per machine
*slot*, machines offer their cheapest slot, and the runner-up offer may be
the same machine's second-cheapest slot.

Exactness: costs are integers; we scale them by (n_tasks + 1) and run a
single forward-auction phase from *zero initial prices* with eps = 1. For
the asymmetric problem (slots may stay free) complementary slackness
requires free slots to end at price 0 — which zero-start forward auction
guarantees (a slot that was never successfully bid keeps its initial
price), while persistent/warm prices would violate it (we measured the
effect: warm-started epsilon-scaling returned +30% cost on random
instances — see EXPERIMENTS.md §Perf for the confirmed-refuted log).
The standard bound total <= opt + n_tasks * eps then pins the scaled
optimum exactly (property-tested against the reference MCMF and networkx
in tests/test_auction.py). Scaled values are kept < 2^24 so float32 VPU
arithmetic stays exact. Price wars between same-job tasks (identical cost
rows) self-limit because bid increments are the real top-2 margins, not
bare eps steps.

All state is fixed-shape JAX arrays; each Jacobi round is one jitted step:
  1. bid_top2 over the (T, M) machine value matrix (the Pallas kernel's op)
     merged with the task's own unscheduled offer,
  2. conflict resolution by packed segment-max per machine,
  3. mark-based scatter updates of slot prices / owners / assignments
     (winner sets are duplicate-free by construction; evictions are applied
     through add-scatter marks to avoid duplicate-index write races).
Shapes are padded to power-of-two buckets to bound retracing across
scheduling rounds; prices warm-start from the previous round (DESIGN.md §4
item 5 - the dense analogue of Firmament's incremental solver reuse).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.auction_bid import ops as bid_ops

from .policy import INF_COST

NEG_VALUE = jnp.float32(-(2.0**40))  # value of a forbidden column
PRICE_LOCK = jnp.float32(2.0**40)  # price of a slot beyond a machine's capacity
_F32_EXACT = 2**24  # |ints| exactly representable in float32


def _bucket(n: int, lo: int = 8) -> int:
    """Power-of-two padding bucket with floor ``lo``.

    The floor bounds retracing (one compilation per bucket per program);
    8 keeps at most two extra compilations over the old floor of 32 while
    letting the small rounds that dominate 1s-cadence trace replays run
    (8, M)-shaped pipelines instead of (32, M) — a 4x cut in per-iteration
    element traffic exactly where per-round dispatch overhead already
    dominates."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class AuctionResult:
    assigned_col: np.ndarray  # (T,) machine id, or the task's unsched column
    total_cost: int
    iterations: int
    prices: np.ndarray  # (M, S) final slot prices (scaled units)


def auction_phase_step(
    price,  # (M, S) f32 slot prices (scaled integer units)
    values_m,  # (T, M) f32 scaled values (-cost), NEG_VALUE forbidden
    value_u,  # (T,) f32 scaled value of the task's own unscheduled column
    job_col,  # (T,) i32 column id of the task's unscheduled aggregator
    active,  # (T,) bool real (non-padding) tasks
    eps,  # f32 scalar
    max_iters: int,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Pure auction phase: ``(price0, values, ...) -> (price, owner, assigned, iters)``.

    Un-jitted and host-callback-free so `core.round_program.RoundProgram`
    can trace it inside `jax.lax.scan` (a window of rounds) and `jax.vmap`
    (the what-if axis); `_auction_phase` is the jitted standalone wrapper
    the per-round solve paths call. All price/bid arithmetic is on exact
    integer-valued float32, so results are bit-identical wherever the step
    is inlined.
    """
    T, M = values_m.shape
    pallas = jax.default_backend() == "tpu" if use_pallas is None else use_pallas
    m_ids = jnp.arange(M, dtype=jnp.int32)

    owner = jnp.full((M, price.shape[1]), -1, jnp.int32)
    assigned = jnp.where(active, jnp.int32(-1), jnp.int32(0))

    def cond(state):
        _, _, assigned, it = state
        return jnp.logical_and(
            jnp.any(jnp.logical_and(assigned < 0, active)), it < max_iters
        )

    def body(state):
        price, owner, assigned, it = state
        unassigned = jnp.logical_and(assigned < 0, active)

        # Per-machine cheapest and second-cheapest slot. The equality mask
        # fuses into the min reduction (a scatter would copy live `price`).
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, price.shape, 1)
        price1 = jnp.min(price, axis=1)  # (M,)
        slot1 = jnp.argmin(price, axis=1).astype(jnp.int32)
        price2 = jnp.min(
            jnp.where(slot_iota == slot1[:, None], PRICE_LOCK, price), axis=1
        )

        best_m, best_v, second_v = bid_ops.bid_top2_step(
            values_m, price1, price2, use_pallas=pallas, interpret=interpret
        )

        # Merge the task's own unscheduled offer (price pinned at 0).
        u_better = value_u > best_v
        second_for_machine = jnp.maximum(second_v, value_u)
        bids_unsched = jnp.logical_and(unassigned, u_better)
        bids_machine = jnp.logical_and(unassigned, jnp.logical_not(u_better))

        # Machine bid level: beat the runner-up offer by eps.
        bid_level = price1[best_m] + (best_v - second_for_machine) + eps

        # Conflict resolution: max bid per machine, ties broken to the
        # lowest task id (bid levels are integer-valued f32 so equality is
        # exact). Two bit-identical strategies, chosen statically by shape:
        t_ids = jnp.arange(T, dtype=jnp.int32)
        bids = jnp.where(bids_machine, bid_level, jnp.float32(-1.0))
        if T * T <= 4 * M:
            # T-space: a (T, T) same-machine dominance table. For the
            # small rounds that dominate 1s-cadence replays this removes
            # every O(M)-sized intermediate of the segment path (the
            # pairwise table is tiny next to the (T, M) bid pass).
            same_m = best_m[:, None] == best_m[None, :]
            dominated = jnp.logical_or(
                bids[None, :] > bids[:, None],
                jnp.logical_and(
                    bids[None, :] == bids[:, None],
                    t_ids[None, :] < t_ids[:, None],
                ),
            )
            loses = jnp.any(jnp.logical_and(same_m, dominated), axis=1)
            winner = jnp.logical_and(bids_machine, jnp.logical_not(loses))
            win_slot_t = slot1[best_m]
            evicted_t = jnp.where(winner, owner[best_m, win_slot_t], -1)

            # Per-machine winners are unique, so the T-sized scatters are
            # duplicate-free; losers write to the OOB row M and drop.
            win_m_t = jnp.where(winner, best_m, M)
            price = price.at[win_m_t, win_slot_t].set(bids, mode="drop")
            owner = owner.at[win_m_t, win_slot_t].set(t_ids, mode="drop")

            # Evictees are disjoint from winners (winners were unassigned,
            # evictees held a slot); -1 would wrap as a negative index, so
            # remap to the positive OOB sentinel T before the drop-scatter.
            evict_tgt = jnp.where(evicted_t >= 0, evicted_t, T)
            evict_mark = (
                jnp.zeros((T,), jnp.int32).at[evict_tgt].add(1, mode="drop")
            )
            assigned = jnp.where(evict_mark > 0, -1, assigned)
            assigned = jnp.where(winner, best_m, assigned)
            assigned = jnp.where(bids_unsched, job_col, assigned)
            return price, owner, assigned, it + 1

        # M-space: two-pass segment reduction over machines (big rounds,
        # where a (T, T) table would dwarf the O(M) intermediates).
        win_bid = jax.ops.segment_max(bids, best_m, num_segments=M)
        has_winner = win_bid >= 0
        is_winner_cand = jnp.logical_and(bids_machine, bids == win_bid[best_m])
        win_task = jax.ops.segment_min(
            jnp.where(is_winner_cand, t_ids, T), best_m, num_segments=M
        )
        win_task = jnp.where(has_winner, win_task, 0)
        win_slot = slot1

        evicted = jnp.where(has_winner, owner[m_ids, win_slot], -1)

        # Slot updates (per-machine, no duplicates). Masked writes are
        # expressed as out-of-bounds row indices with mode='drop' — one
        # scatter each, no gather+select round trip, identical results.
        win_m = jnp.where(has_winner, m_ids, M)
        price = price.at[win_m, win_slot].set(win_bid, mode="drop")
        owner = owner.at[win_m, win_slot].set(win_task, mode="drop")

        # Eviction marks (duplicate-safe add-scatter; winners and evictees
        # are disjoint: winners were unassigned, evictees held a slot).
        # -1 would wrap like a normal negative index, so remap it to the
        # positive OOB sentinel T before the dropping scatter.
        evict_tgt = jnp.where(evicted >= 0, evicted, T)
        evict_mark = jnp.zeros((T,), jnp.int32).at[evict_tgt].add(1, mode="drop")

        # Winner marks (each task bids on exactly one machine => no dups).
        win_tgt = jnp.where(has_winner, win_task, T)
        win_mark = jnp.zeros((T,), jnp.int32).at[win_tgt].add(1, mode="drop")
        win_col = jnp.zeros((T,), jnp.int32).at[win_tgt].add(
            m_ids + 1, mode="drop"
        )

        assigned = jnp.where(evict_mark > 0, -1, assigned)
        assigned = jnp.where(win_mark > 0, win_col - 1, assigned)
        assigned = jnp.where(bids_unsched, job_col, assigned)
        return price, owner, assigned, it + 1

    price, owner, assigned, iters = jax.lax.while_loop(
        cond, body, (price, owner, assigned, jnp.int32(0))
    )
    return price, owner, assigned, iters


# Jitted standalone phase (the per-round solve paths).
_auction_phase = functools.partial(
    jax.jit, static_argnames=("max_iters", "use_pallas", "interpret")
)(auction_phase_step)


def solve_transportation(
    w: np.ndarray,  # (T, C) int costs, INF_COST = forbidden; C = M + J
    machine_capacity: np.ndarray,  # (M,) slots per machine
    n_machines: int,
    task_job_col: np.ndarray,  # (T,) column id (>= M) of each task's unsched agg
    *,
    warm_prices: np.ndarray | None = None,  # accepted, unused (see module doc)
    slots_per_machine: int | None = None,
    eps: float = 1.0,
    max_iters_per_phase: int = 500_000,
    tie_jitter: int = 0,
    exact: bool = True,
) -> AuctionResult:
    """Solve min-cost assignment of tasks to machine slots / unscheduled.

    `exact=True` scales costs by (T+1) so eps=1 pins the true optimum —
    but that also stretches every tie-breaking price war by the same
    factor (~450x at T=452; measured >500k Jacobi iterations on migration
    rounds, EXPERIMENTS.md §Perf S4). `exact=False` runs on unscaled
    integer costs with eps=1: suboptimality <= 1 cost unit per task,
    an order of magnitude below the 10-unit cost quantum of the paper's
    rounding — the scheduler default.

    `eps` > 1 further trades exactness for speed (suboptimality <=
    T*eps/scale in original cost units).

    `tie_jitter` > 0 adds a deterministic per-(task, machine) jitter in
    [0, tie_jitter) to machine costs. NoMora costs are multiples of 10
    (round(10/p)*10), so jitter <= 9 never reorders distinct cost levels
    but breaks the mass ties that otherwise degenerate the auction into
    +eps price crawls (hundreds of equal-cost tasks contesting equal-cost
    slots). Suboptimality vs the unjittered costs <= (tie_jitter-1) per
    task — below one cost quantum. Exactness tests use tie_jitter=0.
    """
    del warm_prices
    T, C = w.shape
    if tie_jitter > 0 and T > 0:
        M_ = n_machines
        w = w.copy()
        jit = _jitter_matrix_np(T, M_, tie_jitter).astype(np.int64)
        mcols = w[:, :M_]
        w[:, :M_] = np.where(mcols < int(INF_COST), mcols + jit, mcols)
    M = n_machines
    if T == 0:
        return AuctionResult(
            assigned_col=np.zeros((0,), np.int64),
            total_cost=0,
            iterations=0,
            prices=np.zeros((M, int(slots_per_machine or 1)), np.float32),
        )
    assert task_job_col.min() >= M and task_job_col.max() < C

    S = int(slots_per_machine or max(1, int(machine_capacity.max(initial=1))))
    Tp = _bucket(T)
    # exactness needs final eps < 1/n_assigned in original units
    scale = (T + 1) if exact else 1

    w_m = w[:, :M].astype(np.int64)
    finite = w_m < int(INF_COST)
    max_cost = int(np.max(np.where(finite, w_m, 0), initial=1))
    max_unsched = int(np.max(w[np.arange(T), task_job_col]))
    # Prices/bids stay within ~2x the value spread; keep 4x headroom for
    # exact float32 integer arithmetic.
    if max(max_cost, max_unsched) * scale * 4 >= _F32_EXACT:
        raise ValueError(
            f"scaled costs exceed float32-exact range: "
            f"{max(max_cost, max_unsched)} * {scale} * 4 >= 2^24"
        )

    vm = np.where(finite, (-w_m * scale).astype(np.float32), np.float32(NEG_VALUE))
    vu = (-w[np.arange(T), task_job_col].astype(np.int64) * scale).astype(np.float32)

    vm_p = np.full((Tp, M), np.float32(NEG_VALUE), np.float32)
    vm_p[:T] = vm
    vu_p = np.zeros((Tp,), np.float32)
    vu_p[:T] = vu
    jobcol_p = np.full((Tp,), M, np.int32)
    jobcol_p[:T] = task_job_col
    active = np.zeros((Tp,), bool)
    active[:T] = True

    # Zero initial prices: free slots provably end at price 0 (CS for the
    # asymmetric problem). Slots beyond a machine's capacity are locked.
    price0 = np.zeros((M, S), np.float32)
    locked = np.arange(S)[None, :] >= machine_capacity[:, None]
    price0[locked] = float(PRICE_LOCK)

    # The host reference bids through the jnp top-2, never the Pallas
    # kernel, so on a TPU it stays independent of the device path.
    price, _, assigned, iters = _auction_phase(
        jnp.asarray(price0),
        jnp.asarray(vm_p),
        jnp.asarray(vu_p),
        jnp.asarray(jobcol_p),
        jnp.asarray(active),
        jnp.float32(eps),
        max_iters_per_phase,
        use_pallas=False,
    )
    total_iters = int(iters)
    if total_iters >= max_iters_per_phase:
        raise RuntimeError(f"auction hit the iteration cap ({max_iters_per_phase})")

    assigned_np = np.asarray(assigned)[:T]
    if (assigned_np < 0).any():
        raise RuntimeError("auction did not converge: unassigned tasks remain")
    col = assigned_np.astype(np.int64)
    costs = w[np.arange(T), col].astype(np.int64)
    return AuctionResult(
        assigned_col=col,
        total_cost=int(costs.sum()),
        iterations=total_iters,
        prices=np.asarray(price),
    )


# --- Fully on-device round: cost arrays in, assignment out ------------------


def _jitter_matrix_np(n_rows: int, n_cols: int, tie_jitter: int) -> np.ndarray:
    """Deterministic per-(task, machine) tie jitter in [0, tie_jitter).

    The single source of truth for both solve paths — host rounds apply it
    directly, device rounds upload it once per bucket shape — so host and
    device rounds place identically bit for bit.
    """
    tt = np.arange(n_rows, dtype=np.uint64)[:, None]
    mm = np.arange(n_cols, dtype=np.uint64)[None, :]
    h = tt * np.uint64(0x9E3779B97F4A7C15) + mm * np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(29)
    return (h % np.uint64(tie_jitter)).astype(np.int32)


@functools.lru_cache(maxsize=8)
def _jitter_device(n_rows: int, n_cols: int, tie_jitter: int) -> jnp.ndarray:
    """Device-resident jitter matrix, cached per padded round shape.

    Depends only on the (bucketed) shape, so across a replay this is one
    host->device upload per bucket, not per round — the per-round traffic
    of the fused pipeline stays O(T + J*M) inputs and O(T) outputs, never
    the (T, M) cost matrix.
    """
    if tie_jitter <= 0:
        return jnp.zeros((n_rows, n_cols), jnp.int32)
    return jnp.asarray(_jitter_matrix_np(n_rows, n_cols, tie_jitter))


def prepare_values_step(
    w_m,  # (Tp, M) i32 machine costs (INF_COST = no arc)
    a,  # (Tp,) i32 unscheduled costs
    jit_m,  # (Tp, M) i32 tie jitter
    active,  # (Tp,) bool
    capacity,  # (M,) i32 free slots
    scale,  # i32 scalar (python int or traced; (T+1) in exact mode, else 1)
    n_slots: int,
):
    """Pure solver-value prep: jitter, value scaling, zero-start prices.

    The scan/vmap-compatible body of `_prepare_device`; ``scale`` may be a
    traced scalar (the window program passes a per-round (T+1) when exact),
    which is bit-identical to the static-int multiply the jitted wrapper
    compiles in. ``n_slots`` shapes the price matrix and stays static.
    """
    finite = w_m < INF_COST
    wj = jnp.where(finite, w_m + jit_m, w_m)  # int32; bound-checked by caller
    vm = jnp.where(
        jnp.logical_and(finite, active[:, None]),
        (-(wj * scale)).astype(jnp.float32),
        NEG_VALUE,
    )
    vu = jnp.where(active, (-(a * scale)).astype(jnp.float32), jnp.float32(0.0))
    slot_iota = jax.lax.broadcasted_iota(
        jnp.int32, (capacity.shape[0], n_slots), 1
    )
    price0 = jnp.where(slot_iota >= capacity[:, None], PRICE_LOCK, 0.0).astype(
        jnp.float32
    )
    return vm, vu, price0, wj


_prepare_device = functools.partial(
    jax.jit, static_argnames=("scale", "n_slots")
)(prepare_values_step)


def assignment_cost_step(wj, a, assigned, active):
    """Per-task chosen arc cost (jittered machine cols / unsched), (Tp,) i32.

    Returned unsummed: the host accumulates in int64 (the device has no
    x64, and an on-device int32 sum could wrap for huge unscheduled costs
    that individually still pass the float32-exactness guard). Pure and
    un-jitted so the window program can inline it per scanned round.
    """
    M = wj.shape[1]
    rows = jnp.arange(wj.shape[0])
    mcost = wj[rows, jnp.clip(assigned, 0, M - 1)]
    per_task = jnp.where(assigned < M, mcost, a)
    return jnp.where(active, per_task, 0)


_assignment_cost = jax.jit(assignment_cost_step)


def solve_transportation_device(
    w_m: jnp.ndarray,  # (Tp, M) i32 device machine costs, rows >= n_tasks junk
    a: jnp.ndarray,  # (Tp,) i32 device unscheduled costs
    n_tasks: int,  # actual task count T <= Tp
    machine_capacity: np.ndarray,  # (M,) host slots per machine
    n_machines: int,
    task_job: np.ndarray,  # (T,) host round-local job index
    *,
    slots_per_machine: int | None = None,
    eps: float = 1.0,
    max_iters_per_phase: int = 500_000,
    tie_jitter: int = 0,
    exact: bool = True,
    cost_bound: int | None = None,
) -> AuctionResult:
    """`solve_transportation` on pre-built device cost arrays.

    The (Tp, M) machine-cost matrix enters and stays on device: jitter,
    value scaling, and slot prices are one jitted prep, then the same
    `_auction_phase` the host path runs. Only O(T) results (assignment,
    iteration count, total cost) come back to host; identical inputs give
    bit-identical assignments to the host path because the phase consumes
    bit-identical float32 values.

    ``cost_bound`` is a host-known upper bound on any finite cost
    (pre-jitter); pass it to keep the float32-exactness check free of a
    device sync. NoMora machine costs are <= 10000 by construction
    (perf is clipped to >= 1e-2), so callers only need to bound the
    unscheduled column.
    """
    T = n_tasks
    M = n_machines
    Tp = int(w_m.shape[0])
    S = int(slots_per_machine or max(1, int(np.max(machine_capacity, initial=1))))
    if T == 0:
        return AuctionResult(
            assigned_col=np.zeros((0,), np.int64),
            total_cost=0,
            iterations=0,
            prices=np.zeros((M, S), np.float32),
        )
    scale = (T + 1) if exact else 1
    if cost_bound is None:
        finite = np.asarray(w_m[:T] < INF_COST)
        cost_bound = int(
            max(
                np.max(np.where(finite, np.asarray(w_m[:T]), 0), initial=1),
                np.max(np.asarray(a[:T])),
            )
        )
    if (cost_bound + max(tie_jitter - 1, 0)) * scale * 4 >= _F32_EXACT:
        raise ValueError(
            f"scaled costs exceed float32-exact range: "
            f"{cost_bound} * {scale} * 4 >= 2^24"
        )

    jobcol_p = np.full((Tp,), M, np.int32)
    jobcol_p[:T] = M + task_job
    active = np.zeros((Tp,), bool)
    active[:T] = True
    active_dev = jnp.asarray(active)

    vm, vu, price0, wj = _prepare_device(
        w_m,
        a,
        _jitter_device(Tp, M, tie_jitter),
        active_dev,
        jnp.asarray(machine_capacity.astype(np.int32)),
        scale,
        S,
    )
    price, _, assigned, iters = _auction_phase(
        price0,
        vm,
        vu,
        jnp.asarray(jobcol_p),
        active_dev,
        jnp.float32(eps),
        max_iters_per_phase,
    )
    total_iters = int(iters)
    if total_iters >= max_iters_per_phase:
        raise RuntimeError(f"auction hit the iteration cap ({max_iters_per_phase})")
    assigned_np = np.asarray(assigned)[:T]
    if (assigned_np < 0).any():
        raise RuntimeError("auction did not converge: unassigned tasks remain")
    total_cost = int(
        np.asarray(_assignment_cost(wj, a, assigned, active_dev))
        .astype(np.int64)
        .sum()
    )
    return AuctionResult(
        assigned_col=assigned_np.astype(np.int64),
        total_cost=total_cost,
        iterations=total_iters,
        prices=price,  # left on device; host pulls via np.asarray on demand
    )
