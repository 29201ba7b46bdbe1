"""Persistent device-resident round program: scan across scheduling rounds.

PR 2 fused a *single* scheduling round into jitted device programs, but the
replay loop still paid per-round dispatch: every round re-entered Python,
re-staged padded inputs, launched several XLA programs, and synced results
back before the next round could start. At Google-trace scale (M=12,500,
one round per simulated second) that fixed per-round overhead — not the
round math — dominates wall clock.

This module keeps the round state *resident on device* and advances it with
`jax.lax.scan` over a **window** of rounds in one dispatch:

- `DeviceRoundState` — the fixed-shape, bucketed carry: free slots,
  last-round slot prices, last-round assignment. Registered as a pytree so
  the jitted window program can **donate** its buffers (the state is
  consumed and rebuilt in place on backends that support donation; CPU
  silently copies).
- `RoundWindow` — one window's exogenous inputs, stacked `(R, ...)` on the
  bucketed shapes `(Tp, Jp)` shared by every round of the window (built by
  `stack_round_states` from per-round `policy.RoundState` records). Host
  latency rows stop at the window's own row bucket `Jr <= Jp`; the program
  pads them to `Jp` on the device after the upload.
- `RoundProgram` — compiles the window program once per bucket shape and
  runs it: each scanned round inlines the *pure* step functions
  (`policy.cost_round_step` → Eq. 7 preemption discount →
  `auction.prepare_values_step` → `auction.auction_phase_step` →
  `auction.assignment_cost_step`), so a window of R rounds is one XLA
  dispatch with no host callbacks. Slot prices start from zero every round
  (complementary slackness for the asymmetric problem — see auction.py;
  the *carry* is cluster state, never warm prices).
- the **what-if axis**: `RoundProgram.what_if` vmaps one round over K
  stacked `PolicyParams` variants (e.g. preemption aggressiveness
  ``beta_scale``, thresholds ``p_m``/``p_r``) and returns each variant's
  placement plus its *true* (undiscounted, unjittered) cost in a single
  dispatch — the primitive the paper's migration controller needs to pick
  "a better placement" (§7). Variants may additionally carry a per-task
  **mover mask** (``active_masks``): rows masked out of a lane are frozen
  in place — they keep their current machine (its slot is re-debited from
  the lane's free slots on device) and contribute their *stay* cost to the
  lane outcome, so "migrate only this subset" hypotheses are comparable
  with full-migration hypotheses on total true cost.

Slot-accounting modes (``chain_slots``):

- ``False`` (exogenous): round ``r`` uses ``window.free_slots[r]`` exactly
  as a sequential caller would pass it — the mode that is bit-identical to
  R independent `AuctionBackend.place` calls.
- ``True`` (chained): the carry's free slots advance on device — round
  ``r`` uses ``carry + window.free_slots[r]`` (the per-round row is an
  exogenous *delta*: admissions/retirements/mover reclaims), and the
  placements of round ``r`` are debited before round ``r+1``. Bit-identical
  to a sequential loop that applies the same slot accounting on host
  between `place` calls (tests/test_policy_device.py).

Bit-parity contract: for identical per-round inputs, every scanned round's
assignment, iteration count, and objective are bit-identical to the
per-round `policy.device_round_costs` + `auction.solve_transportation_device`
path — same int32/float32 ops, same jitter matrix (hash of (row, col),
shape-independent), same zero-start prices. The numpy `dense_costs` host
path remains the parity oracle one level further down.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import auction, perf_model, policy
from .policy import MAX_MACHINE_COST, PolicyParams, RoundState


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["free_slots", "prices", "assigned"],
    meta_fields=[],
)
@dataclasses.dataclass
class DeviceRoundState:
    """Fixed-shape device-resident carry of the window scan.

    ``free_slots`` is the live cluster occupancy (advanced in-scan under
    ``chain_slots=True``); ``prices`` / ``assigned`` are the last scanned
    round's final slot prices and assignment (diagnostics and warm-state
    for consumers that want them — the next round's solve never reads
    them, by the zero-start-price requirement).
    """

    free_slots: jnp.ndarray  # (M,) i32
    prices: jnp.ndarray  # (M, S) f32
    assigned: jnp.ndarray  # (Tp,) i32; -1 = no decision


@dataclasses.dataclass
class RoundWindow:
    """One window's stacked exogenous inputs (host-built, fixed shapes).

    ``free_slots`` rows are absolute per-round slot vectors under
    ``chain_slots=False`` and per-round *deltas* under ``chain_slots=True``.
    ``scale`` is the per-round auction cost scale ((T+1) exact, else 1).
    ``n_tasks`` / ``wait_max`` stay on host for result slicing and the
    float32-exactness guard. Host ``root_latency`` holds ``Jr`` rows, the
    row bucket of the window's largest round (``Jr <= Jp``); device rows
    are already ``(R, Jp, M)``.
    """

    task_job: np.ndarray  # (R, Tp) i32
    perf_idx: np.ndarray  # (R, Tp) i32
    root_latency: np.ndarray  # (R, Jr, M) f32 on host; (R, Jp, M) on device
    wait_s: np.ndarray  # (R, Tp) f32
    run_s: np.ndarray  # (R, Tp) f32
    cur_machine: np.ndarray  # (R, Tp) i32
    active: np.ndarray  # (R, Tp) bool
    free_slots: np.ndarray  # (R, M) i32 (absolute, or deltas when chained)
    scale: np.ndarray  # (R,) i32
    n_tasks: Tuple[int, ...]  # host: real task count per round
    wait_max: Tuple[float, ...]  # host: max wait_s per round (cost bound)

    @property
    def n_rounds(self) -> int:
        return int(self.task_job.shape[0])


@dataclasses.dataclass
class WindowResult:
    """Host view of one `advance` window (padded rows still present)."""

    assigned: np.ndarray  # (R, Tp) i32
    iterations: np.ndarray  # (R,) i32
    per_task_cost: np.ndarray  # (R, Tp) i32 (jittered, discounted)
    per_task_true_cost: np.ndarray  # (R, Tp) i32 (no jitter, no discount)
    n_tasks: Tuple[int, ...]

    def round_cols(self, r: int) -> np.ndarray:
        """Round ``r``'s assignment for its real tasks, (T_r,) int64."""
        return self.assigned[r, : self.n_tasks[r]].astype(np.int64)

    def round_objective(self, r: int) -> int:
        """Round ``r``'s solver objective (jittered units, int64 on host)."""
        return int(self.per_task_cost[r].astype(np.int64).sum())

    def round_true_cost(self, r: int) -> int:
        return int(self.per_task_true_cost[r].astype(np.int64).sum())


@dataclasses.dataclass
class WhatIfResult:
    """K what-if variants of one round, from a single vmapped dispatch."""

    assigned: np.ndarray  # (K, Tp) i32
    iterations: np.ndarray  # (K,) i32
    per_task_cost: np.ndarray  # (K, Tp) i32
    per_task_true_cost: np.ndarray  # (K, Tp) i32
    # Undiscounted cost of every task *staying put* (running tasks on
    # their current machine, pending tasks unscheduled) — the comparison
    # baseline for masked lanes and the controller's improvement ranking.
    per_task_stay_cost: np.ndarray  # (K, Tp) i32
    n_tasks: int
    # The per-lane mover masks the lanes ran under (all-True without
    # explicit masks); frozen rows' `assigned` is meaningless.
    active_masks: Optional[np.ndarray] = None  # (K, Tp) bool

    @property
    def true_costs(self) -> np.ndarray:
        """(K,) total undiscounted cost per variant — the migration
        controller's ranking key ("pick a better placement")."""
        return self.per_task_true_cost.astype(np.int64).sum(axis=1)

    def lane_outcomes(self) -> np.ndarray:
        """(K,) total true cost of each lane's *overall* outcome: solved
        rows contribute their placement's true cost, frozen rows their
        stay cost. Comparable across lanes with different mover masks
        (every lane sums over the same task set)."""
        T = self.n_tasks
        true_c = self.per_task_true_cost[:, :T].astype(np.int64)
        stay_c = self.per_task_stay_cost[:, :T].astype(np.int64)
        if self.active_masks is None:
            return true_c.sum(axis=1)
        masks = self.active_masks[:, :T]
        return np.where(masks, true_c, stay_c).sum(axis=1)

    def best_variant(self) -> int:
        """Lowest true-cost variant (ties -> lowest index, deterministic)."""
        return int(np.argmin(self.true_costs))

    def variant_cols(self, k: int) -> np.ndarray:
        return self.assigned[k, : self.n_tasks].astype(np.int64)


def _pad_params(params_seq: Sequence[PolicyParams]) -> dict:
    """Stack K PolicyParams into (K,) device scalars for the vmap axis."""
    return dict(
        p_m=jnp.asarray([np.int32(p.p_m) for p in params_seq]),
        p_r=jnp.asarray([np.int32(p.p_r) for p in params_seq]),
        omega=jnp.asarray([np.float32(p.omega) for p in params_seq]),
        gamma=jnp.asarray([np.float32(p.gamma) for p in params_seq]),
        preemption=jnp.asarray([bool(p.preemption) for p in params_seq]),
        beta_scale=jnp.asarray([np.float32(p.beta_scale) for p in params_seq]),
    )


def stack_round_states(
    states: Sequence[RoundState],
    *,
    n_pad_tasks: int,
    n_pad_jobs: int,
    exact: bool = False,
) -> RoundWindow:
    """Pad each round to the window's (Tp, Jp) bucket and stack along R.

    Mirrors `policy.device_round_costs`'s padding exactly (task_job/perf
    pads to 0, cur_machine to -1, latency rows to 0) so real rows are
    bit-identical to the per-round path regardless of bucket size.

    Host latency rows are stacked only up to ``Jr``, the row bucket of the
    window's largest round (capped at ``Jp``): `RoundProgram` appends the
    ``Jp - Jr`` zero rows on the device, so a round under a large pinned
    bucket neither zero-fills nor uploads rows it does not have.
    """
    R = len(states)
    if R == 0:
        raise ValueError("empty round window")
    Tp, Jp = n_pad_tasks, n_pad_jobs
    M = states[0].n_machines
    device_latency = isinstance(states[0].root_latency, jax.Array)
    Jr = Jp if device_latency else min(
        auction._bucket(max(s.root_latency.shape[0] for s in states), 8), Jp
    )
    out = RoundWindow(
        task_job=np.zeros((R, Tp), np.int32),
        perf_idx=np.zeros((R, Tp), np.int32),
        root_latency=np.zeros((R, Jr, M), np.float32),
        wait_s=np.zeros((R, Tp), np.float32),
        run_s=np.zeros((R, Tp), np.float32),
        cur_machine=np.full((R, Tp), -1, np.int32),
        active=np.zeros((R, Tp), bool),
        free_slots=np.zeros((R, M), np.int32),
        scale=np.ones((R,), np.int32),
        n_tasks=tuple(s.n_tasks for s in states),
        wait_max=tuple(
            float(s.wait_s.max(initial=0.0)) for s in states
        ),
    )
    # Device-resident latency rows (DeviceLatencyOracle) stay on device:
    # a numpy setitem would silently sync+download them, so scatter into a
    # device buffer instead (after shape validation below) and hand
    # `_window_arrays` the jax array as-is.
    #
    # Latency rows may carry MORE rows than the round has jobs (a pinned
    # oracle pads its output to a fixed job bucket so its device programs
    # compile once — see `latency_device.DeviceLatencyOracle.pin_jobs`);
    # the scatter copies whatever is there, up to the window bucket. Rows
    # past the round's real jobs are never indexed by a real task
    # (task_job < n_jobs), so they are as inert as zero padding.
    for r, s in enumerate(states):
        T, J = s.n_tasks, s.n_jobs
        if T > Tp or J > Jp or s.root_latency.shape[0] > Jp:
            raise ValueError(
                f"round {r} ({T} tasks, {J} jobs, "
                f"{s.root_latency.shape[0]} latency rows) exceeds the "
                f"window bucket ({Tp}, {Jp})"
            )
        if s.n_machines != M:
            raise ValueError("all rounds in a window must share the cluster")
        out.task_job[r, :T] = s.task_job
        out.perf_idx[r, :T] = s.perf_idx
        if not device_latency:
            out.root_latency[r, : s.root_latency.shape[0]] = s.root_latency
        out.wait_s[r, :T] = s.wait_s
        out.run_s[r, :T] = s.run_s
        out.cur_machine[r, :T] = s.cur_machine
        out.active[r, :T] = True
        out.free_slots[r] = s.free_slots.astype(np.int32)
        out.scale[r] = np.int32(T + 1 if exact else 1)
    if device_latency:
        rl = jnp.zeros((R, Jp, M), jnp.float32)
        for r, s in enumerate(states):
            rl = rl.at[r, : s.root_latency.shape[0]].set(s.root_latency)
        out.root_latency = rl
    return out


@functools.partial(jax.jit, static_argnums=1)
def _pad_rows(rows, n_pad_jobs: int):
    """(R, Jr, M) latency rows -> (R, Jp, M), zero rows appended on the
    device: the block the host would otherwise build and ship, bit for
    bit. One small program per (Jr, Jp); the round programs keep their
    (R, Jp, M) input."""
    return jnp.pad(rows, ((0, 0), (0, n_pad_jobs - rows.shape[1]), (0, 0)))


class RoundProgram:
    """Compiled persistent window program for one (Tp, Jp, M) bucket.

    Holds the device-resident round-invariant inputs (perf LUT, tie-jitter
    matrix, policy scalars) and the jitted scan/vmap programs; `advance`
    consumes and returns a `DeviceRoundState` (donated where the backend
    supports it), `what_if` fans one round out over K `PolicyParams`
    variants.
    """

    def __init__(
        self,
        topo,
        params: PolicyParams,
        lut_table: Optional[jnp.ndarray] = None,
        *,
        n_pad_tasks: int,
        n_pad_jobs: int,
        slots_per_machine: Optional[int] = None,
        tie_jitter: int = 9,
        exact: bool = False,
        eps: float = 1.0,
        max_iters: int = 500_000,
        chain_slots: bool = False,
        use_pallas: Optional[bool] = None,
        interpret: bool = False,
    ):
        self.topo = topo
        self.params = params
        self.n_pad_tasks = int(n_pad_tasks)
        self.n_pad_jobs = int(n_pad_jobs)
        self.n_machines = int(topo.n_machines)
        self.n_slots = int(slots_per_machine or topo.slots_per_machine)
        self.tie_jitter = int(tie_jitter)
        self.exact = bool(exact)
        self.eps = float(eps)
        self.max_iters = int(max_iters)
        self.chain_slots = bool(chain_slots)
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.lut = perf_model.perf_lut_table() if lut_table is None else lut_table
        # Device-resident, shape-keyed: one upload per program, not per round.
        self.jitter = auction._jitter_device(
            self.n_pad_tasks, self.n_machines, self.tie_jitter
        )
        # The policy scalars are fixed for the program, so they too go up
        # once: six scalar transfers a round took about 2.6 ms on a TPU
        # v5e, more than the whole window's arrays.
        self.scalars = jax.device_put(dict(
            p_m=np.int32(params.p_m),
            p_r=np.int32(params.p_r),
            omega=np.float32(params.omega),
            gamma=np.float32(params.gamma),
            preemption=np.bool_(params.preemption),
            beta_scale=np.float32(params.beta_scale),
        ))
        # Buffer donation keeps the carry in place across windows; CPU has
        # no donation support, so skip it there to avoid per-call warnings.
        donate = (0,) if jax.default_backend() != "cpu" else ()
        self._advance_jit = jax.jit(
            self._advance_impl, donate_argnums=donate
        )
        self._whatif_jit = jax.jit(self._whatif_impl)

    # ------------------------------------------------------------------ #

    def init_state(self, free_slots: np.ndarray) -> DeviceRoundState:
        """Fresh device state from the host's slot-occupancy view."""
        return DeviceRoundState(
            free_slots=jnp.asarray(free_slots.astype(np.int32)),
            prices=jnp.zeros((self.n_machines, self.n_slots), jnp.float32),
            assigned=jnp.full((self.n_pad_tasks,), -1, jnp.int32),
        )

    def warmup(self, free_slots: np.ndarray, root_latency=None) -> None:
        """Compile + execute the R=1 advance path on a synthetic round.

        A serving loop wants its *first real decision* to be a warm
        dispatch, so this runs one throwaway window — a single task of job
        0 rooted on machine 0 with zero latency everywhere — through the
        full program: every jitted piece (the scan body, the window-array
        uploads, `init_state`'s buffer builds) compiles here, at the
        bucket shapes all later rounds share. The warmup carry is
        discarded; under exogenous slot accounting (``chain_slots=False``,
        the serving mode) a round's ``free_slots`` comes from its window
        row, so nothing the warmup computed can leak into real results.
        Works against a full cluster too: an unplaceable task lands on its
        unscheduled aggregator column, which still counts as assigned.

        ``root_latency`` optionally substitutes the latency rows — pass a
        device array (e.g. a pinned `DeviceLatencyOracle.root_rows`
        output) to also compile `stack_round_states`'s device-scatter
        branch at the exact row shape real rounds will carry, or a host
        block of fewer than ``Jp`` rows to also compile the device-side
        row pad for its row bucket; otherwise a host (Jp, M) zero block
        is uploaded whole, as a round with ``Jp`` jobs is.
        """
        window = self._synthetic_window(free_slots, root_latency)
        with obs.span("round_program.warmup", bucket_tasks=self.n_pad_tasks):
            self.advance(self.init_state(np.asarray(free_slots)), window)

    def _synthetic_window(self, free_slots, root_latency=None) -> RoundWindow:
        """One throwaway round at this bucket: a single task of job 0
        rooted on machine 0, zero latency unless ``root_latency`` is
        given."""
        M = self.n_machines
        state = RoundState(
            task_job=np.zeros(1, np.int64),
            perf_idx=np.zeros(1, np.int64),
            root_machine=np.zeros(1, np.int64),
            root_latency=(
                np.zeros((self.n_pad_jobs, M), np.float32)
                if root_latency is None
                else root_latency
            ),
            wait_s=np.zeros(1, np.float32),
            run_s=np.zeros(1, np.float32),
            cur_machine=np.full(1, -1, np.int64),
            free_slots=np.asarray(free_slots, np.int32),
        )
        return stack_round_states(
            [state],
            n_pad_tasks=self.n_pad_tasks,
            n_pad_jobs=self.n_pad_jobs,
            exact=self.exact,
        )

    def _round_body(
        self, free_slots, inputs, *, p_m, p_r, omega, gamma, preemption,
        beta_scale, scale, stay_active=None,
    ):
        """One scheduling round on device: pure, scan/vmap-compatible.

        Returns ``(price, assigned, iters, per_task_cost, per_task_true,
        per_task_stay)``. The Eq. 7 preemption discount is applied *here*,
        on top of the undiscounted `policy.cost_round_step` output, so the
        true (performance-only) cost of every placement is available to the
        what-if axis without a second cost build — through the same
        `policy.apply_preemption_discount` the per-round path inlines.
        ``per_task_stay`` is the undiscounted cost of every task staying
        put (running tasks on their current machine, pending tasks
        unscheduled), evaluated over ``stay_active`` rows (defaults to the
        round's active rows) — what-if lanes pass the *unmasked* active set
        so frozen movers still report a stay cost.
        """
        (task_job, perf_idx, root_lat, wait_s, run_s, cur_machine, active) = inputs
        M = self.n_machines
        w_base, a, _d, _c_rack, _b = policy.cost_round_step(
            self.lut,
            task_job,
            perf_idx,
            root_lat,
            wait_s,
            run_s,
            cur_machine,
            p_m,
            p_r,
            omega,
            gamma,
            jnp.bool_(False),  # discount applied below, on w_base
            beta_scale,
            per_rack=self.topo.machines_per_rack,
            use_pallas=self.use_pallas,
            interpret=self.interpret,
        )
        w_m = policy.apply_preemption_discount(
            w_base, cur_machine, run_s, preemption, beta_scale
        )

        job_col = jnp.where(active, M + task_job, M).astype(jnp.int32)
        vm, vu, price0, wj = auction.prepare_values_step(
            w_m, a, self.jitter, active, free_slots, scale, self.n_slots
        )
        price, _owner, assigned, iters = auction.auction_phase_step(
            price0,
            vm,
            vu,
            job_col,
            active,
            jnp.float32(self.eps),
            self.max_iters,
            use_pallas=self.use_pallas,
            interpret=self.interpret,
        )
        per_task_cost = auction.assignment_cost_step(wj, a, assigned, active)
        per_task_true = auction.assignment_cost_step(w_base, a, assigned, active)
        stay_cols = jnp.where(cur_machine >= 0, cur_machine, M + task_job).astype(
            jnp.int32
        )
        per_task_stay = auction.assignment_cost_step(
            w_base, a, stay_cols, active if stay_active is None else stay_active
        )
        return price, assigned, iters, per_task_cost, per_task_true, per_task_stay

    def _consumed(self, assigned, active):
        """(M,) slots debited by one round's placements (duplicate-safe)."""
        placed = jnp.logical_and(
            active, jnp.logical_and(assigned >= 0, assigned < self.n_machines)
        )
        return (
            jnp.zeros((self.n_machines,), jnp.int32)
            .at[jnp.clip(assigned, 0, self.n_machines - 1)]
            .add(placed.astype(jnp.int32))
        )

    def _advance_impl(self, state, window_arrays, params_scalars):
        def body(carry, per_round):
            (task_job, perf_idx, root_lat, wait_s, run_s, cur_machine,
             active, slots_in, scale) = per_round
            # Exogenous mode: each round's slots come from its window row,
            # as a sequential caller would pass them. Chained mode: the
            # row is a delta on the device-carried occupancy.
            free_slots = (
                carry.free_slots + slots_in if self.chain_slots else slots_in
            )
            price, assigned, iters, cost, true_cost, _stay = self._round_body(
                free_slots,
                (task_job, perf_idx, root_lat, wait_s, run_s, cur_machine,
                 active),
                scale=scale,
                **params_scalars,
            )
            new_carry = DeviceRoundState(
                free_slots=free_slots - self._consumed(assigned, active),
                prices=price,
                assigned=assigned,
            )
            return new_carry, (assigned, iters, cost, true_cost)

        return jax.lax.scan(body, state, window_arrays)

    def _whatif_impl(
        self, free_slots, round_arrays, variant_params, variant_active, scale
    ):
        (task_job, perf_idx, root_lat, wait_s, run_s, cur_machine, active) = (
            round_arrays
        )
        M = self.n_machines

        def one(vp, mask):
            # Frozen movers (active rows masked out of this lane) keep
            # running where they are: re-debit their current machine's
            # slot (the host reclaimed it when nominating them as movers)
            # and solve the round for the remaining rows only.
            lane_active = jnp.logical_and(active, mask)
            frozen = jnp.logical_and(active, jnp.logical_not(mask))
            keeps = jnp.logical_and(
                frozen, jnp.logical_and(cur_machine >= 0, cur_machine < M)
            )
            free_lane = free_slots - (
                jnp.zeros((M,), jnp.int32)
                .at[jnp.clip(cur_machine, 0, M - 1)]
                .add(keeps.astype(jnp.int32))
            )
            _price, assigned, iters, cost, true_cost, stay = self._round_body(
                free_lane,
                (task_job, perf_idx, root_lat, wait_s, run_s, cur_machine,
                 lane_active),
                scale=scale,
                stay_active=active,
                **vp,
            )
            return assigned, iters, cost, true_cost, stay

        return jax.vmap(one)(variant_params, variant_active)

    # ------------------------------------------------------------------ #

    def _arg_shapes(self, sharding=None):
        """Shape-only stand-ins for `advance`'s arguments on one R=1
        round at this bucket: the carry, the window arrays and the policy
        scalars, placed by ``sharding``."""
        window = self._synthetic_window(np.zeros(self.n_machines, np.int32))
        args = (
            self.init_state(window.free_slots[0]),
            self._window_arrays(window),
            self.scalars,
        )
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            args,
        )

    def lower_window(self, sharding=None):
        """Lower the R=1 window program (what `advance` runs for one
        round) from shapes alone. ``sharding`` places it on a device that
        need not be attached — e.g. one chip of a described TPU topology —
        so ``.compile().as_text()`` shows which kernels it really calls."""
        return self._advance_jit.lower(*self._arg_shapes(sharding))

    def lower_whatif(self, n_lanes: int, sharding=None):
        """Lower the ``n_lanes``-wide what-if program from shapes alone
        (see `lower_window`)."""

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        _state, window, scalars = self._arg_shapes(sharding)
        return self._whatif_jit.lower(
            sds(window[7].shape[1:], window[7].dtype),
            tuple(sds(a.shape[1:], a.dtype) for a in window[:7]),
            {k: sds((n_lanes,), v.dtype) for k, v in scalars.items()},
            sds((n_lanes, self.n_pad_tasks), jnp.bool_),
            sds((), jnp.int32),
        )

    def _check_cost_bound(
        self, window: RoundWindow, variants: Optional[Sequence[PolicyParams]] = None
    ) -> None:
        """Host-side float32-exactness guard (no device sync), mirroring
        `auction.solve_transportation_device`'s check — per round, and per
        what-if variant when ``variants`` is given."""
        for params in variants if variants is not None else (self.params,):
            for r in range(window.n_rounds):
                a_max = int(params.omega * window.wait_max[r] + params.gamma) + 1
                bound = max(MAX_MACHINE_COST, a_max)
                scale = int(window.scale[r])
                if (
                    (bound + max(self.tie_jitter - 1, 0)) * scale * 4
                    >= auction._F32_EXACT
                ):
                    raise ValueError(
                        f"scaled costs exceed float32-exact range in round {r}: "
                        f"{bound} * {scale} * 4 >= 2^24"
                    )

    def _count_upload(self, window: RoundWindow) -> None:
        """Telemetry of one window's upload: ``h2d.upload_bytes`` and
        ``h2d.latency_rows_skipped``, the zero latency rows the device
        pads on instead of the host shipping them (R x (Jp - Jr))."""
        obs.add("h2d.upload_bytes", self._window_upload_bytes(window))
        obs.add(
            "h2d.latency_rows_skipped",
            window.n_rounds * (self.n_pad_jobs - window.root_latency.shape[1]),
        )

    def _window_upload_bytes(self, window: RoundWindow) -> int:
        """Host bytes `_window_arrays` ships to device for this window
        (host latency rows at their row bucket ``Jr``, not ``Jp``).

        Device-resident latency rows (`DeviceLatencyOracle` path) are
        already on device — `stack_round_states` scatters them with a
        device-side ``.at[].set`` — so only numpy-held fields count."""
        total = 0
        for field in (
            window.task_job, window.perf_idx, window.root_latency,
            window.wait_s, window.run_s, window.cur_machine,
            window.active, window.free_slots, window.scale,
        ):
            if isinstance(field, np.ndarray):
                total += field.nbytes
        return total

    def _window_arrays(self, window: RoundWindow):
        """The window's arrays on the device, in one batched transfer
        (about 0.5 ms a round less than one transfer per array on a TPU
        v5e), latency rows padded there to ``Jp``."""
        arrs = list(jax.device_put((
            window.task_job, window.perf_idx, window.root_latency,
            window.wait_s, window.run_s, window.cur_machine, window.active,
            window.free_slots, window.scale,
        )))
        if arrs[2].shape[1] < self.n_pad_jobs:
            arrs[2] = _pad_rows(arrs[2], self.n_pad_jobs)
        return tuple(arrs)

    def _record_window_spans(
        self,
        t0_ns: int,
        sync: Tuple[int, int],
        window: RoundWindow,
        iters_np: np.ndarray,
    ) -> None:
        """Record the ``round_program.advance`` span and its synthetic
        per-round sub-slices, after the fact.

        ``advance`` runs from ``t0_ns`` (before the upload) to now (after
        the sync) and carries the bucket shape and each round's auction
        iterations in its args. It is recorded before its sub-slices, so a
        reader walking the span list meets the bucket first.

        The ``round_program.round`` sub-slices are synthetic: the scanned
        window is one XLA program, so no host code runs between rounds.
        They split the ``sync`` interval (device run plus wait) by each
        round's iteration count and nest inside the live
        ``round_program.sync`` span. Their times measure nothing; they are
        kept only for the ``iterations`` arg the kernel roofline readers
        take.
        """
        t1_ns = time.perf_counter_ns()
        R = window.n_rounds
        iters = iters_np.astype(np.int64).reshape(-1)[:R]
        obs.record_span(
            "round_program.advance",
            t0_ns,
            t1_ns - t0_ns,
            {"rounds": R, "bucket_tasks": self.n_pad_tasks,
             "bucket_jobs": self.n_pad_jobs,
             "iterations": [int(i) for i in iters]},
        )
        obs.add("window.rounds", R)
        obs.add("auction.iterations", int(iters.sum()))
        obs.add(
            "auction.pad_waste_tasks",
            sum(self.n_pad_tasks - T for T in window.n_tasks),
        )
        s0, s1 = sync
        weights = np.maximum(iters.astype(np.float64), 1.0)
        edges = s0 + np.round(
            np.cumsum(np.concatenate([[0.0], weights])) / weights.sum() * (s1 - s0)
        ).astype(np.int64)
        for r in range(R):
            obs.record_span(
                "round_program.round",
                int(edges[r]),
                int(edges[r + 1] - edges[r]),
                {"round": r, "iterations": int(iters[r]),
                 "n_tasks": window.n_tasks[r]},
                depth=1,
            )

    def advance(
        self, state: DeviceRoundState, window: RoundWindow
    ) -> Tuple[DeviceRoundState, WindowResult]:
        """Scan the window's rounds through the device-resident state.

        One dispatch for all R rounds; the input ``state`` is consumed
        (donated on supporting backends) and the advanced state returned.
        Host-side validation (convergence, iteration caps, float32 cost
        bounds) happens around the dispatch, never inside it.

        Spans: ``round_program.upload`` (the window's transfer and the
        device-side latency-row pad), ``.dispatch`` (the
        jitted call returning), ``.sync`` (the iteration counts coming
        back: device run plus wait) and ``.fetch`` (the other results and
        the checks).
        """
        self._check_cost_bound(window)
        telemetry = obs.enabled()
        if telemetry:
            self._count_upload(window)
            t0_ns = time.perf_counter_ns()
        with obs.span("round_program.upload"):
            arrs = self._window_arrays(window)
            if telemetry:  # the span then measures the transfers
                jax.block_until_ready(arrs)
        with obs.span("round_program.dispatch"):
            new_state, (assigned, iters, cost, true_cost) = self._advance_jit(
                state, arrs, self.scalars
            )
        with obs.span("round_program.sync"):
            if telemetry:
                s0_ns = time.perf_counter_ns()
            iters_np = np.asarray(iters)
            if telemetry:
                s1_ns = time.perf_counter_ns()
        if telemetry:
            self._record_window_spans(t0_ns, (s0_ns, s1_ns), window, iters_np)
        with obs.span("round_program.fetch"):
            if int(iters_np.max(initial=0)) >= self.max_iters:
                raise RuntimeError(
                    f"auction hit the iteration cap ({self.max_iters}) inside the window"
                )
            assigned_np = np.asarray(assigned)
            for r, T in enumerate(window.n_tasks):
                if (assigned_np[r, :T] < 0).any():
                    raise RuntimeError(
                        f"auction did not converge in round {r}: unassigned tasks remain"
                    )
            result = WindowResult(
                assigned=assigned_np,
                iterations=iters_np,
                per_task_cost=np.asarray(cost),
                per_task_true_cost=np.asarray(true_cost),
                n_tasks=window.n_tasks,
            )
        return new_state, result

    def what_if(
        self,
        state: RoundState,
        variants: Sequence[PolicyParams],
        active_masks: Optional[np.ndarray] = None,
    ) -> WhatIfResult:
        """Evaluate K candidate parameterisations of one round in ONE
        dispatch (vmapped what-if axis).

        Each variant's placement is bit-identical to running that round
        through the per-round pipeline with the variant's `PolicyParams`
        (vmap of the auction while_loop freezes converged lanes, so lanes
        are independent). Rank variants with `WhatIfResult.true_costs` —
        total cost with no preemption discount and no tie jitter, i.e. pure
        expected application performance of the resulting placement.

        ``active_masks`` (K, T) bool — optional per-lane mover masks: rows
        masked False are frozen on their current machine for that lane
        (slot re-debited on device, stay cost reported). An all-True lane
        is bit-identical to the unmasked path. Rank masked lanes with
        `WhatIfResult.lane_outcomes`, which charges frozen rows their stay
        cost so totals are comparable across different masks.
        """
        if not variants:
            raise ValueError("what_if needs at least one PolicyParams variant")
        with obs.span("round_program.stack"):
            window = stack_round_states(
                [state],
                n_pad_tasks=self.n_pad_tasks,
                n_pad_jobs=self.n_pad_jobs,
                exact=self.exact,
            )
        self._check_cost_bound(window, variants)
        K = len(variants)
        T = window.n_tasks[0]
        masks = np.ones((K, self.n_pad_tasks), bool)
        if active_masks is not None:
            active_masks = np.asarray(active_masks, bool)
            if active_masks.shape[0] != K or active_masks.shape[1] > self.n_pad_tasks:
                raise ValueError(
                    f"active_masks shape {active_masks.shape} does not match "
                    f"{K} variants / bucket {self.n_pad_tasks}"
                )
            masks[:, : active_masks.shape[1]] = active_masks
        scale = int(window.scale[0])
        telemetry = obs.enabled()
        if telemetry:
            self._count_upload(window)
            obs.add("whatif.lanes", K)
        with obs.span("round_program.upload"):
            arrs = self._window_arrays(window)
            lane_args = (
                _pad_params(variants), jnp.asarray(masks), jnp.int32(scale)
            )
            if telemetry:  # the span then measures the transfers
                jax.block_until_ready((arrs, lane_args))
        with obs.span("round_program.dispatch"):
            round_arrays = tuple(a[0] for a in arrs[:7])
            free_slots = arrs[7][0]
            assigned, iters, cost, true_cost, stay_cost = self._whatif_jit(
                free_slots, round_arrays, *lane_args
            )
        with obs.span("round_program.sync"):
            iters_np = np.asarray(iters)
        if telemetry:
            obs.add("auction.iterations", int(iters_np.astype(np.int64).sum()))
        with obs.span("round_program.fetch"):
            if int(iters_np.max(initial=0)) >= self.max_iters:
                raise RuntimeError(
                    f"auction hit the iteration cap ({self.max_iters}) in a what-if lane"
                )
            assigned_np = np.asarray(assigned)
            if ((assigned_np[:, :T] < 0) & masks[:, :T]).any():
                raise RuntimeError(
                    "auction did not converge in a what-if lane: unassigned tasks remain"
                )
            return WhatIfResult(
                assigned=assigned_np,
                iterations=iters_np,
                per_task_cost=np.asarray(cost),
                per_task_true_cost=np.asarray(true_cost),
                per_task_stay_cost=np.asarray(stay_cost),
                n_tasks=T,
                active_masks=masks if active_masks is not None else None,
            )
