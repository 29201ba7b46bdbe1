"""Pluggable per-round placement engines behind one `SchedulerBackend` API.

The simulator's round used to branch on (policy string x solver string)
across three code paths; every strategy is now a backend with one
*required* entry point:

    backend.place(state: RoundState, ctx: RoundContext) -> Placement

plus three *optional* axes, declared by capability flags instead of
``hasattr`` probing (the flags are the documented protocol; `simulator.py`
and `core.serving.ScheduleService` branch on them exclusively):

- ``supports_window``  -> `place_window(states, ctx, chain=...)` — R staged
  rounds in one fused dispatch;
- ``supports_whatif``  -> `place_whatif(...)` / `whatif_result(...)` — K
  parameter/mover-mask variants of one round, vmapped;
- ``supports_serving`` -> `pin_serving(...)` / `warm_serving(...)` — the
  backend can run a long-lived serving loop with ZERO post-warmup jit
  recompiles: either it compiles nothing (host paths), or its compiled
  shapes can be pinned up front to a fixed bucket that every subsequent
  round fits inside.

Calling an optional entry point on a backend whose flag is False raises
`BackendCapabilityError` (a `NotImplementedError`) — loudly, instead of an
``AttributeError`` from a missing duck-typed method.

`Placement.cols` assigns every round task a column — a machine id in
[0, M), >= M for "stay unscheduled", or -1 for "no decision" — and
`Placement.algo_s` is the backend-measured solver wall time, excluding
cost-model construction on every backend (the fused ``auction`` backend
syncs its device cost arrays before starting the clock), matching the
paper's Fig. 6 "algorithm runtime" and the pre-refactor measurement
points.

``algo_s`` semantics (unified via `solver_clock`): every backend times
exactly its solver region through the one `solver_clock` helper, which
doubles as the ``solver.<backend>`` telemetry span (`repro.obs`). The
reported number is always **per scheduling round**:

- single-round entry points (`place`, `place_whatif`, `whatif_result`)
  report the raw wall time of their one solve/dispatch;
- `WindowedAuctionBackend.place_window` runs R rounds in ONE fused
  dispatch and reports ``elapsed / R`` on every returned `Placement`
  (`solver_clock`'s ``per_round``) — the amortised per-round cost,
  comparable with R sequential `place` calls, *not* the whole window's
  wall time repeated R times.

Backends:

- `AuctionBackend` (name ``auction``) — the production path: fused
  on-device cost build (`policy.device_round_costs`, task/job dims padded
  to power-of-two buckets so the pipeline compiles once per bucket) into
  `auction.solve_transportation_device`; the (T, M) cost matrix never
  crosses the host↔device boundary. ``auction_host`` is the same solver
  through the numpy `dense_costs` reference — kept as the parity oracle,
  bit-identical placements (tests/test_policy_device.py).
- `WindowedAuctionBackend` (``auction_windowed``) — the same round math
  through the persistent device-resident `core.round_program.RoundProgram`:
  `place` is an R=1 window (bit-identical to ``auction``), `place_window`
  scans R staged rounds in one dispatch, `place_whatif` vmaps K parameter
  variants of one round (the migration controller's what-if axis).
- `MCMFBackend` (``mcmf``) — the paper-faithful Quincy graph through the
  SSP min-cost-max-flow reference solver.
- `RandomBackend` / `LoadSpreadingBackend` (``random``/``load_spreading``)
  — the paper §6.1 heuristics; no cost model, no latency plane reads.
- `RandomSolverBackend` / `SpreadSolverBackend` — Firmament-style
  baselines: fixed/load-derived costs through the auction engine.

`make_backend` maps a `SimConfig` (or an explicit ``cfg.backend`` name) to
an instance; `core/sweep.py` exposes the same names per grid cell via the
``policy:backend`` cell syntax.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro import obs

from . import auction, flow_network, mcmf, perf_model
from .policy import (
    INF_COST,
    MAX_MACHINE_COST,
    PolicyParams,
    RoundState,
    dense_costs,
    device_round_costs,
    load_spreading_placement,
    random_placement,
)
from .topology import Topology


class _SolverClock:
    """Elapsed-time handle yielded by `solver_clock`."""

    __slots__ = ("elapsed",)

    def __init__(self) -> None:
        self.elapsed = 0.0

    def per_round(self, n_rounds: int) -> float:
        """Amortised per-round time for fused multi-round dispatches."""
        return self.elapsed / max(int(n_rounds), 1)


@contextlib.contextmanager
def solver_clock(name: str, **span_args):
    """The one ``algo_s`` measurement point shared by every backend.

    Wraps the timed region in an ``obs.span`` (zero-cost when telemetry
    is disabled) and exposes the measured wall time as ``clk.elapsed``
    after the block exits. Callers must perform any device sync *before*
    entering (e.g. ``jax.block_until_ready`` on cost arrays) so the clock
    covers solver work only — the span inherits exactly the legacy
    `time.perf_counter()` window of each backend.
    """
    clk = _SolverClock()
    with obs.span(name, **span_args):
        t0 = time.perf_counter()
        try:
            yield clk
        finally:
            clk.elapsed = time.perf_counter() - t0


@dataclasses.dataclass
class RoundContext:
    """Simulator-side inputs a backend may need beyond the RoundState."""

    rng: np.random.Generator  # shared simulator stream (random baselines)
    task_counts: np.ndarray  # (M,) running tasks per machine (spreading)
    n_ready: int  # state's first n_ready tasks are pending; the rest migrate


@dataclasses.dataclass
class Placement:
    """One round's decision: column per task + the measured solver time."""

    cols: np.ndarray  # (T,) machine id, >= M unscheduled, -1 no decision
    algo_s: float
    objective: Optional[int] = None  # solver objective (cost-model backends)


class BackendCapabilityError(NotImplementedError):
    """An optional `SchedulerBackend` entry point was invoked on a backend
    whose capability flag (``supports_window`` / ``supports_whatif`` /
    ``supports_serving``) is False."""


class SchedulerBackend:
    """Strategy interface for one scheduling round.

    Required: `place`. Optional axes are declared by the ``supports_*``
    capability flags below and default to raising `BackendCapabilityError`
    — callers branch on the flags, never on ``hasattr``.
    """

    name: str = "abstract"
    #: Whether RoundState.root_latency must be populated (cost-model paths).
    needs_latency: bool = True
    #: Whether round admission is capped at free slots + slack (solver
    #: paths; a big backlog against a full cluster degenerates the auction
    #: into unscheduled-price wars).
    caps_admission: bool = True
    #: Whether the backend can re-place running tasks (preemption arcs):
    #: gates periodic migration rounds and the application of mover columns.
    supports_migration: bool = False
    #: Whether straggler/migration rounds feed movers into this backend's
    #: RoundState at all. Solver baselines select movers (their presence
    #: changes the solve and, for random costs, the rng stream — seed
    #: semantics) even though their mover columns are never applied.
    selects_movers: bool = False
    #: Whether `place_window` exists: R staged rounds in one fused dispatch.
    supports_window: bool = False
    #: Whether `place_whatif` / `whatif_result` exist: K parameter (and
    #: mover-mask) variants of one round in one vmapped dispatch.
    supports_whatif: bool = False
    #: Whether the backend can run a long-lived serving loop with zero
    #: post-warmup jit recompiles (`pin_serving` / `warm_serving`). True
    #: for pure-host backends (nothing compiles) and for device backends
    #: whose compiled shapes can be pinned to a fixed bucket; False for
    #: the per-round ``auction`` device path, whose bucket tracks the live
    #: task count and therefore recompiles as the arrival batch varies.
    supports_serving: bool = False

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        raise NotImplementedError

    # ------------------------- optional axes ------------------------- #

    def place_window(
        self, states, ctx: Optional[RoundContext] = None, *, chain: bool = False
    ):
        raise BackendCapabilityError(
            f"backend {self.name!r} has no window axis (supports_window=False)"
        )

    def place_whatif(
        self, state: RoundState, ctx: RoundContext, variants
    ) -> Placement:
        raise BackendCapabilityError(
            f"backend {self.name!r} has no what-if axis (supports_whatif=False)"
        )

    def whatif_result(
        self, state: RoundState, ctx: RoundContext, variants, active_masks=None
    ):
        raise BackendCapabilityError(
            f"backend {self.name!r} has no what-if axis (supports_whatif=False)"
        )

    def pin_serving(self, n_tasks: int, n_jobs: int) -> None:
        """Fix the compiled shapes a serving loop will run under.

        After pinning, every round whose (task, job) counts fit inside the
        pinned power-of-two buckets reuses the same compiled programs —
        the zero-post-warmup-recompile contract `core.serving` measures
        with the ``jit.backend_compiles`` counter. Host backends compile
        nothing; their pin is a no-op.
        """
        if not self.supports_serving:
            raise BackendCapabilityError(
                f"backend {self.name!r} cannot serve (supports_serving=False)"
            )

    def warm_serving(self, free_slots: np.ndarray, root_latency=None) -> None:
        """Compile + execute the pinned serving path once, ahead of the
        loop (results-harmless). ``root_latency`` optionally carries a
        device latency-row block so the device stacking path warms too.
        No-op on host backends."""
        if not self.supports_serving:
            raise BackendCapabilityError(
                f"backend {self.name!r} cannot serve (supports_serving=False)"
            )


class RandomBackend(SchedulerBackend):
    name = "random"
    needs_latency = False
    caps_admission = False
    supports_serving = True  # pure host: nothing compiles

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        with solver_clock("solver.random") as clk:
            cols = random_placement(ctx.rng, state.n_tasks, state.free_slots)
        return Placement(cols=cols, algo_s=clk.elapsed)


class LoadSpreadingBackend(SchedulerBackend):
    name = "load_spreading"
    needs_latency = False
    caps_admission = False
    supports_serving = True  # pure host: nothing compiles

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        with solver_clock("solver.load_spreading") as clk:
            cols = load_spreading_placement(
                ctx.task_counts, state.free_slots, state.n_tasks
            )
        return Placement(cols=cols, algo_s=clk.elapsed)


class _SolverBaselineBackend(SchedulerBackend):
    """Fixed-cost (random) / task-count (load-spreading) matrices run
    through the same auction engine, mirroring Firmament baseline policies
    (the paper's Fig. 6 compares *solver* runtimes across policies)."""

    needs_latency = False
    selects_movers = True  # movers enter the solve; columns never applied
    supports_serving = True  # host auction reference: nothing compiles

    def __init__(self, params: PolicyParams, topo: Topology):
        self.params = params
        self.topo = topo

    def _machine_costs(self, state: RoundState, ctx: RoundContext) -> np.ndarray:
        raise NotImplementedError

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        T, J, M = state.n_tasks, state.n_jobs, state.n_machines
        w = np.full((T, M + J), int(INF_COST), np.int64)
        w[:, :M] = self._machine_costs(state, ctx)
        a = (self.params.omega * state.wait_s + self.params.gamma).astype(
            np.int64
        )
        w[np.arange(T), M + state.task_job] = a
        with solver_clock(f"solver.{self.name}") as clk:
            res = auction.solve_transportation(
                w,
                state.free_slots.astype(np.int64),
                M,
                M + state.task_job.astype(np.int64),
                slots_per_machine=self.topo.slots_per_machine,
                exact=False,
            )
        obs.add("auction.iterations", res.iterations)
        return Placement(
            cols=np.asarray(res.assigned_col, np.int64),
            algo_s=clk.elapsed,
            objective=res.total_cost,
        )


class RandomSolverBackend(_SolverBaselineBackend):
    name = "random_solver"

    def _machine_costs(self, state: RoundState, ctx: RoundContext) -> np.ndarray:
        # Fixed cost + random tie-break jitter (a flat matrix makes any
        # assignment optimal; jitter picks one uniformly and keeps the
        # auction free of degenerate price wars).
        return 100 + ctx.rng.integers(
            0, 10, size=(state.n_tasks, state.n_machines)
        ).astype(np.int64)


class SpreadSolverBackend(_SolverBaselineBackend):
    name = "spread_solver"

    def _machine_costs(self, state: RoundState, ctx: RoundContext) -> np.ndarray:
        return 100 + np.broadcast_to(
            ctx.task_counts[None, :], (state.n_tasks, state.n_machines)
        ).astype(np.int64)


class AuctionBackend(SchedulerBackend):
    """NoMora cost model + auction solver (device-fused or host-reference).

    ``device=True`` (the default, name ``auction``) runs the entire round —
    costmap, rack reduce, thresholds, preemption discount, value scaling,
    auction — as jitted device programs; padding both varying dims to
    power-of-two buckets bounds recompilation across rounds. ``device=False``
    (name ``auction_host``) is the pre-refactor numpy `dense_costs` +
    `solve_transportation` path; both produce bit-identical placements, so
    either satisfies the engine-parity suite.
    """

    supports_migration = True
    selects_movers = True

    def __init__(
        self,
        params: PolicyParams,
        topo: Topology,
        lut_table=None,
        *,
        device: bool = True,
        tie_jitter: int = 9,
        exact: bool = False,
        use_pallas: Optional[bool] = None,
        interpret: bool = False,
    ):
        self.params = params
        self.topo = topo
        self.lut = perf_model.perf_lut_table() if lut_table is None else lut_table
        self.device = device
        self.tie_jitter = tie_jitter
        self.exact = exact
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.name = "auction" if device else "auction_host"
        # The host path compiles nothing; the fused device path compiles
        # one pipeline per (task, job) bucket and cannot pin the bucket —
        # the windowed subclass is the device serving path.
        self.supports_serving = not device

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        if not self.device:
            costs = dense_costs(state, self.topo, self.params, self.lut)
            M = state.n_machines
            with solver_clock("solver.auction_host") as clk:
                res = auction.solve_transportation(
                    costs.w,
                    costs.col_capacity[:M],
                    M,
                    M + state.task_job.astype(np.int64),
                    slots_per_machine=self.topo.slots_per_machine,
                    tie_jitter=self.tie_jitter,
                    exact=self.exact,
                )
            obs.add("auction.iterations", res.iterations)
            return Placement(
                cols=np.asarray(res.assigned_col, np.int64),
                algo_s=clk.elapsed,
                objective=res.total_cost,
            )

        # Fused device round. Syncing the cost arrays before starting the
        # solver clock keeps algo_s solve-only — comparable with every
        # host-side backend and the paper's Fig. 6 measurement points; the
        # arrays stay device-resident (block_until_ready transfers nothing).
        w_m, a, _, _, _ = device_round_costs(
            state,
            self.topo,
            self.params,
            self.lut,
            n_pad_tasks=auction._bucket(state.n_tasks),
            n_pad_jobs=auction._bucket(state.n_jobs, 8),
            use_pallas=self.use_pallas,
            interpret=self.interpret,
        )
        jax.block_until_ready((w_m, a))
        if obs.enabled():
            # Bucket pad waste: padded rows solved beyond the real tasks.
            obs.add(
                "auction.pad_waste_tasks",
                auction._bucket(state.n_tasks) - state.n_tasks,
            )
        with solver_clock("solver.auction") as clk:
            # Host-side cost bound: machine arcs are <= 10000 by
            # construction, the unscheduled column is known from the
            # (host) wait times.
            a_max = int(self.params.omega * float(state.wait_s.max(initial=0.0))
                        + self.params.gamma) + 1
            res = auction.solve_transportation_device(
                w_m,
                a,
                state.n_tasks,
                state.free_slots,
                state.n_machines,
                state.task_job,
                slots_per_machine=self.topo.slots_per_machine,
                tie_jitter=self.tie_jitter,
                exact=self.exact,
                cost_bound=max(MAX_MACHINE_COST, a_max),
            )
        obs.add("auction.iterations", res.iterations)
        return Placement(
            cols=np.asarray(res.assigned_col, np.int64),
            algo_s=clk.elapsed,
            objective=res.total_cost,
        )


class WindowedAuctionBackend(AuctionBackend):
    """NoMora round through the persistent device-resident `RoundProgram`.

    The same cost model and auction solver as ``auction``, but the whole
    round — cost build, value prep, solve, objective — is one compiled
    window program whose round-invariant inputs (perf LUT, tie-jitter
    matrix) and state buffers stay resident on device across calls
    (donated where the backend supports donation). Three entry points:

    - `place` — `SchedulerBackend` contract, one round per call (an R=1
      window through the same scanned program): bit-identical placements
      to ``auction``, so the simulator's admission/migration/straggler
      cadence is untouched. ``algo_s`` covers the fused dispatch (cost +
      solve are one program and cannot be clocked separately — slightly
      *over*-counts solver time relative to the ``auction`` backend's
      solve-only clock).
    - `place_window` — R rounds in ONE dispatch (`jax.lax.scan`), for
      callers that can stage a window of round inputs up front (replay
      drivers, benchmarks); per-round results are bit-identical to R
      sequential `place` calls. ``chain`` threads slot consumption
      through the window on device (round r+1 sees round r's placements).
    - `place_whatif` — the vmapped what-if axis: K `PolicyParams`
      variants of one round in one dispatch, returning the placement of
      the variant with the lowest *true* (undiscounted) cost — the
      migration controller's "pick a better placement" primitive (§7).

    Serving (``supports_serving``): `pin_serving` fixes a bucket floor so
    every round of a long-lived loop re-enters one compiled program and
    its donated device carry regardless of the live-task count, and
    `warm_serving` pre-compiles it — together the zero-post-warmup-
    recompile contract behind `core.serving.ScheduleService`.
    """

    supports_window = True
    supports_whatif = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        if not self.device:
            raise ValueError("WindowedAuctionBackend is device-only")
        self.name = "auction_windowed"
        self.supports_serving = True  # buckets pin via pin_serving
        self._programs: dict = {}  # (Tp, Jp, chain) -> RoundProgram
        self._states: dict = {}  # (Tp, Jp, chain) -> DeviceRoundState
        self._pin = (0, 0)  # serving bucket floor (Tp, Jp); (0, 0) = unpinned

    @property
    def programs(self) -> dict:
        """The round programs built so far, keyed (task bucket, job
        bucket, chained)."""
        return self._programs

    def pin_serving(self, n_tasks: int, n_jobs: int) -> None:
        """Pin the (task, job) bucket floor for long-lived serving.

        Every subsequent `_program` lookup rounds up to at least this
        bucket, so rounds with any live-task count <= the pin re-enter the
        SAME compiled program and donated carry (warm re-entry). Rounds
        that exceed the pin still work — they fall onto a larger bucket,
        at the cost of one compile (which the serving loop's jit-counter
        pin would then surface).
        """
        self._pin = (
            auction._bucket(max(int(n_tasks), 1)),
            auction._bucket(max(int(n_jobs), 1), 8),
        )

    def warm_serving(self, free_slots: np.ndarray, root_latency=None) -> None:
        """Compile + run the pinned R=1 window program on a synthetic
        round (see `RoundProgram.warmup`) so the serving loop's first real
        decision is a warm dispatch. Results-harmless: the warmup carry is
        discarded, and exogenous windows never read carried occupancy.

        Host latency rows reach the program at their own row bucket and
        are padded to the pinned job bucket on the device, so without
        device rows the round also runs once per row bucket 8, 16, ...
        below the pin: each compiles its small pad program here, not in
        the loop."""
        _key, prog = self._program(max(self._pin[0], 1), max(self._pin[1], 1))
        free_slots = np.asarray(free_slots)
        prog.warmup(free_slots, root_latency=root_latency)
        if root_latency is not None:
            return
        rows = 8
        while rows < prog.n_pad_jobs:
            prog.warmup(
                free_slots,
                root_latency=np.zeros((rows, prog.n_machines), np.float32),
            )
            rows *= 2

    def _program(self, n_tasks: int, n_jobs: int, *, chain: bool = False):
        from .round_program import RoundProgram

        key = (
            max(auction._bucket(n_tasks), self._pin[0]),
            max(auction._bucket(n_jobs, 8), self._pin[1]),
            chain,
        )
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = RoundProgram(
                self.topo,
                self.params,
                self.lut,
                n_pad_tasks=key[0],
                n_pad_jobs=key[1],
                slots_per_machine=self.topo.slots_per_machine,
                tie_jitter=self.tie_jitter,
                exact=self.exact,
                chain_slots=chain,
                use_pallas=self.use_pallas,
                interpret=self.interpret,
            )
        return key, prog

    def _state_for(self, key, prog, free_slots):
        """Per-bucket persistent carry; rebuilt only on first use (its
        buffers are donated back by every `advance`). The entry is
        *popped*: `advance` donates the carry's buffers into the dispatch,
        so if it raises (iteration cap, convergence) a cached reference
        would hand deleted arrays to the next call on this bucket — the
        caller re-caches the advanced state on success instead."""
        st = self._states.pop(key, None)
        if st is None:
            st = prog.init_state(free_slots)
        return st

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        from .round_program import stack_round_states

        key, prog = self._program(state.n_tasks, state.n_jobs)
        with obs.span("round_program.stack"):
            window = stack_round_states(
                [state],
                n_pad_tasks=prog.n_pad_tasks,
                n_pad_jobs=prog.n_pad_jobs,
                exact=self.exact,
            )
        dstate = self._state_for(key, prog, state.free_slots)
        with solver_clock("solver.auction_windowed") as clk:
            dstate, res = prog.advance(dstate, window)
        self._states[key] = dstate
        return Placement(
            cols=res.round_cols(0),
            algo_s=clk.elapsed,
            objective=res.round_objective(0),
        )

    def place_window(
        self, states, ctx: Optional[RoundContext] = None, *, chain: bool = False
    ):
        """Solve R staged rounds in one scanned dispatch.

        ``chain=False``: every round uses its own ``free_slots`` exactly as
        R sequential `place` calls would (bit-identical). ``chain=True``:
        round 0 starts from ``states[0].free_slots`` and later rounds'
        ``free_slots`` fields are treated as per-round *deltas* on the
        device-carried occupancy (see `round_program.RoundProgram`).
        Returns a list of `Placement`.
        """
        from .round_program import stack_round_states

        if not states:
            return []
        key, prog = self._program(
            max(s.n_tasks for s in states),
            max(s.n_jobs for s in states),
            chain=chain,
        )
        with obs.span("round_program.stack", rounds=len(states)):
            window = stack_round_states(
                states,
                n_pad_tasks=prog.n_pad_tasks,
                n_pad_jobs=prog.n_pad_jobs,
                exact=self.exact,
            )
        if chain:
            # Round 0's row becomes the delta on the freshly-seeded carry.
            dstate = prog.init_state(states[0].free_slots)
            window.free_slots[0] = 0
        else:
            dstate = self._state_for(key, prog, states[0].free_slots)
        with solver_clock(
            "solver.auction_windowed.window", rounds=len(states), chain=chain
        ) as clk:
            dstate, res = prog.advance(dstate, window)
        # Per-round attribution: one fused dispatch amortised over the
        # window (see the module docstring's algo_s contract).
        algo_s = clk.per_round(len(states))
        if not chain:
            # Chained windows seed a fresh carry per call; caching theirs
            # would just pin device buffers nothing ever reads again.
            self._states[key] = dstate
        return [
            Placement(
                cols=res.round_cols(r),
                algo_s=algo_s,
                objective=res.round_objective(r),
            )
            for r in range(len(states))
        ]

    def place_whatif(
        self, state: RoundState, ctx: RoundContext, variants
    ) -> Placement:
        """One round under K `PolicyParams` variants, one dispatch; returns
        the placement of the variant with the lowest true (undiscounted)
        cost. With a single variant this is `place` under that variant's
        params, bit for bit."""
        _key, prog = self._program(state.n_tasks, state.n_jobs)
        variants = list(variants)
        with solver_clock(
            "solver.auction_windowed.whatif", lanes=len(variants)
        ) as clk:
            res = prog.what_if(state, variants)
        best = res.best_variant()
        return Placement(
            cols=res.variant_cols(best),
            algo_s=clk.elapsed,
            objective=int(
                res.per_task_cost[best].astype(np.int64).sum()
            ),
        )

    def whatif_result(
        self, state: RoundState, ctx: RoundContext, variants, active_masks=None
    ):
        """Raw what-if axis for the migration controller: one dispatch over
        K (PolicyParams, mover-mask) lanes, returning the full
        `WhatIfResult` (placements, true costs, stay costs) plus the
        dispatch time — the controller ranks lanes and applies budgets on
        host, which `place_whatif`'s argmin-and-return hides."""
        _key, prog = self._program(state.n_tasks, state.n_jobs)
        variants = list(variants)
        with solver_clock(
            "solver.auction_windowed.whatif", lanes=len(variants)
        ) as clk:
            res = prog.what_if(state, variants, active_masks=active_masks)
        return res, clk.elapsed


class MCMFBackend(SchedulerBackend):
    """Paper-faithful Quincy flow network + SSP MCMF (the oracle solver)."""

    name = "mcmf"
    supports_migration = True
    selects_movers = True
    supports_serving = True  # pure host: nothing compiles

    def __init__(self, params: PolicyParams, topo: Topology, lut_table=None):
        self.params = params
        self.topo = topo
        self.lut = perf_model.perf_lut_table() if lut_table is None else lut_table

    def place(self, state: RoundState, ctx: RoundContext) -> Placement:
        costs = dense_costs(state, self.topo, self.params, self.lut)
        with solver_clock("solver.mcmf") as clk:
            g = flow_network.build_flow_graph(state, self.topo, self.params, costs)
            fr = mcmf.min_cost_max_flow(
                g.src, g.dst, g.cap, g.cost, g.source, g.sink, g.n_nodes
            )
            cols = flow_network.extract_assignment(g, fr.flow, state)
        return Placement(
            cols=np.asarray(cols, np.int64),
            algo_s=clk.elapsed,
            objective=int(fr.total_cost),
        )


BACKEND_NAMES = (
    "auction",
    "auction_windowed",
    "auction_host",
    "mcmf",
    "random",
    "load_spreading",
    "random_solver",
    "spread_solver",
)


def make_backend(
    name: str,
    params: PolicyParams,
    topo: Topology,
    lut_table=None,
) -> SchedulerBackend:
    """Instantiate a backend by name (see BACKEND_NAMES)."""
    if name == "random":
        return RandomBackend()
    if name == "load_spreading":
        return LoadSpreadingBackend()
    if name == "random_solver":
        return RandomSolverBackend(params, topo)
    if name == "spread_solver":
        return SpreadSolverBackend(params, topo)
    if name == "auction":
        return AuctionBackend(params, topo, lut_table, device=True)
    if name == "auction_windowed":
        return WindowedAuctionBackend(params, topo, lut_table, device=True)
    if name == "auction_host":
        return AuctionBackend(params, topo, lut_table, device=False)
    if name == "mcmf":
        return MCMFBackend(params, topo, lut_table)
    raise KeyError(f"unknown scheduler backend {name!r}; one of {BACKEND_NAMES}")


def backend_for_config(cfg, topo: Topology, lut_table=None) -> SchedulerBackend:
    """Resolve a SimConfig to a backend: explicit ``cfg.backend`` wins,
    otherwise the legacy (policy, solver) pair maps onto a name."""
    if getattr(cfg, "backend", None):
        name = cfg.backend
    else:
        name = {
            "random": "random",
            "load_spreading": "load_spreading",
            "random_solver": "random_solver",
            "spread_solver": "spread_solver",
            "nomora": "auction" if cfg.solver == "auction" else "mcmf",
        }[cfg.policy]
    return make_backend(name, cfg.params, topo, lut_table)
