"""Set-up, measured window and round log of one benchmark run.

Everything here is general: a cell is a configuration file
(``configs/<config>.json``) under a traffic file (``traffic/<mix>.json``);
the traffic file's ``mode`` picks one of the two loops below.

- ``serve``: an open loop on the wall clock. Jobs are due at wall times
  (the trace's arrivals, scaled by ``rate_scale``), the simulated clock
  follows the wall clock one to one, rounds run back to back while work
  is pending, and each task's latency runs from its job's due time to the
  moment its placement is in the simulator's tables.
- ``replay``: a fixed stretch of the trace replayed as fast as it goes,
  one round per simulated second, every ``migration_interval_s``-th round
  a migration round; the loop body is ``Simulator.run``'s.

Both start from a cluster filled to its steady occupancy: the trace's
first ``fill_s`` seconds replayed through the host ``random`` backend, as
the real trace also starts with a loaded cluster.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import List, Optional

import numpy as np

import traffic as traffic_mod

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict  # the whole BENCHMARK.json

    @classmethod
    def load(cls, root: str, name: str) -> "Cell":
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in spec["configs"]}
        cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
        tr = load_json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
        return cls(name, w["config"], w["traffic"], int(w["chips"]), cfg, tr, spec)

    def metrics(self, key: str) -> List[dict]:
        """The spec's metrics of ``key`` ("end_to_end" / "per_layer") that
        this cell reports."""
        e2e = {m["name"] for m in self.spec["end_to_end"]
               if self.name in m.get("workloads", [self.name])}
        out = []
        for m in self.spec[key]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif key == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out


class CompileCounter:
    """Counts the programs XLA builds (``total``: each backend compile, a
    persistent-cache load included) and the persistent cache's hits and
    misses, from ``jax.monitoring`` listeners."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        from jax import monitoring

        self.total = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == self.BUILD:
            self.total += 1

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


class RoundLog:
    """Records, for every solver round of the window, its inputs (the
    round's small arrays, not its latency rows), the columns the backend
    returned and each task's machine in the simulator's tables right after
    the round. Wraps ``Simulator._build_round_state`` and the backend's
    ``place``; placements are untouched."""

    def __init__(self, sim):
        self.sim = sim
        self.on = False
        self.rounds: List[dict] = []
        self._cur: Optional[dict] = None
        build = sim._build_round_state

        def build_and_log(ready_ids, mover_ids, t, with_latency=True):
            state = build(ready_ids, mover_ids, t, with_latency)
            if self.on:
                self._cur = dict(
                    t=float(t),
                    ready_ids=np.array(ready_ids, np.int64),
                    mover_ids=np.array(mover_ids, np.int64),
                    task_job=np.array(state.task_job, np.int64),
                    perf_idx=np.array(state.perf_idx, np.int64),
                    root_machine=np.array(state.root_machine, np.int64),
                    wait_s=np.array(state.wait_s, np.float32),
                    run_s=np.array(state.run_s, np.float32),
                    cur_machine=np.array(state.cur_machine, np.int64),
                    free_slots=np.array(state.free_slots, np.int64),
                )
            return state

        sim._build_round_state = build_and_log

    def wrap(self, backend):
        log = self

        class Logged:
            def __getattr__(self, name):
                return getattr(backend, name)

            def place(self, state, ctx):
                p = backend.place(state, ctx)
                if log._cur is not None:
                    log._cur["cols"] = np.array(p.cols, np.int64)
                return p

        return Logged()

    def after_round(self, migration: bool) -> None:
        cur, self._cur = self._cur, None
        if cur is None or "cols" not in cur:
            return
        ids = np.concatenate([cur["ready_ids"], cur["mover_ids"]])
        cur["after"] = self.sim.tt.machine[ids].copy()
        cur["migration"] = bool(migration)
        self.rounds.append(cur)


def annotate(on: bool):
    """``jax.profiler.TraceAnnotation`` factory in traced runs, else a
    no-op, so the end-to-end run carries no tracing cost."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Run:
    """What a measured window produced (host-clock readings and logs)."""

    mode: str
    window_s: float = 0.0
    rounds: int = 0  # solver rounds in the window
    sim_s: float = 0.0  # simulated seconds covered
    latencies_ms: Optional[np.ndarray] = None
    attempted: int = 0
    failed: int = 0
    generator_lag_ms: Optional[np.ndarray] = None
    compiles_in_window: int = 0


class Bench:
    """One cell, built from the seed: cluster, plane, jobs, simulator."""

    def __init__(self, cell: Cell, seed: int, seconds: float, *,
                 trace: bool = False, backend_factory=None):
        from repro.core.latency import LatencyPlane
        from repro.core.policy import PolicyParams
        from repro.core.scheduler_backend import make_backend
        from repro.core.simulator import SimConfig, Simulator
        from repro.core.topology import Topology

        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        cfg, tr = cell.config, cell.traffic
        self.mode = tr["mode"]
        self.topo = Topology(**{k: int(v) for k, v in cfg["topology"].items()})
        self.fill_s = int(cfg["fill_s"])
        self.policy = dict(tr["policy"])
        self.params = PolicyParams(**self.policy)
        tp = traffic_mod.TraceParams.from_config(cfg)
        if self.mode == "serve":
            self.window_sim_s = self.seconds * float(tr["rate_scale"])
            horizon = self.fill_s + self.seconds + float(tr["drain_s"]) + 2
        elif self.mode == "replay":
            self.window_sim_s = float(int(round(self.seconds * float(tr["nominal_sim_s_per_s"]))))
            horizon = self.fill_s + self.window_sim_s + 2
        else:
            raise ValueError(f"unknown traffic mode {self.mode!r}")
        self.fill_jobs, self.window_jobs = traffic_mod.build_jobs(
            tp, self.seed, self.fill_s, self.window_sim_s
        )
        # The plane covers what the run reads: the fill, the window and
        # its drain (seeded by the run's seed; 32 bits, as numpy takes it).
        self.plane_seed = self.seed % (2**32)
        self.plane = LatencyPlane.synthesize(
            self.topo, int(np.ceil(horizon)), seed=self.plane_seed
        )
        n_jobs = len(self.fill_jobs) + len(self.window_jobs)
        workload = traffic_mod.JobList(
            self.topo, self.fill_s, self.fill_jobs,
            n_jobs_hint=n_jobs, n_tasks_hint=6 * n_jobs,
        )
        self.sim = Simulator(
            workload,
            self.plane,
            SimConfig(
                backend="auction_windowed",
                params=self.params,
                seed=self.plane_seed,
                max_round_tasks=int(tr["max_round_tasks"]),
                migration_interval_s=int(tr.get("migration_interval_s", 10)),
                fixed_algo_s=0.0,
                streaming_metrics=True,
            ),
        )
        self.backend = (
            backend_factory(self) if backend_factory is not None else self.sim.backend
        )
        self.make_backend = make_backend
        self.log = RoundLog(self.sim)
        self.ann = annotate(trace)

    # -------------------------------------------------------------- #
    # Set-up

    def fill(self) -> dict:
        """Replay the fill stretch through the host random backend."""
        sim = self.sim
        sim.backend = self.make_backend("random", self.params, self.topo)
        t0 = time.perf_counter()
        sim.run()
        sim.backend = self.log.wrap(self.backend)
        return {
            "fill_wall_s": time.perf_counter() - t0,
            "running_tasks": int(len(sim.running)),
            "pending_tasks": int(len(sim.pending) + len(sim.pending_roots)),
        }

    def warm(self) -> int:
        """Build and run the round program of every (task bucket, job
        bucket) pair in the mix's ``warm_buckets``, the pairs its windows
        reach; returns how many. A serving mix pins its one bucket first."""
        be, free = self.backend, self.sim.free_slots
        pairs = [tuple(int(x) for x in p) for p in self.cell.traffic["warm_buckets"]]
        if self.mode == "serve":
            (tb, jb), = pairs
            be.pin_serving(tb, jb)
            be.warm_serving(free)
            return 1
        for tb, jb in pairs:
            _key, prog = be._program(tb, jb)
            prog.warmup(free)
        return len(pairs)

    def buckets_used(self) -> dict:
        """``{(task bucket, job bucket): rounds}`` over the logged rounds."""
        from repro.core.auction import _bucket

        out: dict = {}
        for r in self.log.rounds:
            key = (_bucket(len(r["task_job"])), _bucket(len(r["root_machine"]), 8))
            out[key] = out.get(key, 0) + 1
        return out

    # -------------------------------------------------------------- #
    # Measured window

    def _accrue_wait(self, t_prev: float, t_now: float) -> None:
        """Wait time of every pending task, up to ``t_now``."""
        sim = self.sim
        if len(sim.pending) and t_now > t_prev:
            p = sim.pending
            since = np.maximum(sim.tt.submit_s[p], t_prev)
            sim.tt.wait_s[p] += np.maximum(t_now - since, 0.0)

    def run_serve(self, compiles: CompileCounter) -> Run:
        sim, ann, tr = self.sim, self.ann, self.cell.traffic
        W = self.seconds
        jobs = self.window_jobs
        scale = float(tr["rate_scale"])
        due = np.asarray([(j.arrival_s - self.fill_s) / scale for j in jobs])
        n_jobs = len(jobs)
        drain_s = float(tr["drain_s"])
        out_ids = np.empty(0, np.int64)
        out_due = np.empty(0, np.float64)
        lat: List[np.ndarray] = []
        lag: List[float] = []
        attempted = 0
        rounds = 0
        i = 0
        t_prev = float(self.fill_s)
        self.log.on = True
        c0 = compiles.total
        t0 = time.perf_counter()
        with ann("bench.window"):
            while True:
                el = time.perf_counter() - t0
                if el > W + drain_s:
                    break
                t_sim = self.fill_s + el
                if i < n_jobs and due[i] <= el:
                    j = int(np.searchsorted(due, el, side="right"))
                    with ann("bench.admit"):
                        n0 = sim.tt.n
                        sim._admit(jobs[i:j], t_sim)
                        ids = np.arange(n0, sim.tt.n, dtype=np.int64)
                        per_job = np.asarray([jobs[k].n_tasks for k in range(i, j)])
                        out_ids = np.concatenate([out_ids, ids])
                        out_due = np.concatenate([out_due, np.repeat(due[i:j], per_job)])
                        attempted += len(ids)
                    i = j
                with ann("bench.retire"):
                    sim._retire(t_sim)
                if len(sim.pending) or len(sim.pending_roots):
                    with ann("bench.round"):
                        self._accrue_wait(t_prev, t_sim)
                        t_prev = t_sim
                        sim._round(t_sim, False)
                    now = time.perf_counter() - t0
                    self.log.after_round(False)
                    rounds += 1
                    placed = sim.tt.machine[out_ids] >= 0
                    if placed.any():
                        lat.append((now - out_due[placed]) * 1e3)
                        out_ids, out_due = out_ids[~placed], out_due[~placed]
                    continue
                if i >= n_jobs and not len(out_ids) and el >= W:
                    break
                with ann("bench.idle"):
                    if i < n_jobs:
                        # Sleep until the next job is due.
                        wait = due[i] - (time.perf_counter() - t0)
                        if wait > 0:
                            time.sleep(wait)
                        lag.append((time.perf_counter() - t0 - due[i]) * 1e3)
                    else:
                        # Nothing due any more: to the window's end, or a
                        # moment for tasks no round can take yet.
                        time.sleep(min(max(W - el, 0.0), 0.001) if len(out_ids) else max(W - el, 0.0))
            window_s = time.perf_counter() - t0
        self.log.on = False
        return Run(
            mode="serve",
            window_s=window_s,
            rounds=rounds,
            sim_s=window_s,
            latencies_ms=np.concatenate(lat) if lat else np.empty(0),
            attempted=attempted,
            failed=int(len(out_ids)),
            generator_lag_ms=np.asarray(lag),
            compiles_in_window=compiles.total - c0,
        )

    def run_replay(self, compiles: CompileCounter) -> Run:
        sim, ann, tr = self.sim, self.ann, self.cell.traffic
        jobs = self.window_jobs
        interval = int(tr.get("migration_interval_s", 10))
        sample_every = int(tr.get("perf_sample_interval_s", 15))
        t_first = self.fill_s
        t_end = self.fill_s + int(self.window_sim_s)
        i, n_jobs, rounds = 0, len(jobs), 0
        self.log.on = True
        c0 = compiles.total
        t0 = time.perf_counter()
        with ann("bench.window"):
            for t in range(t_first, t_end):
                j = i
                while j < n_jobs and jobs[j].arrival_s <= t:
                    j += 1
                if j > i:
                    with ann("bench.admit"):
                        sim._admit(jobs[i:j], t)
                    i = j
                with ann("bench.retire"):
                    sim._retire(t)
                migration = self.params.preemption and t % interval == 0
                if len(sim.pending_roots) or len(sim.pending) or migration:
                    with ann("bench.round"):
                        sim._round(t, migration)
                    self.log.after_round(migration)
                    rounds += 1
                if t % sample_every == 0:
                    with ann("bench.perf_sample"):
                        sim._sample_perf(t)
                if len(sim.pending):
                    sim.tt.wait_s[sim.pending] += 1
            window_s = time.perf_counter() - t0
        self.log.on = False
        return Run(
            mode="replay",
            window_s=window_s,
            rounds=rounds,
            sim_s=float(t_end - t_first),
            attempted=len(self.log.rounds),
            compiles_in_window=compiles.total - c0,
        )

    def run_window(self, compiles: CompileCounter) -> Run:
        if self.mode == "serve":
            return self.run_serve(compiles)
        return self.run_replay(compiles)
