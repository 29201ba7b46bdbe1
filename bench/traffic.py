"""The benchmark's one job-stream generator.

A traffic mix is a data file (``bench/traffic/<mix>.json``) of parameters;
this module turns a configuration's trace parameters plus a mix's
parameters into the list of jobs a run admits. The arithmetic is a copy of
the scheduler's synthesized Google trace (``repro.core.trace.synth_trace``
and the samplers of ``repro.core.workload``), kept here so that no change
to the program can move the yardstick:

- task counts ``clip(round(exp(N(1.1, 0.9))) + 1, 2, 200)`` (single-task
  jobs dropped, as in the paper);
- durations ``max(exp(N(ln 300, 1.2)), 30)`` seconds (mean about 615 s);
- the paper's application mix, 50% memcached / 25% STRADS / 25% TensorFlow;
- standing services filling ``standing_fraction`` of the slot budget at
  t = 0, lasting the whole trace;
- dynamic arrivals at the rate that fills the rest of the budget to
  ``target_utilisation`` over ``arrival_span`` of the trace.

One departure, for steadiness: the trace draws a Poisson count and fresh
shapes per seed. Here each segment of the run (the fill, then the window)
has a fixed pool of job shapes and inter-arrival gaps, drawn from the
configuration's ``shape_seed``; ``--seed`` only permutes each pool. So
every seed offers the same jobs over the same span, in another order, and
the rate is the trace's own. The latency plane and the scheduler's random
root placement also follow ``--seed``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

# Stream tags, as in repro.core.trace (separate generator streams).
_WINDOW_TAG = 0x5772
_STANDING_TAG = 0x57A2
_PROBE_TAG = 0x5B0B
_SEGMENT_TAG = 0xB3C4

APP_MODELS = ("memcached", "strads", "spark", "tensorflow")  # program order


@dataclasses.dataclass
class JobSpec:
    """One job as the simulator admits it (duck-types ``workload.Job``)."""

    job_id: int
    arrival_s: float
    n_tasks: int
    duration_s: float
    perf_idx: int
    ml_arch: None = None


def sample_n_tasks(rng: np.random.Generator, size: int) -> np.ndarray:
    raw = np.exp(rng.normal(1.1, 0.9, size=size))
    return np.clip(np.round(raw).astype(np.int64) + 1, 2, 200)


def sample_duration(rng: np.random.Generator, size: int) -> np.ndarray:
    return np.clip(np.exp(rng.normal(np.log(300.0), 1.2, size=size)), 30.0, None)


def sample_perf_idx(rng: np.random.Generator, size: int, mix) -> np.ndarray:
    names = [n for n, _ in mix]
    probs = np.asarray([p for _, p in mix], np.float64)
    probs = probs / probs.sum()
    draw = rng.choice(len(names), size=size, p=probs)
    idx = np.asarray([APP_MODELS.index(n) for n in names])
    return idx[draw]


@dataclasses.dataclass(frozen=True)
class TraceParams:
    """A configuration's trace: cluster slots and the Google marginals."""

    slots: int  # machines x slots per machine
    trace_duration_s: int
    target_utilisation: float
    standing_fraction: float
    arrival_span: float
    shape_seed: int
    mix: tuple  # ((application, share), ...)

    @classmethod
    def from_config(cls, cfg: dict) -> "TraceParams":
        topo, tr = cfg["topology"], cfg["trace"]
        return cls(
            slots=int(topo["n_machines"]) * int(topo["slots_per_machine"]),
            trace_duration_s=int(tr["trace_duration_s"]),
            target_utilisation=float(tr["target_utilisation"]),
            standing_fraction=float(tr["standing_fraction"]),
            arrival_span=float(tr["arrival_span"]),
            shape_seed=int(tr["shape_seed"]),
            mix=tuple((str(n), float(p)) for n, p in tr["mix"]),
        )

    @property
    def _budget(self) -> float:
        return self.target_utilisation * self.slots * self.trace_duration_s

    def mean_consumption(self) -> float:
        """Expected slot-seconds per dynamic job (the trace's probe)."""
        rng = np.random.default_rng((self.shape_seed, _PROBE_TAG))
        tasks = sample_n_tasks(rng, 256)
        dur = sample_duration(rng, 256)
        return float(np.mean(tasks * np.minimum(dur, self.trace_duration_s / 2)))

    def rate_jobs_s(self) -> float:
        """Dynamic arrivals per second at the trace's utilisation target."""
        dynamic = self._budget * (1.0 - self.standing_fraction)
        span = max(1.0, self.arrival_span * self.trace_duration_s)
        return dynamic / max(self.mean_consumption(), 1.0) / span

    def standing(self) -> List[JobSpec]:
        """Long-running services at t = 0, lasting the whole trace."""
        rng = np.random.default_rng((self.shape_seed, _STANDING_TAG))
        budget = self._budget * self.standing_fraction
        jobs, used = [], 0.0
        while used < budget:
            n = int(sample_n_tasks(rng, 1)[0])
            perf = int(sample_perf_idx(rng, 1, self.mix)[0])
            jobs.append(JobSpec(-1, 0.0, n, float(self.trace_duration_s), perf))
            used += n * self.trace_duration_s
        return jobs


def segment(
    tp: TraceParams, seed: int, index: int, t_lo: float, span_s: float,
    rate_jobs_s: float,
) -> List[JobSpec]:
    """Dynamic arrivals in ``[t_lo, t_lo + span_s)``: a fixed pool of
    ``round(rate * span)`` job shapes and gaps (from ``shape_seed`` and the
    segment ``index``), each permuted by ``seed``."""
    n = int(round(rate_jobs_s * span_s))
    if n <= 0:
        return []
    pool = np.random.default_rng((tp.shape_seed, _WINDOW_TAG, index))
    gaps = pool.exponential(1.0, size=n)
    n_tasks = sample_n_tasks(pool, n)
    durs = sample_duration(pool, n)
    perf = sample_perf_idx(pool, n, tp.mix)
    # The seed reorders the pool: same jobs, same span, another order.
    perm = np.random.default_rng((int(seed), _SEGMENT_TAG, index))
    gaps = gaps[perm.permutation(n)]
    shapes = perm.permutation(n)
    # Gaps scaled so the n arrivals fill the span exactly (last one inside).
    arrivals = t_lo + np.cumsum(gaps) / gaps.sum() * span_s * (n / (n + 1.0))
    return [
        JobSpec(
            -1,
            float(arrivals[i]),
            int(n_tasks[shapes[i]]),
            float(min(durs[shapes[i]], tp.trace_duration_s - arrivals[i])),
            int(perf[shapes[i]]),
        )
        for i in range(n)
    ]


def build_jobs(tp: TraceParams, seed: int, fill_s: float, window_sim_s: float) -> tuple:
    """``(fill_jobs, window_jobs)`` with dense ids in arrival order: the
    trace's ``[0, fill_s)`` and ``[fill_s, fill_s + window_sim_s)``, both
    at the trace's rate (a serving mix may replay the window's stretch of
    trace faster than the wall clock, see ``harness``)."""
    rate = tp.rate_jobs_s()
    fill = tp.standing() + segment(tp, seed, 0, 0.0, fill_s, rate)
    window = segment(tp, seed, 1, fill_s, window_sim_s, rate)
    for i, job in enumerate(fill + window):
        job.job_id = i
    return fill, window


@dataclasses.dataclass
class JobList:
    """Workload-shaped view the simulator admits from (``topo``,
    ``duration_s``, re-iterable ``jobs`` and table size hints)."""

    topo: object
    duration_s: int
    job_list: Sequence[JobSpec]
    n_jobs_hint: int = 0
    n_tasks_hint: int = 0

    @property
    def jobs(self):
        return iter(self.job_list)
