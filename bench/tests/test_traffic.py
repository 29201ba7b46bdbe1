"""The job-stream generator: the trace's own rate, seeds that reorder one
fixed pool of jobs, and the same jobs for the same seed."""

import json
import os

import numpy as np

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _google():
    with open(os.path.join(BENCH, "configs", "google-12500.json")) as f:
        return traffic.TraceParams.from_config(json.load(f))


def test_rate_is_the_synthesized_trace_rate():
    from repro.core.topology import google_topology
    from repro.core.trace import synth_trace

    tp = _google()
    cur = synth_trace(google_topology(), 86_400, seed=tp.shape_seed)
    assert np.isclose(tp.rate_jobs_s(), cur._rate)
    assert 12.0 < tp.rate_jobs_s() < 13.5
    standing = sum(j.n_tasks for j in tp.standing())
    assert standing == sum(j.n_tasks for j in cur._standing_jobs())


def test_same_seed_same_jobs_other_seed_same_pool():
    tp = _google()
    a = traffic.build_jobs(tp, 123, 1200.0, 30.0)
    b = traffic.build_jobs(tp, 123, 1200.0, 30.0)
    c = traffic.build_jobs(tp, 2**33 + 5, 1200.0, 30.0)
    key = lambda jobs: [(j.arrival_s, j.n_tasks, j.duration_s, j.perf_idx) for j in jobs]
    assert key(a[0]) == key(b[0]) and key(a[1]) == key(b[1])
    assert key(a[1]) != key(c[1])
    shapes = lambda jobs: sorted((j.n_tasks, j.duration_s, j.perf_idx) for j in jobs)
    assert shapes(a[1]) == shapes(c[1])
    win = a[1]
    assert all(1200.0 <= j.arrival_s < 1230.0 for j in win)
    assert len(win) == round(tp.rate_jobs_s() * 30.0)
    ids = [j.job_id for j in a[0] + a[1]]
    assert ids == list(range(len(ids)))
