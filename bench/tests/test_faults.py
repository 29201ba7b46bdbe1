"""A run with the timed path broken underneath must come out not correct.

Each test drives the whole of a run (set-up, window, check) on a tiny cell
on the CPU, past the harness's look for a chip, with one fault planted in
the program's round: an answer altered where it is produced, half of the
round's tasks left out, or a round that leaves the cluster's state
unchanged. The unbroken run is correct.
"""

import numpy as np
import pytest

import run as bench_run

SECONDS = 4.0


class _Broken:
    """The windowed backend with its placement rewritten by ``fault``."""

    def __init__(self, inner, fault):
        self._inner, self._fault = inner, fault

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def place(self, state, ctx):
        p = self._inner.place(state, ctx)
        p.cols = self._fault(np.array(p.cols, np.int64), state)
        return p


def _alter_one(cols, state):
    M = state.n_machines
    on = np.nonzero(cols < M)[0]
    i = on[0] if len(on) else 0
    cols[i] = (cols[i] + 1) % M if cols[i] < M else 0
    return cols


def _drop_half(cols, state):
    half = len(cols) // 2
    cols[half:] = state.n_machines + np.asarray(state.task_job)[half:]
    return cols


def _backend(fault):
    return lambda b: _Broken(b.sim.backend, fault)


def _no_apply(b):
    b.sim._start_batch = lambda *args, **kwargs: None
    return b.sim.backend


def _run(cell, factory=None):
    return bench_run.run_cell(
        cell.name, 20251017, SECONDS, False, require_tpu=False,
        backend_factory=factory, cell=cell,
    )


@pytest.fixture
def serve(tiny_cell):
    return tiny_cell("serve", rate_scale=40.0, check_min_rounds=4)


def test_sound_run_is_correct(serve):
    res = _run(serve)
    assert res["correct"], res["checks"]
    assert res["checks"]["rounds_compared"]["value"] >= 4
    assert set(res["metrics"]) == {"placement_p50_ms", "placement_p95_ms", "setup_s"}


@pytest.mark.parametrize(
    "factory",
    [_backend(_alter_one), _backend(_drop_half), _no_apply],
    ids=["answer_altered", "half_the_batch_left_out", "state_unchanged"],
)
def test_fault_makes_run_incorrect(serve, factory):
    res = _run(serve, factory)
    assert not res["correct"]
    assert res["checks"]["mismatched_tasks"]["value"] > 0
