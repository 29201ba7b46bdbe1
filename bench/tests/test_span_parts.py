"""The readers that split the solver span and the host round into parts,
on a hand-built span list and on a traced tiny run on the CPU."""

import pytest

import run as bench_run
from repro.obs import SpanRecord

SOLVER_PARTS = {
    "solve_upload_ms_per_round": "round_program.upload",
    "solve_dispatch_ms_per_round": "round_program.dispatch",
    "solve_sync_ms_per_round": "round_program.sync",
    "solve_fetch_ms_per_round": "round_program.fetch",
}
HOST_PARTS = {
    "select_ms_per_round": "sim.select",
    "build_state_ms_per_round": "sim.build_state",
    "stack_ms_per_round": "round_program.stack",
    "apply_ms_per_round": "sim.apply",
}
MS = 1_000_000


def _span(name, t0_ms, dur_ms, depth):
    return SpanRecord(name, t0_ms * MS, dur_ms * MS, depth, 1, None)


def _round(t0, roots, select, build, stack, solver_parts, apply):
    """One round's spans in the registry's order (children exit first):
    ``roots`` ms of unread host time, then the named parts back to back."""
    out, t = [], t0 + roots
    for name, d in (("sim.select", select), ("sim.build_state", build),
                    ("round_program.stack", stack)):
        out.append(_span(name, t, d, 1))
        t += d
    s0 = t
    for name, d in zip(SOLVER_PARTS.values(), solver_parts):
        out.append(_span(name, t, d, 2))
        t += d
    out.append(_span("solver.auction_windowed", s0, t - s0, 1))
    out.append(_span("sim.apply", t, apply, 1))
    t += apply
    out.append(_span("sim.round", t0, t - t0, 0))
    return out


def _obs(spans):
    return bench_run.Observation(run=None, setup_s=0.0, n_machines=64, spans=spans)


@pytest.fixture
def hand():
    spans = _round(0, 0.5, 0.1, 20.0, 0.3, (6.0, 0.6, 1.0, 2.5), 0.4)
    spans += _round(40, 1.5, 0.2, 3.0, 0.1, (5.0, 0.5, 0.8, 2.0), 0.2)
    return _obs(spans)


def _read(name, o):
    return bench_run.reader(name + ".replay")(o)


def test_solver_parts_sum_to_the_solver_span(hand):
    whole = _read("solve_ms_per_round", hand)
    parts = {m: _read(m, hand) for m in SOLVER_PARTS}
    assert parts["solve_upload_ms_per_round"] == pytest.approx((6.0 + 5.0) / 2)
    assert sum(parts.values()) == pytest.approx(whole)


def test_host_parts_stay_within_the_host_round(hand):
    whole = _read("host_ms_per_round", hand)
    parts = {m: _read(m, hand) for m in HOST_PARTS}
    assert parts["build_state_ms_per_round"] == pytest.approx((20.0 + 3.0) / 2)
    # What no part reads: the roots (0.5 and 1.5 ms).
    assert sum(parts.values()) <= whole
    assert whole - sum(parts.values()) == pytest.approx((0.5 + 1.5) / 2)


@pytest.mark.parametrize("metric", sorted({**SOLVER_PARTS, **HOST_PARTS}))
def test_reader_is_silent_without_its_span(hand, metric):
    span = {**SOLVER_PARTS, **HOST_PARTS}[metric]
    assert _read(metric, hand) is not None
    assert _read(metric, _obs([s for s in hand.spans if s.name != span])) is None
    assert _read(metric, _obs(None)) is None


@pytest.mark.parametrize("mix", ["serve", "replay"])
def test_traced_run_reads_every_part(tiny_cell, mix):
    extra = dict(rate_scale=40.0, check_min_rounds=4) if mix == "serve" else {}
    cell = tiny_cell(mix, **extra)
    res = bench_run.run_cell(cell.name, 20251018, 3.0, True, require_tpu=False, cell=cell)
    m = {k.split(".")[0]: v["value"] for k, v in res["metrics"].items()}
    assert set(SOLVER_PARTS) | set(HOST_PARTS) <= set(m)
    assert sum(m[k] for k in SOLVER_PARTS) <= m["solve_ms_per_round"]
    assert sum(m[k] for k in SOLVER_PARTS) >= 0.8 * m["solve_ms_per_round"]
    assert sum(m[k] for k in HOST_PARTS) <= m["host_ms_per_round"]
