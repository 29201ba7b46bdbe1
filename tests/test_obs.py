"""Telemetry plane tests (ISSUE 8): span nesting, zero-cost-when-disabled
identity, Chrome trace export schema, summary-schema stability, per-cell
sweep telemetry shard-merge, compare.py gating, and the end-to-end
instrumented controller replay acceptance."""

import json

import numpy as np
import pytest

from repro import obs
from repro.core import latency, simulator, topology, workload
from repro.core.metrics import SUMMARY_SCALARS, SUMMARY_SERIES, SimMetrics
from repro.core.metrics_stream import StreamingSimMetrics
from repro.core.policy import PolicyParams


@pytest.fixture(autouse=True)
def _obs_sandbox():
    """Every test starts disabled with an empty registry and leaves no
    state behind (the module flag is process-global)."""
    was = obs.enabled()
    obs.set_enabled(False)
    obs.reset()
    yield
    obs.set_enabled(was)
    obs.reset()


# --------------------------------------------------------------------- #
# zero-cost-when-disabled contract


def test_disabled_noop_identity():
    assert not obs.enabled()
    # One shared null span: no allocation per call while disabled.
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2
    with s1:
        pass
    obs.add("c.count", 5)
    obs.gauge("c.track", 1.0)
    obs.audit_event("c.audit", x=1)
    obs.record_span("c.span", 0, 10)
    tel = obs.get()
    assert tel.spans == []
    assert tel.counters == {}
    assert tel.tracks == {}
    assert tel.audit == []


def test_scope_restores_disabled_state():
    with obs.scope() as tel:
        assert obs.enabled()
        assert tel is obs.get()
        obs.add("x")
    assert not obs.enabled()


# --------------------------------------------------------------------- #
# span nesting


def test_span_nesting_depths():
    with obs.scope() as tel:
        with obs.span("outer", kind="test"):
            with obs.span("inner"):
                pass
            with obs.span("inner2"):
                with obs.span("leaf"):
                    pass
    by_name = {s.name: s for s in tel.spans}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["inner2"].depth == 1
    assert by_name["leaf"].depth == 2
    # Children record before parents (exit order) and nest inside them.
    outer = by_name["outer"]
    for child in ("inner", "inner2", "leaf"):
        c = by_name[child]
        assert c.t0_ns >= outer.t0_ns
        assert c.t0_ns + c.dur_ns <= outer.t0_ns + outer.dur_ns
    assert by_name["outer"].args == {"kind": "test"}


def test_counters_and_deterministic_filter():
    with obs.scope():
        obs.add("auction.iterations", 3)
        obs.add("auction.iterations", 4)
        obs.add("jit.backend_compiles", 2)
        snap = obs.counters()
        assert snap["auction.iterations"] == 7.0
        det = obs.deterministic_counters(snap)
        assert "jit.backend_compiles" not in det
        assert det["auction.iterations"] == 7.0


def test_counters_since_delta():
    with obs.scope():
        obs.add("a", 1)
        before = obs.counters()
        obs.add("a", 2)
        obs.add("b", 5)
        obs.add("jit.x", 1)
        delta = obs.counters_since(before)
    assert delta == {"a": 2.0, "b": 5.0}


# --------------------------------------------------------------------- #
# Chrome trace export


def test_chrome_trace_export_schema():
    with obs.scope() as tel:
        with obs.span("round", t=1.0):
            with obs.span("phase"):
                pass
        obs.gauge("queue", 3.0)
        obs.gauge("queue", 5.0)
        obs.add("hits", 2)
        doc = obs.export.to_chrome_trace(tel)
    assert obs.export.validate_chrome_trace(doc) == []
    assert obs.export.slice_names(doc) == {"round", "phase"}
    assert obs.export.counter_track_names(doc) == {"queue"}
    assert doc["otherData"]["counters"]["hits"] == 2.0
    # Round-trips through JSON (Perfetto loads a file, not objects).
    doc2 = json.loads(json.dumps(doc))
    assert obs.export.validate_chrome_trace(doc2) == []


def test_chrome_trace_validator_rejects_bad_docs():
    assert obs.export.validate_chrome_trace({"no": "events"})
    assert obs.export.validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "x"}]}  # missing ts/dur/tid
    )
    # Overlapping-but-not-nested siblings on one thread -> nesting error.
    bad = {
        "traceEvents": [
            {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0,
             "dur": 10.0},
            {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 5.0,
             "dur": 10.0},
        ]
    }
    assert any("overlap" in p for p in obs.export.validate_chrome_trace(bad))


def test_record_span_synthetic_sublices_export():
    with obs.scope() as tel:
        t0 = tel.epoch_ns
        obs.record_span("window", t0 + 1000, 8000, {"rounds": 2})
        obs.record_span("round", t0 + 1000, 4000, {"round": 0}, depth=1)
        obs.record_span("round", t0 + 5000, 4000, {"round": 1}, depth=1)
        doc = obs.export.to_chrome_trace(tel)
    assert obs.export.validate_chrome_trace(doc) == []
    assert obs.export.slice_names(doc) == {"window", "round"}


def test_audit_jsonl_roundtrip(tmp_path):
    with obs.scope() as tel:
        obs.audit_event("controller_round", t=15.0, chosen_lane=2,
                        lanes=[{"lane": 0, "true_cost": 10}])
        obs.audit_event("controller_round", t=30.0, chosen_lane=0, lanes=[])
        path = tmp_path / "audit.jsonl"
        n = obs.export.save_audit_jsonl(str(path), tel)
    assert n == 2
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["t"] for r in recs] == [15.0, 30.0]
    assert recs[0]["kind"] == "controller_round"
    assert recs[0]["lanes"][0]["true_cost"] == 10


def test_bounded_buffers_count_drops():
    tel = obs.Telemetry(max_spans=2, max_track_samples=1, max_audit_events=1)
    for i in range(4):
        tel.record_span(f"s{i}", 0, 10)
        tel.gauge("t", float(i))
        tel.audit_event("k", i=i)
    assert len(tel.spans) == 2 and tel.dropped_spans == 2
    assert sum(len(v) for v in tel.tracks.values()) == 1
    assert tel.dropped_samples == 3
    assert len(tel.audit) == 1 and tel.dropped_audit == 3


# --------------------------------------------------------------------- #
# summary schema stability (SimMetrics <-> StreamingSimMetrics drop-in)


def _fill(m):
    m.record_perf_sample(1, 0.9)
    m.record_perf_sample(1, 0.8)
    m.record_perf_sample(2, 0.7)
    m.algo_runtime_s.append(0.01)
    m.placement_latency_s.extend([1.0, 2.0])
    m.response_time_s.append(30.0)
    m.migrated_pct_per_round.append(0.5)
    m.controller_improvement_per_round.append(100.0)
    m.degraded_jobs_per_round.append(3.0)
    m.tasks_placed += 4
    m.tasks_migrated += 1
    m.rounds += 2
    m.controller_rounds += 1


def test_summary_key_set_identical_empty_and_filled():
    for fill in (False, True):
        exact, stream = SimMetrics(), StreamingSimMetrics()
        if fill:
            _fill(exact)
            _fill(stream)
        k_exact = set(exact.summary())
        k_stream = set(stream.summary())
        assert k_exact == k_stream, (
            "SimMetrics and StreamingSimMetrics summary() diverged "
            f"(fill={fill}): {k_exact ^ k_stream}"
        )
        # The schema constants are the contract both classes iterate.
        for key in SUMMARY_SCALARS:
            assert key in k_exact
        for name, _attr in SUMMARY_SERIES:
            assert f"{name}_p50" in k_exact
            assert f"{name}_mean" in k_exact


# --------------------------------------------------------------------- #
# per-cell sweep telemetry: shard-merge identity


def test_sweep_cell_telemetry_shard_merge_identical():
    from repro.core.sweep import SweepSpec, merge_sweep_results, run_sweep

    spec = SweepSpec(
        n_machines=64, machines_per_rack=8, racks_per_pod=4,
        duration_s=120, target_utilisation=0.4,
        policies=("random", "nomora"), seeds=(0,),
        scenarios=("baseline",), fixed_algo_s=0.0,
    )
    obs.set_enabled(True)
    full = run_sweep(spec)
    shards = [run_sweep(spec, shard=(i, 2)) for i in range(2)]
    merged = merge_sweep_results(shards)
    assert [c.policy for c in merged.cells] == [c.policy for c in full.cells]
    for cf, cm in zip(full.cells, merged.cells):
        assert cf.telemetry is not None
        assert cm.telemetry == cf.telemetry, (cf.scenario, cf.policy)
        # Deterministic counters only: no process-warm-up accounting.
        assert not any(k.startswith("jit.") for k in cf.telemetry)
        assert cf.summary.keys() == cm.summary.keys()
        for k in cf.summary:
            a, b = cf.summary[k], cm.summary[k]
            assert a == b or (np.isnan(a) and np.isnan(b)), (k, a, b)
    # Round-trips through the saved-JSON schema (telemetry is optional
    # so pre-telemetry sweeps still load).
    from repro.core.sweep import SweepResult

    back = SweepResult.from_jsonable(
        json.loads(json.dumps(full.to_jsonable()))
    )
    assert back.cells[0].telemetry == full.cells[0].telemetry


# --------------------------------------------------------------------- #
# compare.py regression gating


def test_compare_docs_gating_and_directions():
    from benchmarks import compare

    base = {
        "cost_speedup": 4.0,
        "host_round_ms": 100.0,
        "telemetry": {"auction.iterations": 50.0},
        "n_machines": 256,
    }
    # Speedup halved (higher-better) and wall doubled (lower-better):
    # both gated regressions at the 50% threshold.
    fresh = {
        "cost_speedup": 1.5,
        "host_round_ms": 250.0,
        "telemetry": {"auction.iterations": 500.0},
        "n_machines": 256,
    }
    rows = compare.compare_docs("round_pipeline", base, fresh, 50.0)
    by_key = {r["key"].split(":", 1)[1]: r for r in rows}
    assert by_key["cost_speedup"]["regression"]
    assert by_key["host_round_ms"]["regression"]
    # Telemetry counters are reported but never gated.
    t = by_key["telemetry.auction.iterations"]
    assert t["pct"] == pytest.approx(900.0)
    assert not t["regression"]
    # Ungated config values never regress.
    assert not by_key["n_machines"]["regression"]
    # Improvements in the gated direction are fine.
    ok = compare.compare_docs(
        "round_pipeline", base, {**base, "cost_speedup": 9.0}, 50.0
    )
    assert not any(r["regression"] for r in ok)


def test_compare_obs_overhead_never_gated():
    from benchmarks import compare

    rows = compare.compare_docs(
        "obs_overhead",
        {"enabled_overhead_pct": 0.1, "base_ms": 10.0},
        {"enabled_overhead_pct": 4.9, "base_ms": 100.0},
        50.0,
    )
    assert not any(r["regression"] for r in rows)


def test_compare_dirs_handles_new_and_missing_files(tmp_path):
    from benchmarks import compare

    b, f = tmp_path / "base", tmp_path / "fresh"
    b.mkdir()
    f.mkdir()
    (b / "old.json").write_text('{"x_ms": 1.0}')
    (f / "old.json").write_text('{"x_ms": 1.1}')
    (f / "brand_new.json").write_text('{"y": 2.0}')
    rows = compare.compare_dirs(str(b), str(f), 50.0)
    notes = {r["key"]: r["note"] for r in rows}
    assert notes.get("brand_new:*") == "new file"
    assert not any(r["regression"] for r in rows)


# --------------------------------------------------------------------- #
# acceptance: instrumented migration-controller replay exports a valid
# Perfetto trace with nested round->phase slices, >= 6 counter tracks,
# and a non-empty migration audit log (ISSUE 8).


def test_export_acceptance_controller_replay(tmp_path):
    topo = topology.Topology(
        n_machines=64, machines_per_rack=8, racks_per_pod=4,
        slots_per_machine=4,
    )
    events = latency.LatencyEvents(
        hotspots=(
            latency.DriftingHotspot(
                start_s=30.0, end_s=220.0, rack0=0,
                drift_racks_per_s=8.0 / 240.0, width_racks=2,
                multiplier=6.0,
            ),
        )
    )
    plane = latency.LatencyPlane.synthesize(
        topo, duration_s=240, seed=0, events=events
    )
    wl = workload.synth_workload(
        topo, duration_s=240, seed=1, target_utilisation=0.35
    )
    cfg = simulator.SimConfig(
        policy="nomora", backend="auction_windowed", seed=11,
        migration_interval_s=15, migration_controller=True,
        qos_threshold=0.95, qos_window=2, qos_hold_s=30.0,
        whatif_betas=(0.0, 100.0 / 3600.0),
        params=PolicyParams(preemption=True, beta_scale=0.0),
    )
    with obs.scope() as tel:
        metrics = simulator.Simulator(wl, plane, cfg).run()
        doc = obs.export.to_chrome_trace(tel)
        audit_path = tmp_path / "audit.jsonl"
        n_audit = obs.export.save_audit_jsonl(str(audit_path), tel)

    assert metrics.rounds >= 16
    assert obs.export.validate_chrome_trace(doc) == []
    # >= 6 counter tracks (queue depth, pending roots, free slots,
    # running tasks, migrated %, degraded jobs).
    tracks = obs.export.counter_track_names(doc)
    assert len(tracks) >= 6, tracks
    assert {"sim.queue_depth", "sim.free_slots", "sim.migrated_pct"} <= tracks
    # Rounds are top-level slices with phases nested inside them.
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    rounds = [e for e in slices if e["name"] == "sim.round"]
    assert len(rounds) >= 16
    phase_names = {"sim.build_state", "sim.apply", "sim.roots"}

    def inside(parent, e):
        return (
            e["ts"] >= parent["ts"] - 1e-3
            and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
        )

    nested_phases = {
        e["name"]
        for e in slices
        if e["name"] in phase_names and any(inside(r, e) for r in rounds)
    }
    assert nested_phases == phase_names
    # Solver spans nest under rounds too (the fused window dispatch, with
    # its reconstructed per-round sub-slices below it).
    solver = [e for e in slices if e["name"].startswith("solver.")]
    assert solver and any(
        any(inside(r, e) for r in rounds) for e in solver
    )
    assert any(e["name"] == "round_program.round" for e in slices)
    # The controller ran and audited its rounds.
    assert n_audit > 0
    recs = [json.loads(l) for l in audit_path.read_text().splitlines()]
    assert all(r["kind"] == "controller_round" for r in recs)
    r0 = recs[0]
    assert r0["lanes"][0]["frozen_baseline"] is True
    assert {"degraded_jobs", "chosen_lane", "improvement", "budget",
            "n_moves_applied", "n_reverts"} <= set(r0)
    # Counters wired end to end: solver iterations, QoS triggers, oracle
    # LRU stats, upload accounting.
    c = doc["otherData"]["counters"]
    assert c.get("auction.iterations", 0) > 0
    assert c.get("qos.triggers", 0) > 0
    assert c.get("sim.tasks_migrated", 0) == metrics.tasks_migrated
    assert c.get("controller.rounds", 0) == metrics.controller_rounds


# --------------------------------------------------------------------- #
# Solver and host-round spans: profiler mirror, off path, span order

SOLVER_PARTS = (
    "round_program.upload",
    "round_program.dispatch",
    "round_program.sync",
    "round_program.fetch",
)


def _windowed_sim(duration_s=30):
    """A short replay through the windowed device backend (one R=1 window
    per solver round) at a small M."""
    topo = topology.Topology(
        n_machines=64, machines_per_rack=8, racks_per_pod=4,
        slots_per_machine=4,
    )
    plane = latency.LatencyPlane.synthesize(topo, duration_s=duration_s, seed=0)
    wl = workload.synth_workload(
        topo, duration_s=duration_s, seed=1, target_utilisation=0.35
    )
    cfg = simulator.SimConfig(policy="nomora", backend="auction_windowed", seed=3)
    return simulator.Simulator(wl, plane, cfg)


def test_spans_mirror_onto_profiler_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    sim = _windowed_sim()
    with obs.scope():
        with jax.profiler.trace(str(tmp_path)):
            sim.run()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}  # name -> [(start_ns, end_ns)] on the host plane
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns)
                    )

    def inside(iv, outers):
        return any(a <= iv[0] and iv[1] <= b for a, b in outers)

    rounds = host["sim.round"]
    solver = [iv for iv in host["solver.auction_windowed"] if inside(iv, rounds)]
    assert len(solver) == len(host["solver.auction_windowed"]) == sim.metrics.rounds
    for part in SOLVER_PARTS:
        assert len(host[part]) == len(solver), part
        assert all(inside(iv, solver) for iv in host[part]), part
    for name in ("sim.select", "sim.build_state", "round_program.stack", "sim.apply"):
        assert host[name] and all(inside(iv, rounds) for iv in host[name]), name
    # After-the-fact records stay in the registry alone.
    assert "round_program.advance" not in host
    assert "round_program.round" not in host


def test_disabled_solver_spans_add_no_device_sync(monkeypatch):
    import jax

    from repro.obs import spans

    calls = []
    real = jax.block_until_ready

    def counted(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counted)
    sim = _windowed_sim()
    sim.run()
    assert sim.metrics.rounds > 0
    assert calls == []
    assert obs.span("round_program.sync") is spans._NULL_SPAN
    # On, the upload span waits for its transfers: once per solver round.
    sim = _windowed_sim()
    with obs.scope():
        sim.run()
    assert len(calls) == sim.metrics.rounds


def test_advance_span_precedes_its_round_records():
    sim = _windowed_sim()
    with obs.scope() as tel:
        sim.run()
        total = obs.counters()["auction.iterations"]
    spans = list(tel.spans)
    starts = [i for i, s in enumerate(spans) if s.name == "round_program.advance"]
    assert len(starts) == sim.metrics.rounds
    iters = 0
    for i, j in zip(starts, starts[1:] + [len(spans)]):
        adv = spans[i]
        rounds = [s for s in spans[i + 1 : j] if s.name == "round_program.round"]
        assert len(rounds) == adv.args["rounds"] == len(adv.args["iterations"])
        assert [r.args["iterations"] for r in rounds] == adv.args["iterations"]
        iters += sum(adv.args["iterations"])
    assert not any(s.name == "round_program.round" for s in spans[: starts[0]])
    assert iters == total
