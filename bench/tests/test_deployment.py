"""What a configuration may state beyond its cluster and trace: a
``scheduler`` block (``SimConfig`` fields), a ``plane`` block (latency
events and storms) and the ``reference`` that checks it.

A configuration without these keys runs exactly as before they existed:
the same ``SimConfig``, the same plane bytes, the same sampled rounds. One
with them (``tiny_hotspot.json``: a drifting rack hotspot with the QoS
migration controller on) runs through the whole harness with a reference
of its own (``stub_reference.py``), both new files alone, and the shipped
reference refuses to check it.
"""

import copy
import json
import math
import os

import numpy as np
import pytest

import check
import harness
import run as bench_run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3000000001
PLANE_SEED = SEED % 2**32
CONFIGS = ["configs/google-12500.json", "configs/facebook-12500.json", "tests/tiny.json"]


def _load(rel):
    with open(os.path.join(BENCH, rel), encoding="utf-8") as f:
        return json.load(f)


def _topo(cfg):
    from repro.core.topology import Topology

    return Topology(**{k: int(v) for k, v in cfg["topology"].items()})


# ------------------------------------------------------------------ #
# A configuration without the new keys runs as before


@pytest.mark.parametrize("mix", ["serve", "replay"])
@pytest.mark.parametrize("config", CONFIGS)
def test_config_without_blocks_gives_the_same_sim_config(config, mix):
    from repro.core.policy import PolicyParams
    from repro.core.simulator import SimConfig

    tr = _load(f"traffic/{mix}.json")
    params = PolicyParams(**tr["policy"])
    before = SimConfig(
        backend="auction_windowed",
        params=params,
        seed=PLANE_SEED,
        max_round_tasks=int(tr["max_round_tasks"]),
        migration_interval_s=int(tr.get("migration_interval_s", 10)),
        fixed_algo_s=0.0,
        streaming_metrics=True,
    )
    assert harness.sim_config(_load(config), tr, params, PLANE_SEED) == before


@pytest.mark.parametrize("config", CONFIGS)
def test_config_without_blocks_gives_the_same_plane(config):
    from repro.core.latency import LatencyEvents, LatencyPlane

    cfg = _load(config)
    topo = _topo(cfg)
    horizon = int(cfg["fill_s"]) + 80
    plane = harness.latency_plane(topo, cfg, horizon, PLANE_SEED)
    before = LatencyPlane.synthesize(topo, horizon, seed=PLANE_SEED)
    assert plane.series.tobytes() == before.series.tobytes()
    assert plane.events == LatencyEvents()
    assert plane.rack_multipliers(horizon - 1) is None and plane.regime_epoch(horizon - 1) == 0


def test_place_log_samples_the_same_rounds():
    """Indices drawn by the parent's ``sample_rounds`` for this log and
    these seeds; a log of ``place`` rounds draws them still."""
    rng = np.random.default_rng(7)
    sizes = rng.integers(1, 600, 120)
    rounds = [
        dict(kind="place", task_job=np.zeros(int(s)), migration=i % 10 == 0)
        for i, s in enumerate(sizes)
    ]
    replay = [0, 33, 40, 44, 46, 60, 62, 69, 80, 87, 88, 90, 94, 95, 97, 105]
    serve = [
        0, 1, 2, 7, 8, 12, 13, 14, 15, 18, 23, 24, 26, 27, 29, 31, 33, 34, 36, 39, 41, 43,
        46, 48, 49, 51, 53, 56, 63, 64, 65, 67, 68, 70, 74, 75, 77, 79, 81, 82, 85, 86, 88,
        89, 90, 94, 95, 96, 97, 98, 100, 102, 104, 105, 106, 107, 108, 109, 111, 112, 113,
        114, 116, 118,
    ]
    for kinds in (None, ("place",), ("place", "controller")):
        assert check.sample_rounds(rounds, 3000000001, 16, 4, kinds) == replay
        assert check.sample_rounds(rounds, 1618033988, 64, 0, kinds) == serve


def test_sample_draws_only_the_kinds_its_reference_solves():
    rounds = [
        dict(kind="controller" if i % 3 == 0 else "place", task_job=np.zeros(i + 1),
             migration=i % 3 == 0)
        for i in range(60)
    ]
    picks = check.sample_rounds(rounds, 5, 12, 2, ("place",))
    assert len(picks) == 12 and all(rounds[i]["kind"] == "place" for i in picks)
    assert max(picks) == 59  # the largest place round
    ctrl = check.sample_rounds(rounds, 5, 4, 2, ("controller",))
    assert all(i % 3 == 0 for i in ctrl) and 57 in ctrl


# ------------------------------------------------------------------ #
# The blocks


def _expected_multipliers(hotspots, t, n_racks):
    mult = np.ones(n_racks, np.float32)
    for h in hotspots:
        if h["start_s"] <= t < h["end_s"]:
            lead = math.floor(h.get("rack0", 0) + h.get("drift_racks_per_s", 0.0) * (t - h["start_s"]))
            for k in range(h.get("width_racks", 1)):
                r = (lead + k) % n_racks
                mult[r] = max(mult[r], np.float32(h.get("multiplier", 3.0)))
    return mult


def test_plane_block_sets_hotspots_regime_and_storms():
    from repro.core.latency import LatencyPlane, SpikeStormSpec, overlay_spike_storms

    cfg = _load("tests/tiny_hotspot.json")
    hotspots = [
        {"start_s": 100, "end_s": 400, "rack0": 3, "drift_racks_per_s": 0.05,
         "width_racks": 2, "multiplier": 4.0},
        {"start_s": 300, "end_s": 500, "rack0": 14, "width_racks": 3, "multiplier": 2.5},
    ]
    storms = {"storms_per_hour": 60, "mean_duration_s": 30, "amp_scale": 2.0,
              "tiers": [3], "traces": 2}
    cfg["plane"] = {"hotspots": hotspots, "regime": {"times": [200, 350], "frac": 0.25},
                    "storms": storms}
    topo = _topo(cfg)
    plane = harness.latency_plane(topo, cfg, 600, PLANE_SEED)
    for t in (0, 99, 100, 250, 320, 399, 400, 499, 599):
        assert np.array_equal(plane.rack_multipliers(t),
                              _expected_multipliers(hotspots, t, topo.n_racks)), t
    assert [plane.regime_epoch(t) for t in (199, 200, 349, 350, 599)] == [0, 1, 1, 2, 2]
    assert plane.events.regime.frac == 0.25

    calm = LatencyPlane.synthesize(topo, 600, seed=PLANE_SEED).series
    added = plane.series - calm
    assert (added >= 0).all() and added[3, :2].any()
    assert not np.delete(added.reshape(-1, 600), [3 * 6, 3 * 6 + 1], axis=0).any()
    spec = SpikeStormSpec(seed=PLANE_SEED ^ harness.STORM_SEED_SALT, storms_per_hour=60,
                          mean_duration_s=30, amp_scale=2.0, tiers=(3,), traces=2)
    assert np.array_equal(plane.series, overlay_spike_storms(calm, spec))
    other = harness.latency_plane(topo, cfg, 600, PLANE_SEED + 1)
    assert not np.array_equal(other.series - LatencyPlane.synthesize(
        topo, 600, seed=PLANE_SEED + 1).series, added)  # storms follow the seed


def test_scheduler_block_reaches_sim_config():
    from repro.core.policy import PolicyParams

    tr = _load("traffic/replay.json")
    cfg = {"scheduler": {"migration_controller": True, "qos_threshold": 0.8, "qos_window": 3,
                         "qos_clear_margin": 0.05, "qos_hold_s": 30, "migration_budget": 32,
                         "whatif_betas": [0, 0.01], "device_latency": False}}
    sc = harness.sim_config(cfg, tr, PolicyParams(**tr["policy"]), 1)
    assert sc.migration_controller and sc.qos_window == 3 and sc.migration_budget == 32
    assert sc.qos_threshold == 0.8 and sc.qos_clear_margin == 0.05 and sc.qos_hold_s == 30.0
    assert sc.whatif_betas == (0.0, 0.01) and sc.backend == "auction_windowed"


@pytest.mark.parametrize(
    "block, key",
    [
        ({"scheduler": {"qos_treshold": 0.8}}, "qos_treshold"),
        ({"scheduler": {"backend": "auction_host"}}, "backend"),
        ({"plane": {"hotspot": []}}, "hotspot"),
        ({"plane": {"hotspots": [{"start_s": 0, "end_s": 9, "speed": 1}]}}, "speed"),
        ({"plane": {"storms": {"seed": 4}}}, "seed"),
    ],
)
def test_unknown_key_is_refused_by_name(block, key):
    from repro.core.policy import PolicyParams

    cfg = {**_load("tests/tiny.json"), **block}
    tr = _load("traffic/replay.json")
    with pytest.raises(ValueError, match=f"unknown key '{key}'"):
        harness.sim_config(cfg, tr, PolicyParams(**tr["policy"]), 1)
        harness.latency_plane(_topo(cfg), cfg, 10, 1)


# ------------------------------------------------------------------ #
# The reference a configuration names


def test_config_selects_its_reference():
    cfg = _load("tests/tiny_hotspot.json")
    assert check.load_reference({}).KINDS == ("place",)
    stub = check.load_reference({**cfg, "reference": "tests/stub_reference.py"})
    assert "controller" in stub.KINDS and set(stub.BLOCKS) == {"plane", "scheduler"}
    with pytest.raises(FileNotFoundError, match="no_such_reference"):
        check.load_reference({"reference": "tests/no_such_reference.py"})
    with pytest.raises(ValueError, match="outside bench"):
        check.load_reference({"reference": "../src/repro/core/policy.py"})


@pytest.mark.parametrize("block", ["plane", "scheduler"])
def test_shipped_reference_refuses_a_block(block):
    cfg = {**_load("tests/tiny.json"), block: {}}
    with pytest.raises(ValueError, match=f"'{block}' block"):
        check.load_reference(cfg)


# ------------------------------------------------------------------ #
# The hotspot-plus-controller configuration through the harness

# The (task, job) buckets this cell's windows reach at these seeds; every
# one warms its round program and its controller's what-if programs.
HOT_BUCKETS = [[8, 8], [16, 8], [32, 8], [64, 8], [64, 16], [128, 16], [128, 32], [128, 64],
               [256, 64], [512, 64]]
HOT_SEED = 20251017


@pytest.fixture
def hotspot(tiny_cell):
    return tiny_cell("replay", config="tiny_hotspot.json", warm_buckets=HOT_BUCKETS)


def test_shipped_reference_stops_the_hotspot_config(hotspot):
    with pytest.raises(ValueError, match="'plane' block"):
        bench_run.run_cell(hotspot.name, HOT_SEED, 4.0, False, require_tpu=False, cell=hotspot)


def test_hotspot_config_runs_with_its_own_reference(hotspot, capsys):
    hotspot.config["reference"] = "tests/stub_reference.py"
    seen = []
    res = bench_run.run_cell(
        hotspot.name, HOT_SEED, 4.0, False, require_tpu=False, cell=hotspot,
        backend_factory=lambda b: seen.append(b) or b.sim.backend,
    )
    err = capsys.readouterr().err
    bench = seen[0]
    assert "compiles in window: 0;" in err
    kinds = bench.kinds_logged()
    assert kinds.get("controller", 0) >= 1 and kinds.get("place", 0) >= 1
    assert bench.lane_counts() == [3, 5]
    for r in bench.log.rounds:
        if r["kind"] != "controller":
            assert "lanes" not in r
            continue
        T = len(r["task_job"])
        assert len(r["lanes"]) in (3, 5) and len(r["outcomes"]) == len(r["lanes"])
        assert all(lane["active"].shape == (T,) for lane in r["lanes"])
        assert not r["lanes"][0]["active"][len(r["ready_ids"]):].any()  # baseline: all frozen
        assert r["outcomes"][r["lane"]] == r["outcomes"].min() or r["lane"] == 0
        assert r["cols"].shape == (T,) and r["migration"]
    check_line = next(line for line in err.splitlines() if line.startswith("[check]"))
    assert "'controller'" in check_line
    # The stub places nothing and moves nothing: the comparison finds the
    # tasks the program placed and moved.
    assert not res["correct"] and res["checks"]["mismatched_tasks"]["value"] > 0
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device", "checks"}


def test_shared_backend_logs_each_run(hotspot):
    """As ``control.py`` runs seeds: one warmed backend serves every run,
    and each run's log records its own controller rounds."""
    shared = []

    def backend(b):
        if not shared:
            shared.append(b.sim.backend)
        return shared[0]

    for seed in (HOT_SEED, HOT_SEED + 1):
        bench, run, _setup = bench_run.measure(
            hotspot, seed, 3.0, False, harness.CompileCounter(), backend)
        assert run.compiles_in_window == 0
        ctrl = [r for r in bench.log.rounds if r["kind"] == "controller"]
        assert ctrl and all("lanes" in r and "lane" in r for r in ctrl)


def test_whatif_rounds_are_logged(tiny_cell):
    # Without the controller every running task is a mover: 512 of them.
    warm = [[8, 8], [16, 8], [32, 8], [512, 128]]
    cell = tiny_cell("replay", config="tiny_hotspot.json", warm_buckets=warm)
    cell.config["scheduler"] = {"whatif_betas": [0.0, 0.05, 0.2]}
    bench, run, _setup = bench_run.measure(cell, HOT_SEED, 2.0, False, harness.CompileCounter())
    assert run.compiles_in_window == 0
    whatif = [r for r in bench.log.rounds if r["kind"] == "whatif"]
    assert whatif and bench.lane_counts() == [3]
    for r in whatif:
        assert [lane["params"]["beta_scale"] for lane in r["lanes"]] == [0.0, 0.05, 0.2]
        assert all(lane["active"].all() for lane in r["lanes"])
        assert r["outcomes"][r["lane"]] == r["outcomes"].min() and r["migration"]


def test_device_latency_warms_the_serving_path(tiny_cell):
    cell = tiny_cell("serve", rate_scale=40.0)
    cell.config = {**copy.deepcopy(cell.config), "scheduler": {"device_latency": True}}
    bench, run, _setup = bench_run.measure(cell, HOT_SEED, 2.0, False, harness.CompileCounter())
    assert bench.sim.oracle is not None and run.rounds > 0
    assert run.compiles_in_window == 0


def test_device_latency_needs_a_serving_mix(tiny_cell):
    cell = tiny_cell("replay")
    cell.config = {**copy.deepcopy(cell.config), "scheduler": {"device_latency": True}}
    with pytest.raises(ValueError, match="device_latency needs a serving mix"):
        harness.Bench(cell, HOT_SEED, 1.0)
