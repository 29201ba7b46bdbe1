"""The trace reduction on small traces: the busy union of overlapping
device ops, kernel time and calls by name, the top ops, and idle gaps
named by the host annotation that covers them."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _hand_trace():
    ms = 1e6
    host = [
        (0.0, 100 * ms, "bench.window"),
        (0.0, 10 * ms, "bench.admit"),
        (10 * ms, 60 * ms, "bench.round"),
        (70 * ms, 30 * ms, "bench.idle"),
    ]
    dev = [
        (20 * ms, 10 * ms, "%fusion.1 = f32[8] fusion(%costmap_pallas.2)"),
        (25 * ms, 10 * ms, "%costmap_pallas.2 = s32[8,16] custom-call(%copy.1)"),  # overlaps fusion.1
        (40 * ms, 5 * ms, "%bid_top2_pallas.3 = f32[8] custom-call(%fusion.1)"),
        (50 * ms, 5 * ms, "%bid_top2_pallas.3 = f32[8] custom-call(%fusion.1)"),
        (95 * ms, 10 * ms, "%copy.4 = f32[8] copy(%costmap_pallas.2)"),  # runs past the window's end
        (200 * ms, 1 * ms, "%fusion.9 = f32[8] fusion()"),  # after the window
    ]
    return {"device": [dev], "host": host}


def test_union_merges_overlaps():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_hand_trace():
    r = tr.reduce(_hand_trace(), ("costmap_pallas", "bid_top2_pallas"))
    # busy: [20, 35) + [40, 45) + [50, 55) + [95, 100) = 30 ms of 100 ms
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["idle_share"] == pytest.approx(0.70)
    assert r["kernel_s"]["costmap_pallas"] == pytest.approx(0.010)
    assert r["kernel_s"]["bid_top2_pallas"] == pytest.approx(0.010)
    assert r["kernel_calls"] == {"costmap_pallas": 1, "bid_top2_pallas": 2}
    assert r["device_ops"][0][0] == "%fusion.1"
    gaps = dict(r["idle_gaps"])
    # [0, 10) admit; [10, 20) + [35, 40) + [45, 50) + [55, 70) round;
    # [70, 95) idle.
    assert gaps["bench.admit"] == pytest.approx(0.010)
    assert gaps["bench.round"] == pytest.approx(0.035)
    assert gaps["bench.idle"] == pytest.approx(0.025)


def test_no_device_ops_gives_nothing():
    t = _hand_trace()
    t["device"] = [[]]
    assert tr.reduce(t) is None


@pytest.mark.skipif(
    not os.path.exists(os.path.join(HERE, "recorded_trace.json")),
    reason="no recorded trace",
)
def test_recorded_trace():
    """A slice of a real TPU v5e trace of the serve cell (see the file's
    ``about``), reduced here and checked by hand counts."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    r = tr.reduce(rec["events"], ("costmap_pallas", "bid_top2_pallas"))
    exp = rec["expected"]
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert r["kernel_calls"] == exp["kernel_calls"]
    assert r["kernel_s"] == pytest.approx(exp["kernel_s"], rel=1e-9)
    assert dict(r["idle_gaps"]) == pytest.approx(exp["idle_gaps"], rel=1e-9)


def test_op_names_and_containers():
    assert tr.op_base("%costmap_pallas.2") == "costmap_pallas"
    assert tr.op_base("%bid_top2_pallas") == "bid_top2_pallas"
    assert tr.op_base("%fusion.v2") == "fusion.v2"
    t = _hand_trace()
    t["device"][0].append((20e6, 30e6, "%while.17 = (s32[]) while(%tuple.1)"))
    r = tr.reduce(t, ("costmap_pallas",))
    assert all(not name.startswith("%while") for name, _ in r["device_ops"])
    assert r["busy_s"] == pytest.approx(0.040)  # [20, 50) + [50, 55) + [95, 100)
