"""Bytes and operations of the two kernels, against hand counts, and the
peaks table's refusal of an unknown device."""

import pytest

import kernel_cost as kc


def test_costmap_bytes_by_hand():
    # (128, 12500): read f32 latency + write i32 cost + 128 int32 model ids.
    assert kc.costmap_bytes(128, 12_500) == 128 * 12_500 * 8 + 128 * 4 == 12_800_512


def test_bid_bytes_by_hand():
    # (1024, 12500): f32 values, two f32 price vectors, three (T,) outputs.
    assert kc.bid_bytes(1024, 12_500) == 1024 * 12_500 * 4 + 2 * 12_500 * 4 + 3 * 1024 * 4
    assert kc.bid_bytes(1024, 12_500) == 51_312_288


def test_roofline_is_memory_bound_on_v5e():
    peak = kc.load_peak("TPU v5 lite")
    t, bound = kc.roofline_s(kc.bid_bytes(1024, 12_500), kc.bid_flops(1024, 12_500), peak)
    assert bound == "memory"
    assert t == pytest.approx(51_312_288 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        kc.load_peak("TPU v9 imaginary")
