"""Pallas kernel allclose sweeps (interpret mode) for the scheduler kernels:
costmap and auction_bid vs their pure-jnp oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perf_model
from repro.kernels.auction_bid import kernel as bid_kernel
from repro.kernels.auction_bid import ref as bid_ref
from repro.kernels.costmap import kernel as cm_kernel
from repro.kernels.costmap import ref as cm_ref

LUT = perf_model.perf_lut_table()


@pytest.mark.parametrize(
    "T,M",
    [(1, 1), (3, 7), (8, 128), (17, 300), (64, 513), (256, 1024)],
)
def test_costmap_kernel_matches_ref(T, M):
    rng = np.random.default_rng(T * 1000 + M)
    perf_idx = jnp.asarray(rng.integers(0, 4, size=T), jnp.int32)
    lat = jnp.asarray(rng.uniform(0, 1400, size=(T, M)), jnp.float32)
    got = cm_kernel.costmap_pallas(perf_idx, lat, interpret=True)
    want = cm_ref.costmap_ref(LUT, perf_idx, lat)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_t,block_m", [(8, 128), (16, 256), (256, 512)])
def test_costmap_kernel_blocking_invariance(block_t, block_m):
    rng = np.random.default_rng(0)
    T, M = 48, 700
    perf_idx = jnp.asarray(rng.integers(0, 4, size=T), jnp.int32)
    lat = jnp.asarray(rng.uniform(0, 1100, size=(T, M)), jnp.float32)
    got = cm_kernel.costmap_pallas(
        perf_idx, lat, block_t=block_t, block_m=block_m, interpret=True
    )
    want = cm_ref.costmap_ref(LUT, perf_idx, lat)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_costmap_boundary_latencies():
    # Threshold edges and the LUT rounding boundary (45 -> 40 vs 50).
    perf_idx = jnp.asarray([0, 0, 0, 0], jnp.int32)
    lat = jnp.asarray([[0.0, 39.9, 44.9, 45.1]], jnp.float32).T.repeat(4, 1)
    got = cm_kernel.costmap_pallas(perf_idx, lat, interpret=True)
    want = cm_ref.costmap_ref(LUT, perf_idx, lat)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "T,C",
    [(1, 2), (5, 17), (32, 128), (50, 700), (128, 1024)],
)
def test_auction_bid_kernel_matches_ref(T, C):
    rng = np.random.default_rng(T * 31 + C)
    # Integer-valued f32, like the solver produces.
    values = jnp.asarray(
        rng.integers(-(2**20), 0, size=(T, C)).astype(np.float32)
    )
    price1 = jnp.asarray(rng.integers(0, 2**16, size=C).astype(np.float32))
    price2 = jnp.asarray(
        np.maximum(np.asarray(price1), rng.integers(0, 2**17, size=C)).astype(
            np.float32
        )
    )
    gi, gb, gs = bid_kernel.bid_top2_pallas(values, price1, price2, interpret=True)
    ri, rb, rs = bid_ref.bid_top2_ref(values, price1, price2)
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))


@pytest.mark.parametrize("block_c", [128, 512])
def test_auction_bid_kernel_breaks_ties_to_lowest_column(block_c):
    # Integer-valued bids tie constantly; the winner must be the lowest
    # tied column (jnp.argmax's rule), within a tile and across tiles.
    rng = np.random.default_rng(7)
    T, C = 16, 1024
    values = jnp.asarray(rng.integers(-4, 0, size=(T, C)).astype(np.float32))
    price1 = jnp.asarray(rng.integers(0, 2, size=C).astype(np.float32))
    price2 = price1 + 1
    gi, gb, gs = bid_kernel.bid_top2_pallas(
        values, price1, price2, block_c=block_c, interpret=True
    )
    ri, rb, rs = bid_ref.bid_top2_ref(values, price1, price2)
    for g, r in ((gi, ri), (gb, rb), (gs, rs)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_auction_bid_single_column_second_is_slot2():
    # With one column, the runner-up offer must be its second slot price.
    values = jnp.asarray([[-100.0]], jnp.float32)
    p1 = jnp.asarray([5.0], jnp.float32)
    p2 = jnp.asarray([9.0], jnp.float32)
    gi, gb, gs = bid_kernel.bid_top2_pallas(values, p1, p2, interpret=True)
    assert float(gb[0]) == -105.0
    assert float(gs[0]) == -109.0
    assert int(gi[0]) == 0
