"""Pallas TPU kernel: dense auction bidding (row top-2 with slot prices).

The auction solver's hot spot is, per Jacobi round, a (T, C) reduction:
for every unassigned task, the best and second-best offer over all machine
columns, where a machine's offer is value - lowest_slot_price and the
runner-up may be the same machine's second-lowest slot (DESIGN.md §4/§5).

TPU mapping: the column dimension is tiled into (BT, BC) VMEM blocks; the
running (best, second, argmax) triple lives in small revisited output blocks
so the reduction streams over C without materialising (T, C) twice. Rows are
a parallel grid dimension; columns are an 'arbitrary' (sequential) dimension
accumulated in-place — the canonical Pallas revisiting-output pattern.

Values are float32 carrying *integers* (the solver scales costs to ints and
keeps |V| < 2^24 by construction) so exactness is preserved on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-(2.0**62))
DEFAULT_BT = 256
DEFAULT_BC = 512


def _bid_kernel(values_ref, price1_ref, price2_ref, idx_ref, best_ref, second_ref):
    j = pl.program_id(1)
    bc = values_ref.shape[1]

    v1 = values_ref[...] - price1_ref[...]  # (BT, BC)
    v2 = values_ref[...] - price2_ref[...]

    tile_best = jnp.max(v1, axis=1, keepdims=True)  # (BT, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, v1.shape, 1)
    # Lowest column among the row's maxima — jnp.argmax's tie rule, which
    # the host reference and the auction's parity rely on. The TPU's
    # native arg-max reduction picks another tied column (measured on a
    # v5e), and integer-valued bids tie all the time.
    tile_arg = jnp.min(jnp.where(v1 == tile_best, cols, bc), axis=1)  # (BT,)
    is_arg = cols == tile_arg[:, None]
    runner_other = jnp.max(jnp.where(is_arg, NEG_INF, v1), axis=1, keepdims=True)
    runner_same = jnp.max(jnp.where(is_arg, v2, NEG_INF), axis=1, keepdims=True)
    tile_second = jnp.maximum(runner_other, runner_same)
    tile_idx = (tile_arg[:, None] + j * bc).astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = tile_idx
        best_ref[...] = tile_best
        second_ref[...] = tile_second

    @pl.when(j > 0)
    def _merge():
        cur_best = best_ref[...]
        cur_second = second_ref[...]
        cur_idx = idx_ref[...]
        new_best = jnp.maximum(cur_best, tile_best)
        new_second = jnp.maximum(
            jnp.minimum(cur_best, tile_best), jnp.maximum(cur_second, tile_second)
        )
        idx_ref[...] = jnp.where(tile_best > cur_best, tile_idx, cur_idx)
        best_ref[...] = new_best
        second_ref[...] = new_second


@functools.partial(jax.jit, static_argnames=("block_t", "block_c", "interpret"))
def bid_top2_pallas(
    values: jnp.ndarray,  # (T, C) f32
    price1: jnp.ndarray,  # (C,) f32 lowest slot price per column
    price2: jnp.ndarray,  # (C,) f32 second-lowest slot price per column
    *,
    block_t: int = DEFAULT_BT,
    block_c: int = DEFAULT_BC,
    interpret: bool = False,
):
    T, C = values.shape
    bt = min(block_t, T)
    bc = min(block_c, C)
    if C % bc != 0:
        # Pad columns with NEG_INF values so they can never win a bid.
        pad = -C % bc
        values = jnp.pad(values, ((0, 0), (0, pad)), constant_values=NEG_INF)
        price1 = jnp.pad(price1, (0, pad))
        price2 = jnp.pad(price2, (0, pad))
        C = C + pad
    grid = (pl.cdiv(T, bt), C // bc)
    idx, best, second = pl.pallas_call(
        _bid_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        values.astype(jnp.float32),
        price1.astype(jnp.float32)[None, :],
        price2.astype(jnp.float32)[None, :],
    )
    return idx[:, 0], best[:, 0], second[:, 0]
