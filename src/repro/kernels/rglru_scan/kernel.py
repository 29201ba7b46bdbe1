"""Pallas TPU kernel: RG-LRU gated linear recurrence.

Elementwise diagonal recurrence: channels are embarrassingly parallel, so
the channel axis is tiled (BD lanes) as a parallel grid dimension together
with batch; time streams sequentially in BT tiles with the (1, BD) hidden
state held in VMEM scratch. Within a tile, a fori_loop of fused
multiply-adds — pure VPU work, one HBM read per input element and one
write per output element (memory-roofline optimal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 256
DEFAULT_BD = 512


def _rglru_kernel(la_ref, gx_ref, h0_ref, o_ref, hf_ref, h_scr, *, bt):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    la = la_ref[0].astype(jnp.float32)  # (BT, BD)
    gx = gx_ref[0].astype(jnp.float32)

    def step(t, carry):
        h, out = carry  # (1, BD), (BT, BD)
        la_t = jax.lax.dynamic_slice_in_dim(la, t, 1, 0)
        gx_t = jax.lax.dynamic_slice_in_dim(gx, t, 1, 0)
        a_t = jnp.exp(la_t)
        mult = jnp.sqrt(-jnp.expm1(2.0 * la_t))
        h = a_t * h + mult * gx_t
        out = jax.lax.dynamic_update_slice_in_dim(out, h, t, 0)
        return h, out

    h0 = h_scr[...]
    out0 = jnp.zeros_like(la)
    h, out = jax.lax.fori_loop(0, bt, step, (h0, out0))
    h_scr[...] = h
    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ti == nt - 1)
    def _final():
        hf_ref[...] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def rglru_scan_pallas(
    log_a: jnp.ndarray,  # (B, T, D)
    gx: jnp.ndarray,  # (B, T, D)
    h0: jnp.ndarray | None = None,  # (B, D)
    *,
    block_t: int = DEFAULT_BT,
    block_d: int = DEFAULT_BD,
    interpret: bool = False,
):
    B, T, D = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, D), jnp.float32)
    bt = min(block_t, T)
    bd = min(block_d, D)
    assert T % bt == 0 and D % bd == 0

    grid = (B * (D // bd), T // bt)
    nd = D // bd
    kernel = functools.partial(_rglru_kernel, bt=bt)
    out, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda bd_, ti: (bd_ // nd, ti, bd_ % nd)),
            pl.BlockSpec((1, bt, bd), lambda bd_, ti: (bd_ // nd, ti, bd_ % nd)),
            pl.BlockSpec((1, bd), lambda bd_, ti: (bd_ // nd, bd_ % nd)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda bd_, ti: (bd_ // nd, ti, bd_ % nd)),
            pl.BlockSpec((1, bd), lambda bd_, ti: (bd_ // nd, bd_ % nd)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, D), gx.dtype),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(log_a, gx, h0)
    return out, h_final
