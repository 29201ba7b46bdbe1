"""Pallas TPU kernel: RWKV-6 data-dependent-decay linear recurrence.

TPU adaptation: the (N x N) per-head state lives in VMEM scratch across the
whole sequence (N=64 => 16 KB fp32); time streams in BT-step tiles as a
sequential grid dimension. Inside a tile the recurrence is a fori_loop of
rank-1 updates — outer products and row-scalings on (N, N) VPU tiles, no
MXU needed. (b, h) pairs are the parallel grid dimension, so a pod's worth
of heads fills all cores; HBM traffic is exactly one read of r/k/v/w and
one write of o per token (the roofline optimum for this op).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 128


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sf_ref, s_scr, *, bt
):
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)  # (BT, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (N,)

    def step(t, carry):
        S, out = carry
        r_t = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)  # (1, N)
        k_t = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        v_t = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        w_t = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = k_t.T * v_t  # (N, N) rank-1 outer product
        o_t = ((S + u[:, None] * kv) * r_t.T).sum(axis=0, keepdims=True)  # (1, N)
        S = w_t.T * S + kv
        out = jax.lax.dynamic_update_slice_in_dim(out, o_t, t, 0)
        return S, out

    S0 = s_scr[...]
    out0 = jnp.zeros((bt, r.shape[1]), jnp.float32)
    S, out = jax.lax.fori_loop(0, bt, step, (S0, out0))
    s_scr[...] = S
    o_ref[0, 0] = out.astype(o_ref.dtype)

    @pl.when(ti == nt - 1)
    def _final():
        sf_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan_pallas(
    r: jnp.ndarray,  # (B, H, T, N)
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,  # decay in (0,1)
    u: jnp.ndarray,  # (H, N)
    s0: jnp.ndarray | None = None,  # (B, H, N, N)
    *,
    block_t: int = DEFAULT_BT,
    interpret: bool = False,
):
    B, H, T, N = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, N, N), jnp.float32)
    bt = min(block_t, T)
    assert T % bt == 0

    grid = (B * H, T // bt)
    kernel = functools.partial(_rwkv6_kernel, bt=bt)
    out, s_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bt, N), lambda bh, ti: (bh // H, bh % H, ti, 0)),
            pl.BlockSpec((1, 1, bt, N), lambda bh, ti: (bh // H, bh % H, ti, 0)),
            pl.BlockSpec((1, 1, bt, N), lambda bh, ti: (bh // H, bh % H, ti, 0)),
            pl.BlockSpec((1, 1, bt, N), lambda bh, ti: (bh // H, bh % H, ti, 0)),
            pl.BlockSpec((1, N), lambda bh, ti: (bh % H, 0)),
            pl.BlockSpec((1, 1, N, N), lambda bh, ti: (bh // H, bh % H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bt, N), lambda bh, ti: (bh // H, bh % H, ti, 0)),
            pl.BlockSpec((1, 1, N, N), lambda bh, ti: (bh // H, bh % H, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return out, s_final
