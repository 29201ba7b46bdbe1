"""Pallas TPU kernel: blocked causal (flash) attention with GQA.

TPU mapping (DESIGN.md §4 item 4): queries are tiled (BQ) as a parallel
grid dimension; keys stream sequentially (BK tiles) with the online-softmax
running (max, sum, acc) triple held in VMEM scratch. Logits accumulate in
fp32 on the MXU; block shapes default to (BQ, D) x (BK, D) with BQ=BK=512,
giving a ~(512x128 q + 512x128 k/v + 512x512 logits) fp32 working set of
~2.3 MB — comfortably inside a v5e core's 16 MB VMEM with double-buffering.

GQA is free: the kv BlockSpec index_map divides the head index by the
group size, so no repeated K/V materialisation in HBM.

Causality: k-tiles strictly above the diagonal are skipped via pl.when on
the *whole block* (the scheduler still iterates them, but no FLOPs issue),
and the diagonal tile applies an elementwise mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal, bq, bk
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # Skip k-tiles strictly above the diagonal block row.
        run = ki * bk <= qi * bq + (bq - 1)

    @pl.when(run if causal else (ki >= 0))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]  # (BQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)  # (BQ, 1)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, KVH, S, D)
    v: jnp.ndarray,  # (B, KVH, S, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BQ,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, S, D = q.shape
    KVH = k.shape[1]
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    if scale is None:
        scale = 1.0 / (D**0.5)
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, "seq len must divide block sizes"

    grid = (B * H, S // bq, S // bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda bh, qi, ki: (bh // H, (bh % H) // group, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, bk, D),
                lambda bh, qi, ki: (bh // H, (bh % H) // group, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, D), lambda bh, qi, ki: (bh // H, bh % H, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out
