"""Pallas TPU kernel: single-token decode attention against a KV cache.

Decode is memory-bound: the whole KV cache streams once through VMEM per
new token. Tiling: (batch*head) parallel grid dim; the cache's sequence
axis streams in BK tiles (sequential) with the online-softmax triple in
VMEM scratch, exactly like the flash kernel but with a single query row.
Per-batch valid lengths mask the tail tile; fully-invalid tiles are
skipped with pl.when so short sequences in a ragged batch cost nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 1024
NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, bk
):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * bk < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (1, BK)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, D)
    k_cache: jnp.ndarray,  # (B, KVH, S, D)
    v_cache: jnp.ndarray,  # (B, KVH, S, D)
    lengths: jnp.ndarray,  # (B,) int32
    *,
    scale: float | None = None,
    block_k: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, D = q.shape
    KVH, S = k_cache.shape[1], k_cache.shape[2]
    assert H % KVH == 0
    group = H // KVH
    if scale is None:
        scale = 1.0 / (D**0.5)
    bk = min(block_k, S)
    assert S % bk == 0

    grid = (B * H, S // bk)
    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bh, ki: (bh // H,)),
            pl.BlockSpec((1, 1, 1, D), lambda bh, ki: (bh // H, bh % H, 0, 0)),
            pl.BlockSpec(
                (1, 1, bk, D), lambda bh, ki: (bh // H, (bh % H) // group, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, D), lambda bh, ki: (bh // H, (bh % H) // group, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda bh, ki: (bh // H, bh % H, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q[:, :, None, :], k_cache, v_cache)
    return out[:, :, 0, :]
