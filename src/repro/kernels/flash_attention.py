"""Flash attention Pallas TPU kernel (causal, GQA).

TPU adaptation: query blocks ride the grid so the MXU sees
[block_q, d] x [d, block_k] matmuls, and K/V stream through VMEM one
[block_k, d] block per step of the innermost grid axis.  The
online-softmax running (max, sum, acc) lives in VMEM scratch across
those steps, so VMEM holds a few blocks whatever the sequence length.
Block sizes default to MXU-aligned 128.

Layout: q [B, H, S, d], k/v [B, KV, S, d] -> out [B, H, S, d].
Grid: (B, H, S // block_q, S // block_k); GQA maps query head h to kv
head h // g.  Under the causal mask, K/V blocks past the diagonal are
neither computed nor fetched: their index map repeats the last needed
block, and the pipeline skips a copy whose block index is unchanged.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [block_q, d]
    k_ref,  # [block_k, d]
    v_ref,  # [block_k, d]
    o_ref,  # [block_q, d]
    m_scr,  # [block_q, 1] running max
    l_scr,  # [block_q, 1] running sum
    acc_scr,  # [block_q, d] running output
    *,
    block_q: int,
    block_k: int,
    causal: bool,
):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_cur

    if causal:
        # k-blocks strictly after this q-block contribute nothing
        pl.when(kb * block_k < (qb + 1) * block_q)(_step)
    else:
        _step()

    @pl.when(kb == pl.num_programs(3) - 1)
    def _done():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, H, S, d]
    k: jax.Array,  # [B, KV, S, d]
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, d = q.shape
    KV = k.shape[1]
    g = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)

    def kv_index(b, h, i, j):
        if causal:  # past the diagonal: repeat the last needed block
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        return (b, h // g, j, 0)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_k, d), kv_index),
            pl.BlockSpec((None, None, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
