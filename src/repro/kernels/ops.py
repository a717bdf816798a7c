"""Jit'd public wrappers around the Pallas kernels.

``interpret=True`` (default off) runs the kernel bodies in Python on CPU
— the validation mode used by this repo's tests; on real TPUs the same
calls compile to Mosaic.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_matmul import moe_matmul
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_intra_chunk


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, block_q=128, block_k=128, interpret=False):
    return flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=interpret
    )


@partial(jax.jit, static_argnames=("block_c", "block_d", "block_f", "interpret"))
def moe_matmul_op(buf, w, *, block_c=128, block_d=128, block_f=128, interpret=False):
    return moe_matmul(
        buf, w, block_c=block_c, block_d=block_d, block_f=block_f, interpret=interpret
    )


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_op(x, weight, *, eps=1e-5, block_rows=256, interpret=False):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = rmsnorm(x2, weight, eps=eps, block_rows=min(block_rows, x2.shape[0]),
                  interpret=interpret)
    return out.reshape(shape)


@partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk_op(x, b, c, cum, *, interpret=False):
    return ssd_intra_chunk(x, b, c, cum, interpret=interpret)
