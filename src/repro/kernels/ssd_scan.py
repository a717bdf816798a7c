"""Mamba-2 SSD intra-chunk Pallas TPU kernel.

Computes, for one (batch, chunk, head) grid cell, the quadratic
intra-chunk output and the chunk's contribution to the inter-chunk
state (the sequential inter-chunk recurrence stays a cheap lax.scan in
:mod:`repro.models.ssm` — it is O(S/Q) steps over tiny states).

VMEM tiling: the [Q, Q] decay mask is materialized per head in VMEM
(Q = 256 -> 256 KB f32), never in HBM — on GPU the reference
implementation tiles over the same quadratic form with shared memory;
the TPU-native adaptation keeps one chunk resident and lets the MXU
run the [Q,N]x[N,Q] and [Q,Q]x[Q,hd] contractions.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, b_ref, c_ref, cum_row_ref, cum_col_ref, y_ref, state_ref):
    x = x_ref[...].astype(jnp.float32)  # [Q, hd] (dt-weighted inputs)
    b = b_ref[...].astype(jnp.float32)  # [Q, N]
    c = c_ref[...].astype(jnp.float32)  # [Q, N]
    cum_row = cum_row_ref[...].astype(jnp.float32)  # [1, Q]
    cum_col = cum_col_ref[...].astype(jnp.float32)  # [Q, 1]
    Q = x.shape[0]
    diff = cum_col - cum_row  # [Q, Q]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(row >= col, jnp.exp(diff), 0.0)
    cb = jax.lax.dot_general(  # c @ b.T
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * L  # [Q, Q]
    y_ref[...] = jnp.dot(cb, x, preferred_element_type=jnp.float32).astype(y_ref.dtype)
    decay_to_end = jnp.exp(cum_col_ref[Q - 1 :, :].astype(jnp.float32) - cum_col)  # [Q, 1]
    state_ref[...] = jax.lax.dot_general(  # (x * decay).T @ b
        x * decay_to_end, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(state_ref.dtype)


def ssd_intra_chunk(
    x: jax.Array,  # [BNC, H, Q, hd]  dt-weighted inputs per chunk
    b: jax.Array,  # [BNC, Q, N]
    c: jax.Array,  # [BNC, Q, N]
    cum: jax.Array,  # [BNC, H, Q]
    *,
    interpret: bool = False,
):
    """Returns (y_intra [BNC, H, Q, hd], states [BNC, H, hd, N]).

    ``cum`` enters the kernel twice, as a row [1, Q] and as a column
    [Q, 1] block: the decay mask needs both orientations, and either
    block's last two dims then equal the array's, as Mosaic requires."""
    BNC, H, Q, hd = x.shape
    N = b.shape[-1]
    grid = (BNC, H)
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, Q, hd), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((None, Q, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((None, Q, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((None, None, 1, Q), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((None, None, Q, 1), lambda i, h: (i, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, hd), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((None, None, hd, N), lambda i, h: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BNC, H, Q, hd), x.dtype),
            jax.ShapeDtypeStruct((BNC, H, hd, N), jnp.float32),
        ],
        interpret=interpret,
    )(x, b, c, cum[:, :, None, :], cum[:, :, :, None])
