"""Plain float32 reference of what a cell computes: a model's forward
pass, the judge's sequence log-likelihood, and the GRPO loss, its
gradient and the AdamW update.

It imports nothing of the program.  Every matrix product runs at
``highest`` precision on float32 copies of the weights, layer by layer
inside a scan, and batches run in blocks of rows, so that a judge of
several GB fits beside its float32 temporaries.  ``prec="fp8"`` is the
control: the same computation with both operands of every matrix product
rounded to float8 (4 exponent and 3 mantissa bits, one scale per weight
matrix and per row of activations), the precision step below the configuration's bfloat16.

The layer equations are the architecture's (``arch/<name>.py``, named by
``dims["arch"]``); this module holds what every architecture shares: the
matrix product and its rounding, RMSNorm, rotary embeddings, the
batching, the judge's score, GRPO and AdamW.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chip import spec

HIGHEST = jax.lax.Precision.HIGHEST
#: fp8 e4m3 as ``reduce_precision`` rounds it (largest finite value 240)
F8_BITS, F8_MAX = (4, 3), 240.0


def round_to(x: jax.Array, dtype) -> jax.Array:
    """``x`` rounded to ``dtype``'s grid, kept in float32.  An explicit
    ``reduce_precision``: XLA may drop a pair of converts as excess
    precision, and does on the TPU."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)


def quantize(x: jax.Array, prec: str, axis=None) -> jax.Array:
    """``x`` as the matrix unit sees it: unchanged in f32, or rounded to
    scaled fp8 (scale = amax / 240 over ``axis``; None = whole tensor)."""
    if prec == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return jax.lax.reduce_precision(x / s, *F8_BITS) * s


def mm(subscripts: str, a: jax.Array, w: jax.Array, prec: str) -> jax.Array:
    """Activation ``a`` (scaled per row) times weight ``w`` (per matrix)."""
    return jnp.einsum(subscripts, quantize(a, prec, -1), quantize(w, prec), precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, S, N, hd]; rotate the two halves of the head by position."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("dims", "prec"))
def _logp_block(params, tokens, dims, prec):
    d = dict(dims)
    return spec.arch_of(d).token_logp(params, tokens, d, prec)


def _hashable(v):
    return tuple(_hashable(x) for x in v) if isinstance(v, list) else v


def frozen(dims: Dict) -> Tuple:
    """``dims`` as a static argument: sorted pairs, lists made tuples."""
    return tuple(sorted((k, _hashable(v)) for k, v in dims.items()))


def logp_rows(params, tokens: np.ndarray, dims: Dict, prec: str, rows: int) -> np.ndarray:
    """The architecture's ``token_logp`` over a batch, ``rows`` sequences
    at a time: the log-prob of each realised next token, [N, S-1]."""
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, tokens.shape[0], rows):
            out.append(np.asarray(_logp_block(params, jnp.asarray(tokens[i:i + rows]),
                                              frozen(dims), prec)))
    return np.concatenate(out, axis=0)


def judge_scores(params, tokens, dims, prec="f32", rows=2) -> np.ndarray:
    """Sequence log-likelihood of each row: the judge's reward."""
    return logp_rows(params, tokens, dims, prec, rows).astype(np.float64).sum(axis=1)


# ---------------------------------------------------------------------------
# GRPO and AdamW
# ---------------------------------------------------------------------------


def group_advantages(rewards: np.ndarray, group: int) -> np.ndarray:
    r = rewards.reshape(-1, group).astype(np.float32)
    adv = (r - r.mean(axis=1, keepdims=True)) / (r.std(axis=1, keepdims=True) + 1e-6)
    return adv.reshape(-1)


def _grpo_block_loss(params, tokens, mask, adv, old_logp, denom, dims, prec, clip_eps, kl_coef):
    logp = spec.arch_of(dims).token_logp(params, tokens, dims, prec, remat=True)
    ratio = jnp.exp(logp - old_logp)
    a = adv[:, None]
    pg = -jnp.minimum(ratio * a, jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * a)
    d = old_logp - logp  # the reference policy's log-probs are the rollout's
    kl = jnp.exp(d) - d - 1.0
    return (jnp.sum(pg * mask) + kl_coef * jnp.sum(kl * mask)) / denom


@partial(jax.jit, static_argnames=("dims", "prec", "clip_eps", "kl_coef"))
def _grpo_block(params, tokens, mask, adv, old_logp, denom, dims, prec, clip_eps, kl_coef):
    return jax.value_and_grad(_grpo_block_loss)(
        params, tokens, mask, adv, old_logp, denom, dict(dims), prec, clip_eps, kl_coef)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def grpo_grads(params, tokens, mask, adv, old_logp, dims, prec, grpo: Dict, rows: int):
    """Loss and gradient of the GRPO objective over the whole batch, summed
    over blocks of ``rows`` sequences (the loss is a sum over tokens divided
    by the batch's count of trained tokens)."""
    denom = jnp.float32(max(float(mask.sum()), 1.0))
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, tokens.shape[0], rows):
            sl = slice(i, i + rows)
            l, g = _grpo_block(params, jnp.asarray(tokens[sl]), jnp.asarray(mask[sl]),
                               jnp.asarray(adv[sl]), jnp.asarray(old_logp[sl]), denom,
                               frozen(dims), prec, grpo["clip_eps"], grpo["kl_coef"])
            loss += float(l)
            grads = g if grads is None else _add(grads, g)
    return loss, grads


@partial(jax.jit, static_argnames=("opt", "param_dtype"))
def adamw(params, grads, m, v, step, opt, param_dtype):
    """One AdamW step (global-norm clip, warm-up then cosine decay,
    decoupled decay).  Returns the new float32 parameters as stored (in
    ``param_dtype``), the moments, and the clipped gradient the moments
    were fed."""
    o = dict(opt)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / (gnorm + 1e-9))
    t = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, t / max(1, o["warmup_steps"]))
    prog = jnp.clip((t - o["warmup_steps"]) / max(1, o["total_steps"] - o["warmup_steps"]), 0, 1)
    lr = o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    b1, b2 = o["beta1"], o["beta2"]
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    clipped = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, clipped)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, clipped)
    params = jax.tree.map(
        lambda p, m, v: round_to(p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
                                            + o["weight_decay"] * p), param_dtype),
        params, m, v)
    return params, m, v, clipped


@jax.jit
def _norms(xs):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]


@jax.jit
def _diff_norms(xs, ys):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(xs, ys)]


def leaf_norms(tree, minus=None) -> Dict[str, float]:
    """Norm of each leaf (of ``tree - minus`` when given), by path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    xs = [x for _, x in flat]
    norms = _norms(xs) if minus is None else _diff_norms(xs, jax.tree.leaves(minus))
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}
