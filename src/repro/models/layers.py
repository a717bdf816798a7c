"""Shared model building blocks: params DSL, RMSNorm, RoPE, GQA attention.

All modules are pure functions over explicit parameter pytrees.  Parameter
trees are described by :class:`ParamDef` schemas — one schema drives both
initialization (values) and sharding (PartitionSpecs via logical dims),
so the two can never drift apart.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.rules import Rules


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    dims: Tuple[Optional[str], ...]  # logical axis labels, len == len(shape)
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.02

    def __post_init__(self) -> None:
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)


Schema = Dict[str, "SchemaNode"]  # nested dicts of ParamDef


def init_from_schema(key: jax.Array, schema, dtype) -> dict:
    flat, treedef = jax.tree.flatten(schema, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(flat))
    vals = []
    for k, pdef in zip(keys, flat):
        if pdef.init == "zeros":
            vals.append(jnp.zeros(pdef.shape, dtype))
        elif pdef.init == "ones":
            vals.append(jnp.ones(pdef.shape, dtype))
        else:
            vals.append(
                (jax.random.normal(k, pdef.shape, jnp.float32) * pdef.scale).astype(dtype)
            )
    return jax.tree.unflatten(treedef, vals)


def abstract_from_schema(schema, dtype) -> dict:
    """ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
        schema,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def specs_from_schema(schema, rules: Rules) -> dict:
    return jax.tree.map(
        lambda p: rules.spec(p.shape, p.dims),
        schema,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def stacked(pdef: ParamDef, layers: int) -> ParamDef:
    """Layer-stacked parameter for ``lax.scan`` over the depth dimension."""
    return ParamDef(
        (layers, *pdef.shape), ("layers", *pdef.dims), pdef.init, pdef.scale
    )


# ---------------------------------------------------------------------------
# norms / positional encodings
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, N, head_dim]; positions: [B, S] (int32)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, causal / sliding-window / cross / decode)
# ---------------------------------------------------------------------------


def attention_schema(cfg: ModelConfig) -> Dict[str, ParamDef]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, h * hd), ("embed", "qkv")),
        "wk": ParamDef((d, kv * hd), ("embed", "qkv")),
        "wv": ParamDef((d, kv * hd), ("embed", "qkv")),
        "wo": ParamDef((h * hd, d), ("qkv", "embed")),
    }


def multihead_attention(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    mask: Optional[jax.Array] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    sliding_window: int = 0,
    rules: Optional[Rules] = None,
    use_rope: bool = True,
) -> jax.Array:
    """Full (non-incremental) GQA attention.

    ``kv_override`` supplies external keys/values (cross-attention);
    ``sliding_window > 0`` restricts attention to the last W positions.
    """
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kv

    # BEYOND-PAPER (EXPERIMENTS.md §Perf, pair 1 iteration 6): when the
    # head count does not divide the model axis (granite 24H, smollm 15H,
    # hymba 25H on 16) GSPMD replicates ALL attention activations and
    # compute.  Pad (kv, g) group-interleaved — real head i keeps its kv
    # group, dead q columns are zero, dead kv rows have zero keys so
    # their scores are uniform over zero values, and zero out-proj rows
    # cancel dead-head outputs — so the math is exactly GQA(h, kv) while
    # the padded head dim shards.
    wq, wk, wv, wo = params["wq"], params["wk"], params["wv"], params["wo"]
    kv_p, g_p = kv, g
    if rules is not None and kv_override is None:
        ext = rules.extent("heads")
        if ext > 1 and h % ext:
            kv_p, g_p = _pad_plan(kv, g, ext)
        if kv_p != kv or g_p != g:
            D = wq.shape[0]
            wq = jnp.pad(
                wq.reshape(D, kv, g, hd),
                ((0, 0), (0, kv_p - kv), (0, g_p - g), (0, 0)),
            ).reshape(D, kv_p * g_p * hd)
            wk = jnp.pad(
                wk.reshape(D, kv, hd), ((0, 0), (0, kv_p - kv), (0, 0))
            ).reshape(D, kv_p * hd)
            wv = jnp.pad(
                wv.reshape(D, kv, hd), ((0, 0), (0, kv_p - kv), (0, 0))
            ).reshape(D, kv_p * hd)
            wo = jnp.pad(
                wo.reshape(kv, g, hd, D),
                ((0, kv_p - kv), (0, g_p - g), (0, 0), (0, 0)),
            ).reshape(kv_p * g_p * hd, D)
    h_p = kv_p * g_p

    q = (x @ wq).reshape(B, S, h_p, hd)
    if kv_override is None:
        k = (x @ wk).reshape(B, S, kv_p, hd)
        v = (x @ wv).reshape(B, S, kv_p, hd)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
    if rules is not None:
        q = rules.constrain(q, ("batch", None, "heads", None))
        k = rules.constrain(k, ("batch", None, "kv_heads", None))
        v = rules.constrain(v, ("batch", None, "kv_heads", None))
    Sk = k.shape[1]
    qg = q.reshape(B, S, kv_p, g_p, hd)
    if S >= 2 * Q_CHUNK and mask is None:
        # long sequences: query-block scan keeps the live score tile at
        # [B, KV, G, Q_CHUNK, Sk] instead of [.., S, Sk] (memory roofline)
        out = _chunked_attention(qg, k, v, positions, causal, sliding_window, hd)
    else:
        scores = jnp.einsum(
            "bqngd,bknd->bngqk", qg, k, preferred_element_type=jnp.float32
        )
        scores = scores / math.sqrt(hd)
        if causal:
            qpos = positions[:, :, None]  # [B,Sq,1]
            kpos = jnp.arange(Sk)[None, None, :]
            causal_mask = kpos <= qpos
            if sliding_window > 0:
                causal_mask &= kpos > qpos - sliding_window
            scores = jnp.where(causal_mask[:, None, None, :, :], scores, -1e30)
        if mask is not None:
            scores = jnp.where(mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", w, v)
    out = out.reshape(B, S, h_p * hd)
    return out @ wo


def _pad_plan(kv: int, g: int, ext: int) -> Tuple[int, int]:
    """Smallest (kv_p >= kv, g_p >= g) with kv_p*g_p % ext == 0."""
    best = None
    for kv_p in range(kv, kv + ext):
        for g_p in range(g, g + ext):
            if (kv_p * g_p) % ext == 0:
                cand = (kv_p * g_p, kv_p, g_p)
                if best is None or cand < best:
                    best = cand
    assert best is not None
    return best[1], best[2]


Q_CHUNK = 1024


def _chunked_attention(
    qg: jax.Array,  # [B, S, KV, G, hd]
    k: jax.Array,  # [B, Sk, KV, hd]
    v: jax.Array,
    positions: jax.Array,  # [B, S]
    causal: bool,
    sliding_window: int,
    hd: int,
) -> jax.Array:
    """Flash-style query-block scan (online softmax over full K per block)."""
    B, S, kv, g, _ = qg.shape
    Sk = k.shape[1]
    nq = S // Q_CHUNK
    assert S % Q_CHUNK == 0
    q_blocks = qg.reshape(B, nq, Q_CHUNK, kv, g, hd)
    pos_blocks = positions.reshape(B, nq, Q_CHUNK)
    kpos = jnp.arange(Sk)[None, None, :]

    def block(carry, inp):
        qb, pb = inp  # [B,Q,KV,G,hd], [B,Q]
        scores = jnp.einsum(
            "bqngd,bknd->bngqk", qb, k, preferred_element_type=jnp.float32
        ) / math.sqrt(hd)
        if causal:
            m = kpos <= pb[:, :, None]
            if sliding_window > 0:
                m &= kpos > pb[:, :, None] - sliding_window
            scores = jnp.where(m[:, None, None, :, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(qb.dtype)
        ob = jnp.einsum("bngqk,bknd->bqngd", w, v)
        return carry, ob

    _, out_blocks = jax.lax.scan(
        block, None, (jnp.moveaxis(q_blocks, 1, 0), jnp.moveaxis(pos_blocks, 1, 0))
    )
    return jnp.moveaxis(out_blocks, 0, 1).reshape(B, S, kv, g, hd)


def decode_attention(
    params: dict,
    x: jax.Array,
    pos: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cfg: ModelConfig,
    *,
    sliding_window: int = 0,
    new_token: bool = True,
    use_rope: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """One-token decode against a FLAT [B, S_max, KV*hd] cache, which it
    only reads.

    The cache keeps its head/dim axes merged so the joint ``kv*hd`` dim
    can shard across the whole model axis even when ``kv`` alone cannot
    (llama3's kv=8 on a 16-way axis: GSPMD splits the 16 ways as
    kv:8 x hd:2 after the in-kernel reshape).  Storing the cache
    [B, S, kv, hd] with kv unshardable forced GSPMD to re-gather the
    ENTIRE cache every decoded token (measured 2x34 GB/step on
    llama3-8b decode_32k — EXPERIMENTS.md §Perf iteration 6).

    ``pos`` is the scalar current position (same for the whole batch).
    With ``new_token`` (self-attention) the token's own k/v are computed
    here and attended beside the cache's positions ``< pos`` (the last
    ``sliding_window - 1`` of them when a window is set), in one softmax;
    the caller writes them at ``pos``.  The cache is not written here: a
    write inside the decode layer scan rewrites each layer's whole slice
    every token.  Without ``new_token`` (cross-attention) the cache's
    positions ``<= pos`` are attended.
    Returns (output [B,1,D], k_new, v_new), the last two [B,1,KV*hd] in
    the cache's dtype, or None without ``new_token``.
    """
    B = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kv
    S_max = k_cache.shape[1]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q = (x @ params["wq"]).reshape(B, 1, h, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
    qg = q.reshape(B, 1, kv, g, hd)

    if sliding_window > 0 and sliding_window < S_max:
        # sub-quadratic long-context decode: attend to the last W entries
        start = jnp.clip(pos - sliding_window + 1, 0, S_max - sliding_window)
        k_att = jax.lax.dynamic_slice(
            k_cache, (0, start, 0), (B, sliding_window, kv * hd)
        ).reshape(B, sliding_window, kv, hd)
        v_att = jax.lax.dynamic_slice(
            v_cache, (0, start, 0), (B, sliding_window, kv * hd)
        ).reshape(B, sliding_window, kv, hd)
        kpos = start + jnp.arange(sliding_window)
    else:
        k_att = k_cache.reshape(B, S_max, kv, hd)
        v_att = v_cache.reshape(B, S_max, kv, hd)
        kpos = jnp.arange(S_max)
    scores = jnp.einsum("bqngd,bknd->bngqk", qg, k_att, preferred_element_type=jnp.float32)
    valid = (kpos < pos) if new_token else (kpos <= pos)
    scores = jnp.where(valid[None, None, None, None, :], scores / math.sqrt(hd), -1e30)
    if not new_token:
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bngqk,bknd->bqngd", w, v_att).reshape(B, 1, h * hd)
        return out @ params["wo"], None, None
    k_new = (x @ params["wk"]).reshape(B, 1, kv, hd)
    if use_rope:
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    k_new = k_new.astype(k_cache.dtype)
    v_new = (x @ params["wv"]).reshape(B, 1, kv, hd).astype(v_cache.dtype)
    s_new = jnp.einsum("bqngd,bknd->bngqk", qg, k_new, preferred_element_type=jnp.float32)
    s_new = s_new / math.sqrt(hd)
    # one softmax over the cache and the new token, summed in two parts:
    # the scores are not concatenated, so a cache sharded on S stays so
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), s_new)
    p, p_new = jnp.exp(scores - m), jnp.exp(s_new - m)
    total = jnp.sum(p, axis=-1, keepdims=True) + p_new
    w, w_new = (p / total).astype(x.dtype), (p_new / total).astype(x.dtype)
    out = jnp.einsum("bngqk,bknd->bqngd", w, v_att, preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bngqk,bknd->bqngd", w_new, v_new, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(B, 1, h * hd)
    return out @ params["wo"], k_new.reshape(B, 1, kv * hd), v_new.reshape(B, 1, kv * hd)


# ---------------------------------------------------------------------------
# attention of one kind in a mixed stack (global or sliding window with a
# sink; partial rotary; v width apart from q/k; a value scale)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer: ``window`` 0 is global; a windowed
    layer has a learned sink logit per head."""

    name: str
    kv_heads: int
    rope_theta: float
    window: int


def attn_kind(cfg: ModelConfig, windowed: bool) -> AttnKind:
    if windowed:
        return AttnKind("attn_window", cfg.window_kv_heads, cfg.window_rope_theta, cfg.window)
    return AttnKind("attn_global", cfg.num_kv_heads, cfg.rope_theta, 0)


def kind_attention_schema(cfg: ModelConfig, kind: AttnKind) -> Dict[str, ParamDef]:
    d, h, kv = cfg.d_model, cfg.num_heads, kind.kv_heads
    hd, vd = cfg.resolved_head_dim, cfg.resolved_v_head_dim
    s = {
        "wq": ParamDef((d, h * hd), ("embed", "qkv")),
        "wk": ParamDef((d, kv * hd), ("embed", "qkv")),
        "wv": ParamDef((d, kv * vd), ("embed", "qkv")),
        "wo": ParamDef((h * vd, d), ("qkv", "embed")),
    }
    if kind.window:
        s["sink"] = ParamDef((h,), (None,))
    return s


def apply_partial_rope(x: jax.Array, positions: jax.Array, theta: float, rotary_dim: int) -> jax.Array:
    """Rotary over the leading ``rotary_dim`` dims of each head (their two
    halves paired, as :func:`apply_rope` pairs them); the rest pass."""
    if rotary_dim >= x.shape[-1]:
        return apply_rope(x, positions, theta)
    rot = apply_rope(x[..., :rotary_dim], positions, theta)
    return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)


def _kind_attend(p: dict, q, k, v, qpos, kpos, kind: AttnKind, cfg: ModelConfig, dtype):
    """q [B,Sq,h,hd], k [B,Sk,kv,hd], v [B,Sk,kv,vd]; causal, windowed when
    the kind is, with the sink's logit in the softmax's sum and its column
    dropped.  Returns the output projection [B, Sq, D]."""
    B, Sq, h, hd = q.shape
    kv = kind.kv_heads
    g = h // kv
    scores = jnp.einsum("bqngd,bknd->bngqk", q.reshape(B, Sq, kv, g, hd), k,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    seen = kpos[None, None, :] <= qpos[:, :, None]  # [B, Sq, Sk]
    if kind.window:
        seen &= kpos[None, None, :] > qpos[:, :, None] - kind.window
    scores = jnp.where(seen[:, None, None], scores, -1e30)
    if kind.window:
        sink = p["sink"].astype(jnp.float32).reshape(1, kv, g, 1, 1)
        top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
        e = jnp.exp(scores - top)
        w = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - top))
    else:
        w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", w.astype(dtype), v)
    out = out.reshape(B, Sq, h * v.shape[-1]) * jnp.asarray(cfg.attention_value_scale, dtype)
    return out @ p["wo"]


def _kind_qkv(p: dict, x, positions, cfg: ModelConfig, kind: AttnKind):
    B, S, _ = x.shape
    h, hd, vd = cfg.num_heads, cfg.resolved_head_dim, cfg.resolved_v_head_dim
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    k = (x @ p["wk"]).reshape(B, S, kind.kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, kind.kv_heads, vd)
    q = apply_partial_rope(q, positions, kind.rope_theta, cfg.rotary_dim)
    k = apply_partial_rope(k, positions, kind.rope_theta, cfg.rotary_dim)
    return q, k, v


def kind_attention(p: dict, x, positions, cfg: ModelConfig, kind: AttnKind):
    """Full-sequence attention of one kind; returns (output, k, v) with k
    rotated, for a prefill's caches."""
    with jax.named_scope(kind.name):
        q, k, v = _kind_qkv(p, x, positions, cfg, kind)
        out = _kind_attend(p, q, k, v, positions, jnp.arange(x.shape[1]), kind, cfg, x.dtype)
    return out, k, v


def kind_decode_attention(p: dict, x, pos, k_cache, v_cache, cfg: ModelConfig, kind: AttnKind):
    """One token against FLAT [B, S_max, kv*hd] / [B, S_max, kv*vd] caches of
    this kind; the windowed kind reads the last ``window`` positions of its
    full-length cache through the mask.  Returns (output, caches)."""
    B = x.shape[0]
    S_max = k_cache.shape[1]
    kv, hd, vd = kind.kv_heads, cfg.resolved_head_dim, cfg.resolved_v_head_dim
    with jax.named_scope(kind.name):
        positions = jnp.full((B, 1), pos, dtype=jnp.int32)
        q, k, v = _kind_qkv(p, x, positions, cfg, kind)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.reshape(B, 1, kv * hd).astype(k_cache.dtype), (0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.reshape(B, 1, kv * vd).astype(v_cache.dtype), (0, pos, 0))
        out = _kind_attend(p, q, k_cache.reshape(B, S_max, kv, hd),
                           v_cache.reshape(B, S_max, kv, vd), positions, jnp.arange(S_max),
                           kind, cfg, x.dtype)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def ffn_schema(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w_gate": ParamDef((d, f), ("embed", "mlp")),
        "w_up": ParamDef((d, f), ("embed", "mlp")),
        "w_down": ParamDef((f, d), ("mlp", "embed")),
    }


def swiglu_ffn(params: dict, x: jax.Array, rules: Optional[Rules] = None) -> jax.Array:
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    if rules is not None:
        h = rules.constrain(h, ("batch", None, "mlp"))
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# embeddings / heads
# ---------------------------------------------------------------------------


def embed_schema(cfg: ModelConfig) -> ParamDef:
    # vocab-sharded ONLY: FSDP-sharding the D axis too makes the token
    # gather un-partitionable (SPMD "involuntary full rematerialization",
    # ~30 GB/device of extra all-reduce on kimi-k2 — measured, see
    # EXPERIMENTS.md §Perf).
    return ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", None), scale=0.02)


def lm_head_schema(cfg: ModelConfig) -> ParamDef:
    return ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))


def logits_fn(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"], preferred_element_type=jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"], preferred_element_type=jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token CE; logits [B,S,V] (f32), labels [B,S]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
