"""Decoder-only transformer covering the dense / moe / ssm / hybrid / vlm
families, as pure functions over schema-driven parameter trees.

Depth is handled with ``jax.lax.scan`` over layer-stacked parameters
(small HLO, fast CPU compiles, remat-friendly); training wraps the layer
body in ``jax.checkpoint``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mixed
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamDef,
    attention_schema,
    cross_entropy,
    decode_attention,
    embed_schema,
    ffn_schema,
    lm_head_schema,
    logits_fn,
    multihead_attention,
    rms_norm,
    stacked,
)
from repro.sharding.rules import Rules

AUX_LB_COEF = 0.01
AUX_Z_COEF = 0.001


# ---------------------------------------------------------------------------
# schemas
# ---------------------------------------------------------------------------


def layer_schema(cfg: ModelConfig) -> Dict[str, Any]:
    """Schema of ONE layer (unstacked)."""
    d = cfg.d_model
    norm = lambda: ParamDef((d,), (None,), init="ones")
    s: Dict[str, Any] = {}
    if cfg.family in ("dense", "vlm", "moe", "hybrid"):
        s["attn"] = attention_schema(cfg)
        s["norm_attn"] = norm()
    if cfg.family in ("ssm", "hybrid"):
        s["ssm"] = ssm_mod.ssm_schema(cfg)
        s["norm_ssm"] = norm()
    if cfg.family == "hybrid":
        # per-branch output norms, Hymba-style parallel-head fusion
        s["norm_attn_out"] = norm()
        s["norm_ssm_out"] = norm()
    if cfg.family == "moe":
        s["moe"] = moe_mod.moe_schema(cfg)
        s["norm_ffn"] = norm()
    elif cfg.family in ("dense", "vlm", "hybrid"):
        s["ffn"] = ffn_schema(cfg)
        s["norm_ffn"] = norm()
    return s


def model_schema(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.layer_pattern:
        return mixed.model_schema(cfg)
    one = layer_schema(cfg)
    s: Dict[str, Any] = {
        "embed": embed_schema(cfg),
        "layers": jax.tree.map(
            lambda p: stacked(p, cfg.num_layers),
            one,
            is_leaf=lambda x: isinstance(x, ParamDef),
        ),
        "final_norm": ParamDef((cfg.d_model,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = lm_head_schema(cfg)
    return s


# ---------------------------------------------------------------------------
# layer body (full-sequence)
# ---------------------------------------------------------------------------


def layer_forward(
    lp: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    rules: Optional[Rules],
    sliding_window: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    aux = {"load_balance": jnp.zeros((), jnp.float32), "router_z": jnp.zeros((), jnp.float32)}
    if cfg.family == "ssm":
        x = x + ssm_mod.ssd_scan(lp["ssm"], rms_norm(x, lp["norm_ssm"], cfg.norm_eps), cfg, rules)
        return x, aux
    if cfg.family == "hybrid":
        h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
        a = multihead_attention(
            lp["attn"], h, positions, cfg, rules=rules, sliding_window=sliding_window
        )
        s = ssm_mod.ssd_scan(lp["ssm"], rms_norm(x, lp["norm_ssm"], cfg.norm_eps), cfg, rules)
        fused = 0.5 * (
            rms_norm(a, lp["norm_attn_out"], cfg.norm_eps)
            + rms_norm(s, lp["norm_ssm_out"], cfg.norm_eps)
        )
        x = x + fused
        x = x + _ffn(lp, x, cfg, rules)
        return x, aux
    # dense / vlm / moe
    h = rms_norm(x, lp["norm_attn"], cfg.norm_eps)
    x = x + multihead_attention(
        lp["attn"], h, positions, cfg, rules=rules, sliding_window=sliding_window
    )
    if cfg.family == "moe":
        y, moe_aux = moe_mod.moe_ffn(
            lp["moe"], rms_norm(x, lp["norm_ffn"], cfg.norm_eps), cfg, rules
        )
        x = x + y
        aux = moe_aux
    else:
        x = x + _ffn(lp, x, cfg, rules)
    return x, aux


def _ffn(lp: dict, x: jax.Array, cfg: ModelConfig, rules: Optional[Rules]) -> jax.Array:
    from repro.models.layers import swiglu_ffn

    return swiglu_ffn(lp["ffn"], rms_norm(x, lp["norm_ffn"], cfg.norm_eps), rules)


# ---------------------------------------------------------------------------
# full forward (train / prefill trunk)
# ---------------------------------------------------------------------------


def forward(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    rules: Optional[Rules],
    sliding_window: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Trunk over embedded inputs x [B,S,D] -> (hidden, aux)."""
    if cfg.layer_pattern:
        return mixed.forward(params, x, positions, cfg, rules)

    def body(carry, lp):
        h, lb, zl = carry
        h, aux = layer_forward(lp, h, positions, cfg, rules, sliding_window)
        return (h, lb + aux["load_balance"], zl + aux["router_z"]), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    (x, lb, zl), _ = jax.lax.scan(
        body,
        (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        params["layers"],
        unroll=cfg.scan_unroll,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    denom = max(cfg.num_layers, 1)
    return x, {"load_balance": lb / denom, "router_z": zl / denom}


def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return params["embed"][tokens].astype(jnp.dtype(cfg.dtype))


def lm_loss(
    params: dict,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token LM loss for dense/moe/ssm/hybrid (+ vlm with patches)."""
    tokens = batch["tokens"]  # [B, S_text]
    B = tokens.shape[0]
    x = embed_tokens(params, tokens, cfg)
    prefix = 0
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype)  # [B, P, D]
        prefix = patches.shape[1]
        x = jnp.concatenate([patches, x], axis=1)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if rules is not None:
        x = rules.constrain(x, ("batch", None, None))
    h, aux = forward(params, x, positions, cfg, rules)
    # predict text tokens; positions prefix..S-2 predict tokens 1..
    h_txt = h[:, prefix:, :]
    logits = logits_fn(params, h_txt[:, :-1, :], cfg)
    if rules is not None:
        logits = rules.constrain(logits, ("batch", None, "vocab"))
    loss = cross_entropy(logits, tokens[:, 1:])
    total = loss + AUX_LB_COEF * aux["load_balance"] + AUX_Z_COEF * aux["router_z"]
    return total, {"lm_loss": loss, **aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


class DecodeState(NamedTuple):
    k_cache: Optional[jax.Array]  # FLAT [L, B, S_max, KV*hd] (see layers.decode_attention)
    v_cache: Optional[jax.Array]
    ssm_state: Optional[jax.Array]  # [L, B, H, hd, N]
    pos: jax.Array  # scalar int32: next position to write


def init_decode_state(
    cfg: ModelConfig,
    batch: int,
    cache_len: int,
    dtype=jnp.bfloat16,
) -> DecodeState:
    if cfg.layer_pattern:
        return mixed.init_decode_state(cfg, batch, cache_len, dtype)
    L = cfg.num_layers
    kc = vc = st = None
    if cfg.family in ("dense", "vlm", "moe", "hybrid", "audio"):
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        kc = jnp.zeros((L, batch, cache_len, kv * hd), dtype)
        vc = jnp.zeros((L, batch, cache_len, kv * hd), dtype)
    if cfg.family in ("ssm", "hybrid"):
        st = jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    return DecodeState(kc, vc, st, jnp.zeros((), jnp.int32))


def decode_state_specs(cfg: ModelConfig, rules: Rules, batch: int, cache_len: int):
    """PartitionSpecs matching init_decode_state's tree."""
    if cfg.layer_pattern:
        raise NotImplementedError("no sharded decode state for a stack of several layer kinds")
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    L = cfg.num_layers
    kc_spec = vc_spec = st_spec = None
    if cfg.family in ("dense", "vlm", "moe", "hybrid", "audio"):
        # flat kv*hd trailing dim; the SEQUENCE dim shards on the model
        # axis (batch on data) — head-dim sharding would force per-token
        # cache re-gathers for GQA (see rules.py 'cache_seq')
        if batch >= rules.data_extent and batch % rules.data_extent == 0:
            dims = ("layers", "batch", "cache_seq", None)
        else:  # long-context single-sequence: shard the cache on sequence
            dims = ("layers", None, "kv_seq", "qkv")
        kc_spec = rules.spec((L, batch, cache_len, kv * hd), dims)
        vc_spec = kc_spec
    if cfg.family in ("ssm", "hybrid"):
        st_spec = rules.spec(
            (L, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            ("layers", "batch", "ssm_inner", None, None),
        )
    from jax.sharding import PartitionSpec as P

    return DecodeState(kc_spec, vc_spec, st_spec, P())


def decode_step(
    params: dict,
    state: DecodeState,
    token: jax.Array,  # [B, 1] int32
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
    sliding_window: int = 0,
) -> Tuple[jax.Array, DecodeState]:
    """One decode step: returns (logits [B, V], new state)."""
    B = token.shape[0]
    x = embed_tokens(params, token, cfg)  # [B,1,D]
    if cfg.layer_pattern:
        return mixed.decode_step(params, state, x, cfg, rules)
    pos = state.pos

    # The scan reads each layer's cache and hands back only the new token's
    # k/v; one write after it stores every layer's at ``pos``.  Engine
    # donates the state, so that write lands in place.
    def body(h, inputs):
        lp, kc, vc, st = inputs
        k_new, v_new, new_st = kc, vc, st  # the dummies where a field is absent
        if cfg.family == "ssm":
            y, new_st = ssm_mod.ssd_decode_step(
                lp["ssm"], rms_norm(h, lp["norm_ssm"], cfg.norm_eps), st, cfg
            )
            h = h + y
            return h, (k_new, v_new, new_st)
        if cfg.family == "hybrid":
            hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
            a, k_new, v_new = decode_attention(
                lp["attn"], hn, pos, kc, vc, cfg, sliding_window=sliding_window
            )
            s, new_st = ssm_mod.ssd_decode_step(
                lp["ssm"], rms_norm(h, lp["norm_ssm"], cfg.norm_eps), st, cfg
            )
            fused = 0.5 * (
                rms_norm(a, lp["norm_attn_out"], cfg.norm_eps)
                + rms_norm(s, lp["norm_ssm_out"], cfg.norm_eps)
            )
            h = h + fused
            h = h + _ffn(lp, h, cfg, rules)
            return h, (k_new, v_new, new_st)
        hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
        a, k_new, v_new = decode_attention(
            lp["attn"], hn, pos, kc, vc, cfg, sliding_window=sliding_window
        )
        h = h + a
        if cfg.family == "moe":
            y, _ = moe_mod.moe_ffn(
                lp["moe"], rms_norm(h, lp["norm_ffn"], cfg.norm_eps), cfg, rules
            )
            h = h + y
        else:
            h = h + _ffn(lp, h, cfg, rules)
        return h, (k_new, v_new, new_st)

    dummy = jnp.zeros((cfg.num_layers, 0), jnp.float32)
    xs = (
        params["layers"],
        state.k_cache if state.k_cache is not None else dummy,
        state.v_cache if state.v_cache is not None else dummy,
        state.ssm_state if state.ssm_state is not None else dummy,
    )
    h, (k_new, v_new, st) = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h, cfg)[:, 0, :]
    kc = vc = None
    if state.k_cache is not None:  # k_new, v_new: [L, B, 1, kv*hd]
        kc = jax.lax.dynamic_update_slice(state.k_cache, k_new, (0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(state.v_cache, v_new, (0, 0, pos, 0))
    new_state = DecodeState(
        kc, vc, st if state.ssm_state is not None else None, pos + 1
    )
    return logits, new_state


def prefill(
    params: dict,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> Tuple[jax.Array, DecodeState]:
    """Prefill: full forward producing last-token logits + decode caches.

    Uses a per-layer pass that also emits this layer's K/V for the cache
    (attention archs) or the final SSD state (ssm/hybrid).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if rules is not None:
        x = rules.constrain(x, ("batch", None, None))
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    if cfg.layer_pattern:
        return mixed.prefill(params, x, positions, cfg, rules)
    if cfg.family in ("ssm", "hybrid"):
        return _prefill_with_state(params, x, positions, cfg, rules)

    def body(h, lp):
        hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
        from repro.models.layers import apply_rope

        k = (hn @ lp["attn"]["wk"]).reshape(B, S, kv, hd)
        v = hn @ lp["attn"]["wv"]  # flat [B, S, kv*hd]
        kc = apply_rope(k, positions, cfg.rope_theta).reshape(B, S, kv * hd)
        h, _ = layer_forward(lp, h, positions, cfg, rules)
        return h, (kc, v)

    h, (kcs, vcs) = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h[:, -1:, :], cfg)[:, 0, :]
    state = DecodeState(
        kcs.astype(jnp.dtype(cfg.dtype)),
        vcs.astype(jnp.dtype(cfg.dtype)),
        None,
        jnp.array(S, jnp.int32),
    )
    return logits, state


def _prefill_with_state(params, x, positions, cfg, rules):
    """Prefill for ssm/hybrid: emit per-layer final SSD state (+KV)."""
    B, S, _ = x.shape
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def body(h, lp):
        kc = vc = jnp.zeros((0,), jnp.float32)
        if cfg.family == "hybrid":
            hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
            from repro.models.layers import apply_rope

            k = (hn @ lp["attn"]["wk"]).reshape(B, S, kv, hd)
            v = hn @ lp["attn"]["wv"]  # flat [B, S, kv*hd]
            kc = apply_rope(k, positions, cfg.rope_theta).reshape(B, S, kv * hd)
            vc = v
        ssm_in = rms_norm(h, lp["norm_ssm"], cfg.norm_eps)
        y_ssm, st = ssm_mod.ssd_scan_with_state(lp["ssm"], ssm_in, cfg, rules)
        if cfg.family == "ssm":
            h = h + y_ssm
        else:
            a = multihead_attention(lp["attn"], rms_norm(h, lp["norm_attn"], cfg.norm_eps),
                                    positions, cfg, rules=rules)
            fused = 0.5 * (
                rms_norm(a, lp["norm_attn_out"], cfg.norm_eps)
                + rms_norm(y_ssm, lp["norm_ssm_out"], cfg.norm_eps)
            )
            h = h + fused
            h = h + _ffn(lp, h, cfg, rules)
        return h, (kc, vc, st)

    h, (kcs, vcs, sts) = jax.lax.scan(body, x, params["layers"], unroll=cfg.scan_unroll)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h[:, -1:, :], cfg)[:, 0, :]
    state = DecodeState(
        kcs.astype(jnp.dtype(cfg.dtype)) if cfg.family == "hybrid" else None,
        vcs.astype(jnp.dtype(cfg.dtype)) if cfg.family == "hybrid" else None,
        sts,
        jnp.array(S, jnp.int32),
    )
    return logits, state
