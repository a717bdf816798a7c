"""Whisper-style encoder-decoder backbone (audio family).

The mel-spectrogram + conv feature extractor is STUBBED per the brief:
inputs are precomputed frame embeddings ``[B, S_enc, D]``.  The encoder
is a non-causal transformer; the decoder adds cross-attention to the
encoder output.  Decode = one token against a self-attention cache of
``seq_len`` plus a fixed-length cross-attention cache.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    ParamDef,
    apply_rope,
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
    swiglu_ffn,
)
from repro.sharding.rules import Rules


def _norm(cfg: ModelConfig) -> ParamDef:
    return ParamDef((cfg.d_model,), (None,), init="ones")


def encoder_layer_schema(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "attn": attention_schema(cfg),
        "norm_attn": _norm(cfg),
        "ffn": ffn_schema(cfg),
        "norm_ffn": _norm(cfg),
    }


def decoder_layer_schema(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "self_attn": attention_schema(cfg),
        "norm_self": _norm(cfg),
        "cross_attn": attention_schema(cfg),
        "norm_cross": _norm(cfg),
        "ffn": ffn_schema(cfg),
        "norm_ffn": _norm(cfg),
    }


def model_schema(cfg: ModelConfig) -> Dict[str, Any]:
    st = lambda sch, L: jax.tree.map(
        lambda p: stacked(p, L), sch, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    s: Dict[str, Any] = {
        "embed": embed_schema(cfg),
        "enc_layers": st(encoder_layer_schema(cfg), cfg.encoder_layers),
        "enc_norm": _norm(cfg),
        "dec_layers": st(decoder_layer_schema(cfg), cfg.num_layers),
        "final_norm": _norm(cfg),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = lm_head_schema(cfg)
    return s


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def encode(
    params: dict, frames: jax.Array, cfg: ModelConfig, rules: Optional[Rules] = None
) -> jax.Array:
    """frames: [B, S_enc, D] (stub embeddings) -> encoder hidden."""
    B, S, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = frames.astype(jnp.dtype(cfg.dtype))
    if rules is not None:
        x = rules.constrain(x, ("batch", None, None))

    def body(h, lp):
        hn = rms_norm(h, lp["norm_attn"], cfg.norm_eps)
        h = h + multihead_attention(
            lp["attn"], hn, positions, cfg, causal=False, rules=rules
        )
        h = h + swiglu_ffn(lp["ffn"], rms_norm(h, lp["norm_ffn"], cfg.norm_eps), rules)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    x, _ = jax.lax.scan(body, x, params["enc_layers"], unroll=cfg.scan_unroll)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder (training / teacher-forced)
# ---------------------------------------------------------------------------


def decode_train(
    params: dict,
    tokens: jax.Array,
    enc_out: jax.Array,
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> jax.Array:
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    Se = enc_out.shape[1]

    def body(h, lp):
        hn = rms_norm(h, lp["norm_self"], cfg.norm_eps)
        h = h + multihead_attention(lp["self_attn"], hn, positions, cfg, rules=rules)
        hn = rms_norm(h, lp["norm_cross"], cfg.norm_eps)
        ck = (enc_out @ lp["cross_attn"]["wk"]).reshape(B, Se, kv, hd)
        cv = (enc_out @ lp["cross_attn"]["wv"]).reshape(B, Se, kv, hd)
        h = h + multihead_attention(
            lp["cross_attn"], hn, positions, cfg,
            kv_override=(ck, cv), causal=False, use_rope=False, rules=rules,
        )
        h = h + swiglu_ffn(lp["ffn"], rms_norm(h, lp["norm_ffn"], cfg.norm_eps), rules)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    x, _ = jax.lax.scan(body, x, params["dec_layers"], unroll=cfg.scan_unroll)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def lm_loss(
    params: dict,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    enc_out = encode(params, batch["frames"], cfg, rules)
    h = decode_train(params, batch["tokens"], enc_out, cfg, rules)
    logits = logits_fn(params, h[:, :-1, :], cfg)
    if rules is not None:
        logits = rules.constrain(logits, ("batch", None, "vocab"))
    loss = cross_entropy(logits, batch["tokens"][:, 1:])
    return loss, {"lm_loss": loss}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


class EncDecState(NamedTuple):
    self_k: jax.Array  # FLAT [L, B, S_max, KV*hd] (see layers.decode_attention)
    self_v: jax.Array
    cross_k: jax.Array  # FLAT [L, B, S_enc, KV*hd]
    cross_v: jax.Array
    pos: jax.Array


def init_decode_state(
    cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16
) -> EncDecState:
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    Se = cfg.encoder_seq
    return EncDecState(
        jnp.zeros((L, batch, cache_len, kv * hd), dtype),
        jnp.zeros((L, batch, cache_len, kv * hd), dtype),
        jnp.zeros((L, batch, Se, kv * hd), dtype),
        jnp.zeros((L, batch, Se, kv * hd), dtype),
        jnp.zeros((), jnp.int32),
    )


def decode_state_specs(cfg: ModelConfig, rules: Rules, batch: int, cache_len: int):
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    if batch >= rules.data_extent and batch % rules.data_extent == 0:
        dims = ("layers", "batch", "cache_seq", None)
    else:
        dims = ("layers", None, "kv_seq", "qkv")
    self_spec = rules.spec((L, batch, cache_len, kv * hd), dims)
    cross_spec = rules.spec(
        (L, batch, cfg.encoder_seq, kv * hd), ("layers", "batch", "cache_seq", None)
    )
    from jax.sharding import PartitionSpec as P

    return EncDecState(self_spec, self_spec, cross_spec, cross_spec, P())


def decode_step(
    params: dict,
    state: EncDecState,
    token: jax.Array,
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
    sliding_window: int = 0,
) -> Tuple[jax.Array, EncDecState]:
    B = token.shape[0]
    x = params["embed"][token].astype(jnp.dtype(cfg.dtype))
    pos = state.pos
    h_kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def body(h, inputs):
        lp, sk, sv, ck, cv = inputs
        hn = rms_norm(h, lp["norm_self"], cfg.norm_eps)
        a, k_new, v_new = decode_attention(
            lp["self_attn"], hn, pos, sk, sv, cfg, sliding_window=sliding_window
        )
        sk = jax.lax.dynamic_update_slice(sk, k_new, (0, pos, 0))
        sv = jax.lax.dynamic_update_slice(sv, v_new, (0, pos, 0))
        h = h + a
        hn = rms_norm(h, lp["norm_cross"], cfg.norm_eps)
        a, _, _ = decode_attention(
            lp["cross_attn"], hn, jnp.array(cfg.encoder_seq - 1, jnp.int32),
            ck, cv, cfg, new_token=False, use_rope=False,
        )
        h = h + a
        h = h + swiglu_ffn(lp["ffn"], rms_norm(h, lp["norm_ffn"], cfg.norm_eps), rules)
        return h, (sk, sv)

    h, (sk, sv) = jax.lax.scan(
        body,
        x,
        (params["dec_layers"], state.self_k, state.self_v, state.cross_k, state.cross_v),
        unroll=cfg.scan_unroll,
    )
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h, cfg)[:, 0, :]
    return logits, EncDecState(sk, sv, state.cross_k, state.cross_v, pos + 1)


def prefill(
    params: dict,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    rules: Optional[Rules] = None,
) -> Tuple[jax.Array, EncDecState]:
    """Encode audio frames; build cross caches; teacher-force the prompt."""
    frames = batch["frames"]
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = encode(params, frames, cfg, rules)
    Se = enc_out.shape[1]
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))

    def body(h, lp):
        hn = rms_norm(h, lp["norm_self"], cfg.norm_eps)
        sk = (hn @ lp["self_attn"]["wk"]).reshape(B, S, kv, hd)
        sv = (hn @ lp["self_attn"]["wv"]).reshape(B, S, kv, hd)
        sk = apply_rope(sk, positions, cfg.rope_theta)
        h = h + multihead_attention(lp["self_attn"], hn, positions, cfg, rules=rules)
        hn = rms_norm(h, lp["norm_cross"], cfg.norm_eps)
        ck = (enc_out @ lp["cross_attn"]["wk"]).reshape(B, Se, kv, hd)
        cv = (enc_out @ lp["cross_attn"]["wv"]).reshape(B, Se, kv, hd)
        h = h + multihead_attention(
            lp["cross_attn"], hn, positions, cfg,
            kv_override=(ck, cv), causal=False, use_rope=False, rules=rules,
        )
        h = h + swiglu_ffn(lp["ffn"], rms_norm(h, lp["norm_ffn"], cfg.norm_eps), rules)
        # caches stored FLAT [B, S, kv*hd] (see layers.decode_attention)
        return h, (
            sk.reshape(B, S, kv * hd),
            sv.reshape(B, S, kv * hd),
            ck.reshape(B, Se, kv * hd),
            cv.reshape(B, Se, kv * hd),
        )

    h, (sks, svs, cks, cvs) = jax.lax.scan(body, x, params["dec_layers"], unroll=cfg.scan_unroll)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h[:, -1:, :], cfg)[:, 0, :]
    dt = jnp.dtype(cfg.dtype)
    return logits, EncDecState(
        sks.astype(dt), svs.astype(dt), cks.astype(dt), cvs.astype(dt),
        jnp.array(S, jnp.int32),
    )
