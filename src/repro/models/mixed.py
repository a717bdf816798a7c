"""A stack of layers of several kinds (MiMo-V2-Flash): global or
sliding-window attention by ``cfg.layer_pattern``, a dense SwiGLU or held
routed experts by ``cfg.moe_pattern``.

Each kind's parameters are stacked apart under ``params["layers"][kind]``;
the stack is walked in the published order, one ``lax.scan`` per run of
consecutive layers of one kind, each indexing its kind's stack (no copy
of a run's weights).  Decode keeps two kinds of K/V state side by side:
the global layers' caches and the windowed layers' full-length caches,
K and V each at its own width.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.layers import (
    ParamDef,
    attn_kind,
    embed_schema,
    ffn_schema,
    kind_attention,
    kind_attention_schema,
    kind_decode_attention,
    lm_head_schema,
    logits_fn,
    rms_norm,
    stacked,
    swiglu_ffn,
)
from repro.sharding.rules import Rules


def kind_name(windowed: int, experts: int) -> str:
    return ("window" if windowed else "global") + ("_experts" if experts else "_dense")


def layer_kinds(cfg: ModelConfig) -> List[str]:
    return [kind_name(w, e) for w, e in zip(cfg.layer_pattern, cfg.moe_pattern)]


def runs(cfg: ModelConfig) -> List[Tuple[str, int, int, int]]:
    """(kind, first index in the kind's stack, length, first layer) of each
    run of consecutive layers of one kind, in the published order."""
    out: List[List] = []
    seen: Dict[str, int] = {}
    for i, k in enumerate(layer_kinds(cfg)):
        if out and out[-1][0] == k:
            out[-1][2] += 1
        else:
            out.append([k, seen.get(k, 0), 1, i])
        seen[k] = seen.get(k, 0) + 1
    return [tuple(r) for r in out]


def model_schema(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    kinds = layer_kinds(cfg)
    layers = {}
    for k in sorted(set(kinds)):
        one = {
            "attn": kind_attention_schema(cfg, attn_kind(cfg, k.startswith("window"))),
            "norm_attn": ParamDef((d,), (None,), init="ones"),
            "norm_ffn": ParamDef((d,), (None,), init="ones"),
        }
        if k.endswith("_experts"):
            one["moe"] = moe_mod.held_moe_schema(cfg)
        else:
            one["ffn"] = ffn_schema(cfg)
        layers[k] = jax.tree.map(lambda p, n=kinds.count(k): stacked(p, n), one,
                                 is_leaf=lambda x: isinstance(x, ParamDef))
    s: Dict[str, Any] = {
        "embed": embed_schema(cfg),
        "layers": layers,
        "final_norm": ParamDef((d,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = lm_head_schema(cfg)
    return s


def _ffn(lp: dict, x, cfg: ModelConfig, kind: str, rules: Optional[Rules]):
    """The layer's second half: (x + ffn, routing counters [3] or None)."""
    h = rms_norm(x, lp["norm_ffn"], cfg.norm_eps)
    if kind.endswith("_experts"):
        y, counters = moe_mod.held_moe_ffn(lp["moe"], h, cfg)
        return x + y, counters
    return x + swiglu_ffn(lp["ffn"], h, rules), None


def _walk(params: dict, cfg: ModelConfig, carry, step):
    """Calls ``step(carry, layer_params, kind, slot) -> (carry, out)`` layer
    by layer in the published order, ``slot`` the layer's index among the
    layers of its attention kind (its cache's); returns the carry and each
    run's stacked outs, in order."""
    slots = _cache_slots(cfg)
    outs = []
    for kind, first, n, layer in runs(cfg):
        stack = params["layers"][kind]

        def body(c, idx, stack=stack, kind=kind):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, idx[0], keepdims=False),
                              stack)
            return step(c, lp, kind, idx[1])

        if cfg.remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        idx = jnp.stack([jnp.arange(first, first + n), slots[layer] + jnp.arange(n)], axis=1)
        carry, out = jax.lax.scan(body, carry, idx.astype(jnp.int32),
                                  unroll=min(cfg.scan_unroll, n))
        outs.append((kind, out))
    return carry, outs


def _cache_slots(cfg: ModelConfig) -> List[int]:
    """Each layer's index among the layers of its attention kind."""
    seen = [0, 0]
    out = []
    for w in cfg.layer_pattern:
        out.append(seen[w])
        seen[w] += 1
    return out


def _routed(outs) -> jax.Array:
    """Routing counters [n_routed_layers, 3], in layer order."""
    rows = [c for kind, c in outs if kind.endswith("_experts")]
    if not rows:
        return jnp.zeros((0, 3), jnp.int32)
    return jnp.concatenate(rows, axis=0)


def forward(params: dict, x: jax.Array, positions: jax.Array, cfg: ModelConfig,
            rules: Optional[Rules]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Trunk over embedded inputs x [B, S, D] -> (hidden, aux); aux holds
    ``routing``, the counters of each expert layer."""

    def step(h, lp, kind, _):
        a, _, _ = kind_attention(lp["attn"], rms_norm(h, lp["norm_attn"], cfg.norm_eps),
                                 positions, cfg, attn_kind(cfg, kind.startswith("window")))
        h, counters = _ffn(lp, h + a, cfg, kind, rules)
        return h, counters

    x, outs = _walk(params, cfg, x, step)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    zero = jnp.zeros((), jnp.float32)
    return x, {"load_balance": zero, "router_z": zero, "routing": _routed(outs)}


# ---------------------------------------------------------------------------
# serving: two kinds of K/V state side by side
# ---------------------------------------------------------------------------


class MixedDecodeState(NamedTuple):
    k_cache: jax.Array  # global layers: FLAT [Lg, B, S_max, kv*hd]
    v_cache: jax.Array  # [Lg, B, S_max, kv*vd]
    window_k: jax.Array  # windowed layers: [Lw, B, S_max, kv_w*hd]
    window_v: jax.Array  # [Lw, B, S_max, kv_w*vd]
    pos: jax.Array  # scalar int32: next position to write


def _cache_widths(cfg: ModelConfig, windowed: bool) -> Tuple[int, int]:
    kv = cfg.window_kv_heads if windowed else cfg.num_kv_heads
    return kv * cfg.resolved_head_dim, kv * cfg.resolved_v_head_dim


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> MixedDecodeState:
    n_window = sum(cfg.layer_pattern)
    n_global = cfg.num_layers - n_window
    gk, gv = _cache_widths(cfg, False)
    wk, wv = _cache_widths(cfg, True)
    z = lambda L, w: jnp.zeros((L, batch, cache_len, w), dtype)
    return MixedDecodeState(z(n_global, gk), z(n_global, gv), z(n_window, wk), z(n_window, wv),
                            jnp.zeros((), jnp.int32))


def prefill(params: dict, x: jax.Array, positions: jax.Array, cfg: ModelConfig,
            rules: Optional[Rules]) -> Tuple[jax.Array, MixedDecodeState]:
    """Last-position logits and the caches of every layer, at S positions."""
    B, S, _ = x.shape
    dtype = jnp.dtype(cfg.dtype)

    def step(h, lp, kind, _):
        a, k, v = kind_attention(lp["attn"], rms_norm(h, lp["norm_attn"], cfg.norm_eps),
                                 positions, cfg, attn_kind(cfg, kind.startswith("window")))
        h, _ = _ffn(lp, h + a, cfg, kind, rules)
        return h, (k.reshape(B, S, -1).astype(dtype), v.reshape(B, S, -1).astype(dtype))

    h, outs = _walk(params, cfg, x, step)
    # runs come in layer order, so each kind's caches do too
    caches = []
    for windowed, prefix in ((False, "global"), (True, "window")):
        widths = _cache_widths(cfg, windowed)
        for j in range(2):
            xs = [kv[j] for kind, kv in outs if kind.startswith(prefix)]
            caches.append(jnp.concatenate(xs, axis=0) if xs
                          else jnp.zeros((0, B, S, widths[j]), dtype))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h[:, -1:, :], cfg)[:, 0, :]
    return logits, MixedDecodeState(*caches, jnp.array(S, jnp.int32))


def decode_step(params: dict, state: MixedDecodeState, x: jax.Array, cfg: ModelConfig,
                rules: Optional[Rules]) -> Tuple[jax.Array, MixedDecodeState]:
    """One token per row, x [B, 1, D] embedded -> (logits [B, V], state)."""
    pos = state.pos

    def step(carry, lp, kind, slot):
        h, caches = carry
        windowed = kind.startswith("window")
        kc, vc = caches[2:] if windowed else caches[:2]
        k1 = jax.lax.dynamic_index_in_dim(kc, slot, keepdims=False)
        v1 = jax.lax.dynamic_index_in_dim(vc, slot, keepdims=False)
        a, k1, v1 = kind_decode_attention(lp["attn"], rms_norm(h, lp["norm_attn"], cfg.norm_eps),
                                          pos, k1, v1, cfg, attn_kind(cfg, windowed))
        kc = jax.lax.dynamic_update_index_in_dim(kc, k1, slot, 0)
        vc = jax.lax.dynamic_update_index_in_dim(vc, v1, slot, 0)
        caches = caches[:2] + (kc, vc) if windowed else (kc, vc) + caches[2:]
        h, _ = _ffn(lp, h + a, cfg, kind, rules)
        return (h, caches), None

    (h, caches), _ = _walk(params, cfg, (x, tuple(state[:4])), step)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, h, cfg)[:, 0, :]
    return logits, MixedDecodeState(*caches, pos + 1)
