"""mimo_v2_flash: MiMo-V2-Flash's decoder, as one chip's share of an
expert-parallel deployment.

Pre-norm RMSNorm.  Each layer attends globally or over a sliding window
(``layer_pattern`` 0 or 1) and has a dense SwiGLU or routed experts
(``moe_pattern`` 0 or 1):

- attention: ``q = y Wq`` [h x hd], ``k = y Wk`` [kv x hd], ``v = y Wv``
  [kv x vd], kv and the rotary base by kind; rotary over the leading
  ``int(hd * partial_rotary_factor)`` dims of each q/k head, the two
  halves paired; scores ``q.k / sqrt(hd)``, causal; a windowed query i
  sees keys ``i - window < j <= i`` and its softmax carries one more
  logit per head, the sink, whose column is dropped; the output is
  ``attention_value_scale * sum_j w_ij v_j`` through ``Wo``;
- routed experts: ``s = sigmoid(y Wr)`` over all ``num_experts``; the
  chosen are the top ``experts_per_token`` of ``s + bias``, weighted by
  their scores over the sum of the chosen's; the output is the sum over
  the chosen experts this chip holds, ``[first_held_expert,
  first_held_expert + experts_held)``, of weight x SwiGLU expert.

Each kind of layer is stacked apart under ``layers/<attention>_<ffn>``,
as the program stacks them.  Departures are listed in the configuration
file.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from chip import reference as ref

#: the ``as_run`` keys this architecture reads, each a field of the
#: program's ``ModelConfig``; the two patterns are lists
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "v_head_dim", "d_ff",
        "vocab_size", "rope_theta", "norm_eps", "tie_embeddings", "dtype", "layer_pattern",
        "moe_pattern", "window", "window_kv_heads", "window_rope_theta", "partial_rotary_factor",
        "attention_value_scale", "num_experts", "experts_per_token", "expert_d_ff",
        "first_held_expert", "experts_held")


def program_value(cfg, key: str):
    """``key`` as the program's ``ModelConfig`` runs it."""
    if key == "head_dim":
        return cfg.resolved_head_dim
    if key == "v_head_dim":
        return cfg.resolved_v_head_dim
    v = getattr(cfg, key)
    return list(v) if isinstance(v, tuple) else v


def _kinds(d: Dict) -> List[str]:
    return [("window" if w else "global") + ("_experts" if e else "_dense")
            for w, e in zip(d["layer_pattern"], d["moe_pattern"])]


def _runs(d: Dict) -> List[Tuple[str, int, int]]:
    """(kind, first index in the kind's stack, length) of each run of
    consecutive layers of one kind, in layer order."""
    out, seen = [], {}
    for k in _kinds(d):
        if out and out[-1][0] == k:
            out[-1][2] += 1
        else:
            out.append([k, seen.get(k, 0), 1])
        seen[k] = seen.get(k, 0) + 1
    return [tuple(r) for r in out]


def _kv(d: Dict, windowed: bool) -> int:
    return d["window_kv_heads"] if windowed else d["num_kv_heads"]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def shapes(dims: Dict) -> Dict:
    """The parameter tree's shapes, in the program's layout; a leading
    ``"ones"`` marks a norm weight."""
    d, V, h = dims["d_model"], dims["vocab_size"], dims["num_heads"]
    hd, vd = dims["head_dim"], dims["v_head_dim"]
    kinds = _kinds(dims)
    layers = {}
    for k in sorted(set(kinds)):
        L, windowed = kinds.count(k), k.startswith("window")
        kv = _kv(dims, windowed)
        attn = {"wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * vd),
                "wo": (L, h * vd, d)}
        if windowed:
            attn["sink"] = (L, h)
        one = {"attn": attn, "norm_attn": ("ones", L, d), "norm_ffn": ("ones", L, d)}
        if k.endswith("_experts"):
            n, f, E = dims["experts_held"], dims["expert_d_ff"], dims["num_experts"]
            one["moe"] = {"router": (L, d, E), "router_bias": (L, E), "w_gate": (L, n, d, f),
                          "w_up": (L, n, d, f), "w_down": (L, n, f, d)}
        else:
            ff = dims["d_ff"]
            one["ffn"] = {"w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
        layers[k] = one
    tree = {"embed": (V, d), "layers": layers, "final_norm": ("ones", d)}
    if not dims["tie_embeddings"]:
        tree["lm_head"] = (d, V)
    return tree


# ---------------------------------------------------------------------------
# the plain float32 forward pass
# ---------------------------------------------------------------------------


def attention(y, a, dims, windowed: bool, prec):
    """One layer's attention on y [B, S, D] (normed, float32)."""
    B, S, _ = y.shape
    h, hd, vd = dims["num_heads"], dims["head_dim"], dims["v_head_dim"]
    kv = _kv(dims, windowed)
    theta = dims["window_rope_theta"] if windowed else dims["rope_theta"]
    rot = int(hd * dims["partial_rotary_factor"]) // 2 * 2

    def rotary(x):
        return jnp.concatenate([ref.rope(x[..., :rot], theta), x[..., rot:]], axis=-1)

    q = rotary(ref.mm("bsd,de->bse", y, a["wq"], prec).reshape(B, S, h, hd))
    k = rotary(ref.mm("bsd,de->bse", y, a["wk"], prec).reshape(B, S, kv, hd))
    v = ref.mm("bsd,de->bse", y, a["wv"], prec).reshape(B, S, kv, vd)
    g = h // kv
    scores = jnp.einsum("bqngd,bknd->bngqk", ref.quantize(q.reshape(B, S, kv, g, hd), prec, -1),
                        ref.quantize(k, prec, -1), precision=ref.HIGHEST) / math.sqrt(hd)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if windowed:
        seen &= j > i - dims["window"]
    scores = jnp.where(seen, scores, -jnp.inf)
    if windowed:
        sink = jnp.broadcast_to(a["sink"].reshape(1, kv, g, 1, 1), scores.shape[:-1] + (1,))
        w = jax.nn.softmax(jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :S]
    else:
        w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bngqk,bknd->bqngd", ref.quantize(w, prec, -1), ref.quantize(v, prec),
                   precision=ref.HIGHEST).reshape(B, S, h * vd)
    return ref.mm("bse,ed->bsd", o * dims["attention_value_scale"], a["wo"], prec)


def routing(y, m, dims, prec):
    """(chosen experts [B, S, K], their weights [B, S, K]) over all experts."""
    s = jax.nn.sigmoid(ref.mm("bsd,de->bse", y, m["router"], prec))
    _, top = jax.lax.top_k(s + m["router_bias"], dims["experts_per_token"])
    chosen = jnp.take_along_axis(s, top, axis=-1)
    return top, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def experts(y, m, dims, prec):
    """This chip's part of the expert layer: the chosen held experts' outputs,
    each times its weight."""
    top, w = routing(y, m, dims, prec)
    out = jnp.zeros_like(y)
    for e in range(dims["experts_held"]):
        weight = jnp.sum(jnp.where(top == dims["first_held_expert"] + e, w, 0.0), axis=-1)
        u = (jax.nn.silu(ref.mm("bsd,df->bsf", y, m["w_gate"][e], prec))
             * ref.mm("bsd,df->bsf", y, m["w_up"][e], prec))
        out = out + weight[..., None] * ref.mm("bsf,fd->bsd", u, m["w_down"][e], prec)
    return out


def block(x, lp, dims, kind: str, prec):
    """One layer of ``kind`` on x [B, S, D] (float32)."""
    eps = dims["norm_eps"]
    x = x + attention(ref.rms_norm(x, lp["norm_attn"], eps), lp["attn"], dims,
                      kind.startswith("window"), prec)
    y = ref.rms_norm(x, lp["norm_ffn"], eps)
    if kind.endswith("_experts"):
        return x + experts(y, lp["moe"], dims, prec)
    f = lp["ffn"]
    u = (jax.nn.silu(ref.mm("bsd,df->bsf", y, f["w_gate"], prec))
         * ref.mm("bsd,df->bsf", y, f["w_up"], prec))
    return x + ref.mm("bsf,fd->bsd", u, f["w_down"], prec)


def token_logp(params, tokens, dims, prec="f32", remat=False):
    """Log-prob of each realised next token, [N, S-1], float32.  Layer by
    layer in order, one scan per run of one kind, each layer's weights made
    float32 inside its step."""
    x = params["embed"][tokens].astype(jnp.float32)
    for kind, first, n in _runs(dims):
        stack = params["layers"][kind]

        def body(x, i, stack=stack, kind=kind):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)
            return block(x, ref.f32(lp), dims, kind, prec), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, jnp.arange(first, first + n))
    x = ref.rms_norm(x, params["final_norm"].astype(jnp.float32), dims["norm_eps"])
    head = params["embed"].T if dims["tie_embeddings"] else params["lm_head"]
    logits = ref.mm("bsd,dv->bsv", x[:, :-1], head.astype(jnp.float32), prec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


# ---------------------------------------------------------------------------
# work counts (see flops.py for what is counted)
# ---------------------------------------------------------------------------


def _attn_params(d: Dict, windowed: bool) -> int:
    D, h, hd, vd = d["d_model"], d["num_heads"], d["head_dim"], d["v_head_dim"]
    kv = _kv(d, windowed)
    return D * h * hd + D * kv * hd + D * kv * vd + h * vd * D


def _expert_params(d: Dict) -> int:
    return 3 * d["d_model"] * d["expert_d_ff"]


def _active_per_token_x_experts(d: Dict) -> int:
    """Parameters a token multiplies through, summed over the layers, times
    ``num_experts`` (held experts count at the expected rate: a token picks
    each with chance ``experts_per_token / num_experts``)."""
    E, k = d["num_experts"], d["experts_per_token"]
    total = 0
    for w, e in zip(d["layer_pattern"], d["moe_pattern"]):
        layer = _attn_params(d, bool(w))
        if e:
            layer += d["d_model"] * E  # the router, over all experts
            total += E * layer + k * d["experts_held"] * _expert_params(d)
        else:
            total += E * (layer + 3 * d["d_model"] * d["d_ff"])
    return total


def head_params(d: Dict) -> int:
    return d["d_model"] * d["vocab_size"]


def _attn(d: Dict, pairs_global: int, pairs_window: int) -> int:
    """Scores and weighted values for (query, key) pairs, over the layers."""
    per_pair = 2 * d["num_heads"] * (d["head_dim"] + d["v_head_dim"])
    n_window = sum(d["layer_pattern"])
    n_global = d["num_layers"] - n_window
    return per_pair * (n_global * pairs_global + n_window * pairs_window)


def forward(d: Dict, seqs: int, seq_len: int, head_positions: int) -> int:
    """A full causal forward pass of ``seqs`` sequences of ``seq_len``
    tokens, with the head applied at ``head_positions`` of each."""
    W = d["window"]
    pairs_window = sum(min(i + 1, W) for i in range(seq_len))
    layers = 2 * seq_len * _active_per_token_x_experts(d) // d["num_experts"]
    return seqs * (layers + _attn(d, seq_len * (seq_len + 1) // 2, pairs_window)
                   + 2 * head_params(d) * head_positions)


def decode(d: Dict, seqs: int, position: int) -> int:
    """One decoded token per sequence at ``position`` (0-based)."""
    layers = 2 * _active_per_token_x_experts(d) // d["num_experts"]
    return seqs * (layers + _attn(d, position + 1, min(position + 1, d["window"]))
                   + 2 * head_params(d))


def judge_step_bytes(d: Dict, seqs: int, seq_len: int) -> int:
    """Least bytes a step's judge work moves: every weight it touches read
    once (bf16; every held expert is touched in a step), the embedding rows
    it gathers and the token ids."""
    leaves = jax.tree.leaves(shapes(d), is_leaf=lambda x: isinstance(x, tuple))
    weights = sum(math.prod(s[1:] if s[0] == "ones" else s) for s in leaves)
    weights -= d["vocab_size"] * d["d_model"]  # the embedding: gathered rows only
    return 2 * weights + seqs * seq_len * (2 * d["d_model"] + 4)
