"""dense: the decoder both configurations run, and the default of a model
section that names no ``arch``.

Pre-norm RMSNorm, grouped-query attention with rotary embeddings over the
whole head split into halves, SwiGLU, a final RMSNorm and a tied or
untied head, every layer alike; departures from a published model are
listed in its configuration file.  This file holds the architecture's
shape keys, its parameter tree, its plain float32 forward pass and its
work counts; ``reference.py`` and ``flops.py`` hold what every
architecture shares.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chip import reference as ref

#: the ``as_run`` keys this architecture reads, each a field of the
#: program's ``ModelConfig``
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
        "vocab_size", "rope_theta", "norm_eps", "tie_embeddings", "dtype")


def program_value(cfg, key: str):
    """``key`` as the program's ``ModelConfig`` runs it."""
    return cfg.resolved_head_dim if key == "head_dim" else getattr(cfg, key)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def shapes(dims: Dict) -> Dict:
    """The parameter tree's shapes, in the program's layout (``embed``,
    layer-stacked ``layers``, ``final_norm``, and ``lm_head`` when the head
    is untied); a leading ``"ones"`` marks a norm weight."""
    d, L, V = dims["d_model"], dims["num_layers"], dims["vocab_size"]
    h, kv, hd, ff = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"], dims["d_ff"]
    tree = {
        "embed": (V, d),
        "layers": {
            "attn": {"wq": (L, d, h * hd), "wk": (L, d, kv * hd),
                     "wv": (L, d, kv * hd), "wo": (L, h * hd, d)},
            "norm_attn": ("ones", L, d),
            "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)},
            "norm_ffn": ("ones", L, d),
        },
        "final_norm": ("ones", d),
    }
    if not dims["tie_embeddings"]:
        tree["lm_head"] = (d, V)
    return tree


# ---------------------------------------------------------------------------
# the plain float32 forward pass
# ---------------------------------------------------------------------------


def block(x, lp, dims, prec):
    """One decoder layer on x [B, S, D] (float32)."""
    B, S, _ = x.shape
    h, kv, hd = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    eps = dims["norm_eps"]
    a = lp["attn"]
    y = ref.rms_norm(x, lp["norm_attn"], eps)
    q = ref.rope(ref.mm("bsd,de->bse", y, a["wq"], prec).reshape(B, S, h, hd), dims["rope_theta"])
    k = ref.rope(ref.mm("bsd,de->bse", y, a["wk"], prec).reshape(B, S, kv, hd), dims["rope_theta"])
    v = ref.mm("bsd,de->bse", y, a["wv"], prec).reshape(B, S, kv, hd)
    g = h // kv
    q = q.reshape(B, S, kv, g, hd)
    scores = jnp.einsum("bqngd,bknd->bngqk", ref.quantize(q, prec, -1), ref.quantize(k, prec, -1),
                        precision=ref.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bngqk,bknd->bqngd", ref.quantize(w, prec, -1), ref.quantize(v, prec),
                   precision=ref.HIGHEST).reshape(B, S, h * hd)
    x = x + ref.mm("bse,ed->bsd", o, a["wo"], prec)
    f = lp["ffn"]
    y = ref.rms_norm(x, lp["norm_ffn"], eps)
    u = (jax.nn.silu(ref.mm("bsd,df->bsf", y, f["w_gate"], prec))
         * ref.mm("bsd,df->bsf", y, f["w_up"], prec))
    return x + ref.mm("bsf,fd->bsd", u, f["w_down"], prec)


def token_logp(params, tokens, dims, prec="f32", remat=False):
    """Log-prob of each realised next token, [N, S-1], float32."""
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return block(x, ref.f32(lp), dims, prec), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = ref.rms_norm(x, params["final_norm"].astype(jnp.float32), dims["norm_eps"])
    head = params["embed"].T if dims["tie_embeddings"] else params["lm_head"]
    logits = ref.mm("bsd,dv->bsv", x[:, :-1], head.astype(jnp.float32), prec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


# ---------------------------------------------------------------------------
# work counts (see flops.py for what is counted)
# ---------------------------------------------------------------------------


def layer_params(d: Dict) -> int:
    D, h, kv, hd, ff = d["d_model"], d["num_heads"], d["num_kv_heads"], d["head_dim"], d["d_ff"]
    return 2 * D * h * hd + 2 * D * kv * hd + 3 * D * ff


def head_params(d: Dict) -> int:
    return d["d_model"] * d["vocab_size"]


def _attn(d: Dict, pairs: int) -> int:
    """Scores and weighted values for ``pairs`` (query, key) pairs."""
    return 4 * d["num_heads"] * d["head_dim"] * d["num_layers"] * pairs


def forward(d: Dict, seqs: int, seq_len: int, head_positions: int) -> int:
    """A full causal forward pass of ``seqs`` sequences of ``seq_len``
    tokens, with the head applied at ``head_positions`` of each."""
    pairs = seq_len * (seq_len + 1) // 2
    return seqs * (2 * d["num_layers"] * layer_params(d) * seq_len + _attn(d, pairs)
                   + 2 * head_params(d) * head_positions)


def decode(d: Dict, seqs: int, position: int) -> int:
    """One decoded token per sequence at ``position`` (0-based)."""
    return seqs * (2 * d["num_layers"] * layer_params(d) + _attn(d, position + 1)
                   + 2 * head_params(d))


def judge_step_bytes(d: Dict, seqs: int, seq_len: int) -> int:
    """Least bytes a step's judge work moves: its weights read once (bf16),
    the embedding rows it gathers and the token ids."""
    weights = d["num_layers"] * (layer_params(d) + 2 * d["d_model"]) + head_params(d) + d["d_model"]
    return 2 * weights + seqs * seq_len * (2 * d["d_model"] + 4)
