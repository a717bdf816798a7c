"""Serving engine: batched prefill + autoregressive decode.

Used both by the examples (serve a small model with batched requests)
and by the GPU manager's reward services (rl/ + serving/reward_service).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.telemetry import span
from repro.models.layers import logits_fn
from repro.models.model import ModelApi
from repro.models.transformer import embed_tokens, forward
from repro.sharding.rules import Rules


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 => greedy
    cache_len: int = 512
    sliding_window: int = 0


#: Self-attention cache fields of the decode states (transformer, mixed
#: stack and enc-dec); prefill emits them at the prompt's length.
_SELF_CACHES = ("k_cache", "v_cache", "window_k", "window_v", "self_k", "self_v")


def _prefill(api: ModelApi, rules: Optional[Rules], cache_len: int, params, batch):
    """Prefill, with the self-attention caches [L, B, S, kv*hd] padded to
    ``cache_len`` positions so that decode writes land in free slots."""
    logits, state = api.prefill(params, batch, rules)
    grown = {}
    for f in _SELF_CACHES:
        c = getattr(state, f, None)
        if c is not None and c.shape[2] < cache_len:
            pad = [(0, 0)] * c.ndim
            pad[2] = (0, cache_len - c.shape[2])
            grown[f] = jnp.pad(c, pad)
    return logits, state._replace(**grown)


def _score(api: ModelApi, rules: Optional[Rules], params, tokens, mask):
    """Sequence log-likelihood of each row, and the trunk's routing counters
    [expert layers, 3] (None for a model without held experts)."""
    cfg = api.cfg
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h, aux = forward(params, x, pos, cfg, rules)
    logits = logits_fn(params, h[:, :-1, :], cfg)  # [N, S-1, V] f32
    logp = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    if mask is not None:
        logp = logp * mask
    return jnp.sum(logp, axis=-1), aux.get("routing")


class Engine:
    """Compiles prefill, the per-token program and score once per input
    shape.  ``params`` is an argument of every compiled call, so swapping
    in new weights (e.g. after a training step) compiles nothing."""

    def __init__(self, api: ModelApi, params, gen: GenerationConfig, rules: Optional[Rules] = None):
        self.api = api
        self.params = params
        self.gen = gen
        self.rules = rules

        # named, so that a profiler trace names each program
        def engine_prefill(p, b):
            return _prefill(api, rules, gen.cache_len, p, b)

        def engine_token(p, s, logits, key, toks, logps, i):
            if gen.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, logits / gen.temperature, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            logp = jnp.take_along_axis(logp, tok[:, None], axis=-1)
            tok = tok[:, None].astype(jnp.int32)
            toks = jax.lax.dynamic_update_slice(toks, tok, (0, i))
            logps = jax.lax.dynamic_update_slice(logps, logp, (0, i))
            logits, s = api.decode_step(p, s, tok, rules, sliding_window=gen.sliding_window)
            return logits, s, key, toks, logps

        def engine_score(p, t, m):
            return _score(api, rules, p, t, m)

        self._prefill = jax.jit(engine_prefill)
        # the decode state and the two output buffers are dead after each
        # token: donating them lets the cache write land in place
        self._token = jax.jit(engine_token, donate_argnums=(1, 4, 5))
        self._score = jax.jit(engine_score)

    def generate(
        self, batch: Dict[str, jax.Array], key: Optional[jax.Array] = None
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (generated tokens [B, max_new] int32, their log-probs
        [B, max_new] in the logits' dtype), both still on the device.

        One compiled program per token, ``engine_token``, samples the
        token from the last logits, writes it and its log-prob into
        column ``i`` of the two buffers it carries, and decodes it; the
        loop only dispatches, so no output leaves the device inside it.
        At temperature > 0 each token splits the key it is handed,
        ``key, sub = split(key)``, and draws from ``sub``: the first
        split is of ``key`` (``PRNGKey(0)`` if None), each later one of
        the key the previous token returned.  At temperature 0 the token
        is the argmax and the key is unused.

        Spans: ``engine.prefill``, then one ``engine.decode`` per token
        around its dispatch."""
        S = batch["tokens"].shape[1]
        n = self.gen.max_new_tokens
        if S + n > self.gen.cache_len:
            raise ValueError(
                f"prompt {S} + {n} new tokens exceed cache_len {self.gen.cache_len}"
            )
        with span("engine.prefill"):
            logits, state = self._prefill(self.params, batch)
        B = logits.shape[0]
        toks = jnp.zeros((B, n), jnp.int32)
        logps = jnp.zeros((B, n), logits.dtype)
        key = key if key is not None else jax.random.PRNGKey(0)
        for i in range(n):
            with span("engine.decode"):
                logits, state, key, toks, logps = self._token(
                    self.params, state, logits, key, toks, logps, i
                )
        return toks, logps

    def score(self, batch: Dict[str, jax.Array]) -> jnp.ndarray:
        """Sequence log-likelihood (used by LLM-as-judge reward services)."""
        return self._score(self.params, batch["tokens"], batch.get("mask"))[0]
