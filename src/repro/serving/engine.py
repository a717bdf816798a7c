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
from repro.models.model import ModelApi
from repro.sharding.rules import Rules


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 => greedy
    cache_len: int = 512
    sliding_window: int = 0


#: Self-attention cache fields of the decode states (transformer and
#: enc-dec); prefill emits them at the prompt's length.
_SELF_CACHES = ("k_cache", "v_cache", "self_k", "self_v")


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
    from repro.training.grpo import token_logprobs

    logp = token_logprobs(params, tokens, api, rules)
    if mask is not None:
        logp = logp * mask
    return jnp.sum(logp, axis=-1)


class Engine:
    """Compiles prefill/decode/score once per input shape.  ``params``
    is an argument of every compiled call, so swapping in new weights
    (e.g. after a training step) compiles nothing."""

    def __init__(self, api: ModelApi, params, gen: GenerationConfig, rules: Optional[Rules] = None):
        self.api = api
        self.params = params
        self.gen = gen
        self.rules = rules

        # named, so that a profiler trace names each program
        def engine_prefill(p, b):
            return _prefill(api, rules, gen.cache_len, p, b)

        def engine_decode(p, s, t):
            return api.decode_step(p, s, t, rules, sliding_window=gen.sliding_window)

        def engine_score(p, t, m):
            return _score(api, rules, p, t, m)

        self._prefill = jax.jit(engine_prefill)
        self._decode = jax.jit(engine_decode)
        self._score = jax.jit(engine_score)

    def generate(
        self, batch: Dict[str, jax.Array], key: Optional[jax.Array] = None
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (generated tokens [B, max_new], per-step logprobs).
        Spans: ``engine.prefill``, then per token ``engine.sample`` (the
        eager sampling ops) and ``engine.decode`` (the decode dispatch),
        then ``engine.collect`` (the per-token outputs joined)."""
        S = batch["tokens"].shape[1]
        if S + self.gen.max_new_tokens > self.gen.cache_len:
            raise ValueError(
                f"prompt {S} + {self.gen.max_new_tokens} new tokens exceed "
                f"cache_len {self.gen.cache_len}"
            )
        with span("engine.prefill"):
            logits, state = self._prefill(self.params, batch)
        out_toks = []
        out_logps = []
        key = key if key is not None else jax.random.PRNGKey(0)
        for i in range(self.gen.max_new_tokens):
            with span("engine.sample"):
                if self.gen.temperature > 0:
                    key, sub = jax.random.split(key)
                    tok = jax.random.categorical(sub, logits / self.gen.temperature, axis=-1)
                else:
                    tok = jnp.argmax(logits, axis=-1)
                logp = jax.nn.log_softmax(logits, axis=-1)
                out_logps.append(jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0])
                tok = tok[:, None].astype(jnp.int32)
                out_toks.append(tok)
            with span("engine.decode"):
                logits, state = self._decode(self.params, state, tok)
        with span("engine.collect"):
            return jnp.concatenate(out_toks, axis=1), jnp.stack(out_logps, axis=1)

    def score(self, batch: Dict[str, jax.Array]) -> jnp.ndarray:
        """Sequence log-likelihood (used by LLM-as-judge reward services)."""
        return self._score(self.params, batch["tokens"], batch.get("mask"))
