"""Engine.generate's per-token program against the eager loop it replaced:
the same key gives the same tokens and the same log-probs, to the bit,
and the program compiles once for a whole generation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import Engine, GenerationConfig

B, S0, NEW, CACHE = 4, 8, 16, 32


def eager_generate(engine: Engine, batch, key):
    """The reference: sampling as eager ops on the host between decode
    programs, one token at a time, outputs joined at the end."""
    gen, api = engine.gen, engine.api

    def engine_decode(p, s, t):
        return api.decode_step(p, s, t, engine.rules, sliding_window=gen.sliding_window)

    decode = jax.jit(engine_decode)
    logits, state = engine._prefill(engine.params, batch)
    out_toks, out_logps = [], []
    for _ in range(gen.max_new_tokens):
        if gen.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / gen.temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        out_logps.append(jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0])
        tok = tok[:, None].astype(jnp.int32)
        out_toks.append(tok)
        logits, state = decode(engine.params, state, tok)
    return jnp.concatenate(out_toks, axis=1), jnp.stack(out_logps, axis=1)


@pytest.mark.parametrize("temperature", [1.0, 0.0])
@pytest.mark.parametrize("name", ["smollm-360m", "granite-moe-3b-a800m", "hymba-1.5b"])
def test_generate_matches_the_eager_loop(name, temperature):
    cfg = get_config(name).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    engine = Engine(api, params, GenerationConfig(max_new_tokens=NEW, temperature=temperature,
                                                  cache_len=CACHE))
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, (B, S0)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt)}
    key = jax.random.PRNGKey(7)

    toks, logps = engine.generate(batch, key=key)
    want_toks, want_logps = eager_generate(engine, batch, key)

    assert toks.shape == (B, NEW) and toks.dtype == jnp.int32
    assert logps.dtype == want_logps.dtype
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want_toks))
    np.testing.assert_array_equal(np.asarray(logps), np.asarray(want_logps))
    # one compile for all NEW positions: the column is a traced argument
    assert engine._token._cache_size() == 1


def test_token_program_donates_the_decode_state():
    """The per-token program writes the K/V caches in place: its compiled
    program aliases at least both caches.  Params and prompts are not
    donated, so a second generate on the same engine gives the same
    tokens."""
    cfg = get_config("smollm-360m").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    engine = Engine(api, params, GenerationConfig(max_new_tokens=NEW, temperature=1.0,
                                                  cache_len=CACHE))
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (B, S0)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt)}
    key = jax.random.PRNGKey(11)

    logits, state = engine._prefill(params, batch)
    toks = jnp.zeros((B, NEW), jnp.int32)
    logps = jnp.zeros((B, NEW), logits.dtype)
    compiled = engine._token.lower(params, state, logits, key, toks, logps, 0).compile()
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias >= state.k_cache.nbytes + state.v_cache.nbytes

    first = engine.generate(batch, key=key)
    second = engine.generate(batch, key=key)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
