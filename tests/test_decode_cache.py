"""transformer.decode_step reads the K/V cache inside its layer scan and
writes each new token's K/V once, after the scan.  Against the cache-first
formula (the token's K/V written into the cache, then every position up to
``pos`` attended): the same logits within the model dtype's rounding, the
prefill's positions untouched to the bit, and each decoded position holding
its token's K/V."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, transformer
from repro.models.layers import decode_attention
from repro.serving.engine import _prefill

B, S0, K, CACHE = 2, 8, 6, 32
#: the reduced configs compute in float32; the two formulas differ only in
#: the order of the softmax's and the value sum's terms
TOL = 1e-4


def cache_first_attention(params, x, pos, k_cache, v_cache, cfg, *, sliding_window=0):
    _, k_new, v_new = decode_attention(params, x, pos, k_cache, v_cache, cfg,
                                       sliding_window=sliding_window)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new, (0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new, (0, pos, 0))
    out, _, _ = decode_attention(params, x, pos, k_cache, v_cache, cfg,
                                 sliding_window=sliding_window, new_token=False)
    return out, k_new, v_new


def _decode(api, params, state, tokens, window):
    step = jax.jit(lambda p, s, t: api.decode_step(p, s, t, sliding_window=window))
    logits = []
    for t in range(tokens.shape[1]):
        out, state = step(params, state, tokens[:, t:t + 1])
        logits.append(np.asarray(out, np.float32))
    return logits, state


@pytest.mark.parametrize("name,window", [
    ("smollm-360m", 0), ("granite-moe-3b-a800m", 0), ("hymba-1.5b", 0), ("smollm-360m", 8),
])
def test_decode_writes_each_token_once(name, window, monkeypatch):
    cfg = get_config(name).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S0)), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, K)), jnp.int32)
    _, pre = _prefill(api, None, CACHE, params, {"tokens": prompt})

    logits, state = _decode(api, params, pre, tokens, window)
    monkeypatch.setattr(transformer, "decode_attention", cache_first_attention)
    want_logits, want = _decode(api, params, pre, tokens, window)

    assert int(state.pos) == S0 + K
    for got, ref in zip(logits, want_logits):
        np.testing.assert_allclose(got, ref, rtol=TOL, atol=TOL)
    written = np.zeros(CACHE, bool)
    written[S0:S0 + K] = True
    for field in ("k_cache", "v_cache"):
        got, ref, before = (np.asarray(getattr(s, field)) for s in (state, want, pre))
        np.testing.assert_array_equal(got[:, :, ~written], before[:, :, ~written])
        np.testing.assert_allclose(got[:, :, written], ref[:, :, written], rtol=TOL, atol=TOL)
        assert np.all(np.any(got[:, :, written] != 0, axis=-1))
