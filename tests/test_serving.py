"""Serving: judge scoring compiles once per shape, its weights are the
same in every process, and generation fills the decode cache."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.compilation import CompileCounter
from repro.models import build_model
from repro.serving.engine import Engine, GenerationConfig
from repro.training.grpo import token_logprobs

REPO = Path(__file__).resolve().parent.parent

#: sum |w| over every parameter of the "judge" service built from the
#: reduced llama3.2-1b with its default key: a change here means the
#: judge's weights moved
JUDGE_ABS_SUM = 24274.120723800865


@pytest.fixture(scope="module")
def judge_engine():
    cfg = get_config("llama3.2-1b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return Engine(api, params, GenerationConfig(max_new_tokens=4, cache_len=32)), cfg


def test_score_compiles_once_per_shape(judge_engine):
    engine, cfg = judge_engine
    rng = np.random.default_rng(0)
    toks = [jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
            for _ in range(3)]
    with CompileCounter() as first:
        engine.score({"tokens": toks[0]})
    with CompileCounter() as later:
        for t in toks[1:]:
            engine.score({"tokens": t})
    new_weights = jax.tree.map(lambda p: p * 1, engine.params)
    engine.params, old = new_weights, engine.params
    try:
        with CompileCounter() as swapped:
            engine.score({"tokens": toks[0]})
    finally:
        engine.params = old
    assert first.lowered >= 1
    assert later.lowered == 0
    assert swapped.lowered == 0


def test_score_matches_token_logprobs(judge_engine):
    engine, cfg = judge_engine
    toks = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 10)),
                       jnp.int32)
    want = jnp.sum(token_logprobs(engine.params, toks, engine.api), axis=-1)
    np.testing.assert_allclose(np.asarray(engine.score({"tokens": toks})),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_generate_matches_teacher_forced_logprobs(judge_engine):
    """Greedy generation from the padded prefill cache: each emitted
    token's log-prob equals a teacher-forced forward over the sequence."""
    engine, cfg = judge_engine
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 6)),
                         jnp.int32)
    toks, logps = engine.generate({"tokens": prompt})
    seq = jnp.concatenate([prompt, toks], axis=1)
    tf = token_logprobs(engine.params, seq, engine.api)[:, prompt.shape[1] - 1:]
    np.testing.assert_allclose(np.asarray(logps), np.asarray(tf), rtol=2e-2, atol=2e-2)


_CHECKSUM = """
    import jax, numpy as np
    from repro.configs import get_config
    from repro.serving.reward_service import deploy_reward_service
    svc = deploy_reward_service("judge", get_config("llama3.2-1b").reduced())
    leaves = jax.tree.leaves(svc.engine.params)
    print(repr(float(sum(np.abs(np.asarray(x, np.float64)).sum() for x in leaves))))
"""


def test_reward_service_weights_are_the_same_in_every_process():
    sums = []
    for hash_seed in ("0", "12345"):
        env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
                   PYTHONHASHSEED=hash_seed)
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CHECKSUM)],
                             env=env, capture_output=True, text=True, timeout=120,
                             check=True)
        sums.append(float(out.stdout.strip().splitlines()[-1]))
    assert sums[0] == sums[1]
    assert sums[0] == pytest.approx(JUDGE_ABS_SUM, rel=1e-6)
