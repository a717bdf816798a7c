"""Each work count is at most what XLA counts for the program that does
the work, so no roofline or MFU share it feeds can read above 100%.
At a tiny size on the CPU, with the layer scan unrolled (XLA's cost
analysis counts a loop body once); the counts of one forward pass, one
decoded token and the judge's bytes are the dense architecture's."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from conftest import TINY_DIMS

from chip import flops, spec

DIMS = dict(TINY_DIMS, num_layers=3)
DENSE = spec.arch_of(DIMS)


@pytest.fixture(scope="module")
def api():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("smollm-360m"), **DIMS, scan_unroll=DIMS["num_layers"])
    return build_model(cfg)


def xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return float(cost["flops"])


def test_judge_action_at_most_xla(api):
    from repro.serving.engine import _score

    S = 24
    p = api.abstract_params()
    t = jax.ShapeDtypeStruct((1, S), jnp.int32)
    ours = flops.judge_action(DIMS, S)
    assert 0 < ours <= xla_flops(lambda p, t: _score(api, None, p, t, None), p, t)


def test_forward_and_update_at_most_xla(api):
    from repro.training import AdamWConfig, init_train_state, make_grpo_step
    from repro.training.grpo import token_logprobs

    n, S = 3, 20
    p = api.abstract_params()
    t = jax.ShapeDtypeStruct((n, S), jnp.int32)
    fwd = DENSE.forward(DIMS, n, S, S - 1)
    assert 0 < fwd <= xla_flops(lambda p, t: token_logprobs(p, t, api), p, t)
    state = jax.eval_shape(lambda: init_train_state(api, jax.random.PRNGKey(0)))
    f = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    batch = {"tokens": t, "mask": f((n, S - 1)), "advantages": f((n,)),
             "old_logp": f((n, S - 1)), "ref_logp": f((n, S - 1))}
    step = make_grpo_step(api, AdamWConfig())
    assert 3 * fwd <= xla_flops(step, state, batch)


def test_rollout_at_most_xla(api):
    """Prefill plus the decodes whose logits are sampled."""
    from repro.serving.engine import _prefill

    n, s0, new = 2, 8, 5
    cache = s0 + new
    p = api.abstract_params()
    pre = xla_flops(lambda p, t: _prefill(api, None, cache, p, {"tokens": t}), p,
                    jax.ShapeDtypeStruct((n, s0), jnp.int32))
    state = api.abstract_decode_state(n, cache)
    dec = xla_flops(lambda p, s, t: api.decode_step(p, s, t), p, state,
                    jax.ShapeDtypeStruct((n, 1), jnp.int32))
    ours = flops.rollout(DIMS, n, s0, new)
    assert 0 < ours <= pre + (new - 1) * dec
    # each decode at its position is within one decode program
    assert DENSE.decode(DIMS, n, cache - 1) <= dec


def test_judge_bytes_count_the_weights_once():
    n, S = 16, 256
    one = DENSE.judge_step_bytes(DIMS, 1, S)
    many = DENSE.judge_step_bytes(DIMS, n, S)
    assert many - one == (n - 1) * S * (2 * DIMS["d_model"] + 4)


def test_unknown_device_kind_raises():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")
