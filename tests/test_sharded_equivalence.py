"""Multi-device equivalence tests for the sharded substrate paths.

The main suite runs on 1 CPU device (the dry-run owns the 512-device
flag), so these tests spawn a subprocess with 8 host devices and assert
the shard_map MoE dispatch and the padded-head attention match their
unsharded oracles bit-for-bit (fwd) and numerically (grads).
"""

import subprocess
import sys
import textwrap

import pytest

_PROLOGUE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.sharding.rules import make_rules
"""


def _run(body: str) -> None:
    proc = subprocess.run(
        [sys.executable, "-c", _PROLOGUE + textwrap.dedent(body)],
        capture_output=True,
        text=True,
        cwd=".",
        timeout=240,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"


@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
def test_moe_sharded_matches_global(mesh_shape):
    _run(f"""
    from repro.models import moe
    cfg = dataclasses.replace(
        get_config("granite-moe-3b-a800m"), num_layers=2, d_model=128,
        expert_d_ff=64, num_experts=10, experts_per_token=4,
        capacity_factor=4.0)
    mesh = jax.make_mesh({mesh_shape}, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(mesh)
    B, S, D = 8, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D)) * 0.1
    params = {{k: jax.random.normal(jax.random.PRNGKey(i), s) * 0.05
              for i, (k, s) in enumerate({{
                  "router": (D, 10), "w_gate": (10, D, 64),
                  "w_up": (10, D, 64), "w_down": (10, 64, D)}}.items())}}
    with mesh:
        y_ref, aux_ref = jax.jit(
            lambda p, x: moe._moe_ffn_global(p, x, cfg, None))(params, x)
        y_sh, aux_sh = jax.jit(
            lambda p, x: moe._moe_ffn_sharded(p, x, cfg, rules))(params, x)
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(
            moe._moe_ffn_sharded(p, x, cfg, rules)[0] ** 2)))(params, x)
        g_ref = jax.jit(jax.grad(lambda p, x: jnp.sum(
            moe._moe_ffn_global(p, x, cfg, None)[0] ** 2)))(params, x)
    assert np.allclose(y_ref, y_sh, atol=1e-5), "forward mismatch"
    for k in aux_ref:
        assert np.allclose(aux_ref[k], aux_sh[k], atol=1e-5), k
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        assert np.allclose(a, b, atol=2e-4), "grad mismatch"
    print("ok")
    """)


def test_padded_head_attention_matches_unsharded():
    _run("""
    from repro.models.layers import multihead_attention, _pad_plan
    # pad plans for the real indivisible archs on a 16-way axis
    assert _pad_plan(8, 3, 16) == (8, 4)    # granite 24H -> 32
    assert _pad_plan(5, 3, 16) == (8, 4)    # smollm 15H -> 32
    assert _pad_plan(2, 7, 16) == (2, 8)    # internvl2 14H -> 16
    cfg = dataclasses.replace(
        get_config("llama3.2-1b"), num_layers=2, d_model=96,
        num_heads=6, num_kv_heads=2, head_dim=16)
    mesh = jax.make_mesh((2, 4), ("data", "model"),  # 6 % 4 != 0 -> pad
                         axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(mesh)
    B, S, D, h, kv, hd = 4, 16, 96, 6, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D)) * 0.2
    params = {
        "wq": jax.random.normal(jax.random.PRNGKey(1), (D, h * hd)) * 0.1,
        "wk": jax.random.normal(jax.random.PRNGKey(2), (D, kv * hd)) * 0.1,
        "wv": jax.random.normal(jax.random.PRNGKey(3), (D, kv * hd)) * 0.1,
        "wo": jax.random.normal(jax.random.PRNGKey(4), (h * hd, D)) * 0.1,
    }
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    with mesh:
        y_ref = jax.jit(
            lambda p, x: multihead_attention(p, x, pos, cfg))(params, x)
        y_sh = jax.jit(
            lambda p, x: multihead_attention(p, x, pos, cfg, rules=rules))(params, x)
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(
            multihead_attention(p, x, pos, cfg, rules=rules) ** 2)))(params, x)
    assert np.allclose(y_ref, y_sh, atol=1e-4), "forward mismatch"
    for k, v in g.items():
        assert v.shape == params[k].shape, (k, v.shape)
        assert np.isfinite(np.asarray(v)).all()
    print("ok")
    """)


def test_flat_cache_decode_matches_5d_math():
    """Decode with the flat [B,S,kv*hd] cache reproduces prefill logits."""
    _run("""
    from repro.models.model import build_model
    cfg = get_config("glm4-9b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
    state = api.init_decode_state(B, S)
    assert state.k_cache.ndim == 4  # flat layout
    for t in range(S):
        logits, state = api.decode_step(params, state, tokens[:, t:t+1])
    pf_logits, pf_state = api.prefill(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(pf_logits, np.float32),
                               np.asarray(logits, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(pf_state.k_cache, np.float32),
                               np.asarray(state.k_cache, np.float32),
                               rtol=2e-2, atol=2e-2)
    print("ok")
    """)
