"""MiMo-V2-Flash's mixed stack against a plain float32 reference, at a tiny
size on the CPU: sliding-window attention with a sink beside global
attention, partial rotary, v heads narrower than q/k, sigmoid-routed
experts of which the model holds a share.

The reference below follows the layer equations as written in
``configs/mimo_v2_flash.py``, layer by layer, with no cache, scan or
batching.  Each of its ``MUTATIONS`` is a fault the comparison must see.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.layers import attn_kind, kind_attention, rms_norm
from repro.models.transformer import embed_tokens, forward
from repro.serving.engine import Engine, GenerationConfig
from repro.training.grpo import token_logprobs

B, S = 2, 32

#: d 64; a dense global lead, then windowed and global expert layers;
#: window 8 at 32 tokens; 16 experts, 4 chosen per token, experts 4-7 held
TINY = dataclasses.replace(
    get_config("mimo-v2-flash"),
    name="mimo-tiny", num_layers=5, d_model=64, num_heads=4, num_kv_heads=2, head_dim=24,
    v_head_dim=16, d_ff=96, vocab_size=300, layer_pattern=(0, 1, 1, 0, 1),
    moe_pattern=(0, 1, 1, 1, 1), window=8, window_kv_heads=4, num_experts=16,
    experts_per_token=4, expert_d_ff=32, first_held_expert=4, experts_held=4, dtype="float32",
)
#: float32 on both sides, the same equations in another order (and XLA's
#: fusions): log-probs near -5.7 agree to a few ulps (4.8e-7 each) per
#: layer; 2e-5 leaves room for the 5 layers and the head, and is under a
#: tenth of what the smallest fault below moves
ATOL = 2e-5

MUTATIONS = ("sink_ignored", "window_off_by_one", "rotary_whole_head", "softmax_routing",
             "bias_in_weights", "unnormalised_topk")


def make_params(cfg=TINY, seed=0):
    """Seeded weights, the matrices at 5x the init scale and the sink and
    correction bias at 0.5, so that each mechanism moves the output."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))

    def scale(path, x):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return x
        if "sink" in name or "router_bias" in name:
            return x * 25.0
        return x * 5.0

    return jax.tree_util.tree_map_with_path(scale, params)


def tokens_for(cfg=TINY, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, cfg.vocab_size)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

HI = jax.lax.Precision.HIGHEST


def _rope(x, theta):
    """x [B, S, N, r]: rotate the two halves by position."""
    r = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float32) / r))
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ref_attention(a, y, cfg, windowed, mutation=None):
    Bq, Sq, _ = y.shape
    h, hd, vd = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    kv = cfg.window_kv_heads if windowed else cfg.num_kv_heads
    theta = cfg.window_rope_theta if windowed else cfg.rope_theta
    rot = hd if mutation == "rotary_whole_head" else int(hd * cfg.partial_rotary_factor) // 2 * 2
    q = jnp.dot(y, a["wq"], precision=HI).reshape(Bq, Sq, h, hd)
    k = jnp.dot(y, a["wk"], precision=HI).reshape(Bq, Sq, kv, hd)
    v = jnp.dot(y, a["wv"], precision=HI).reshape(Bq, Sq, kv, vd)
    q = jnp.concatenate([_rope(q[..., :rot], theta), q[..., rot:]], axis=-1)
    k = jnp.concatenate([_rope(k[..., :rot], theta), k[..., rot:]], axis=-1)
    k = jnp.repeat(k, h // kv, axis=2)  # q head i reads kv head i // (h / kv)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bihd,bjhd->bhij", q, k, precision=HI) / np.sqrt(hd)
    i, j = np.arange(Sq)[:, None], np.arange(Sq)[None, :]
    seen = j <= i
    if windowed:
        window = cfg.window + (mutation == "window_off_by_one")
        seen = seen & (j > i - window)
    s = jnp.where(seen, s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    den = jnp.sum(e, axis=-1, keepdims=True)
    if windowed and mutation != "sink_ignored":
        m = jnp.max(s, axis=-1, keepdims=True)
        den = den + jnp.exp(a["sink"][None, :, None, None] - m)
    o = jnp.einsum("bhij,bjhd->bihd", e / den, v, precision=HI).reshape(Bq, Sq, h * vd)
    return jnp.dot(cfg.attention_value_scale * o, a["wo"], precision=HI)


def ref_route(m, y, cfg, mutation=None):
    """(chosen experts [.., K], weights [.., K]) over all experts."""
    logits = jnp.dot(y, m["router"], precision=HI)
    K = cfg.experts_per_token
    if mutation == "softmax_routing":
        p, top = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        return top, p / jnp.sum(p, axis=-1, keepdims=True)
    s = 1.0 / (1.0 + jnp.exp(-logits))
    _, top = jax.lax.top_k(s + m["router_bias"], K)
    w = jnp.take_along_axis(s + m["router_bias"] if mutation == "bias_in_weights" else s, top, -1)
    if mutation == "unnormalised_topk":
        return top, w
    return top, w / jnp.sum(w, axis=-1, keepdims=True)


def ref_experts(m, y, cfg, first, held, mutation=None):
    top, w = ref_route(m, y, cfg, mutation)
    out = jnp.zeros_like(y)
    for e in range(held):
        weight = jnp.sum(jnp.where(top == first + e, w, 0.0), axis=-1)
        u = jax.nn.silu(jnp.dot(y, m["w_gate"][e], precision=HI)) * jnp.dot(
            y, m["w_up"][e], precision=HI)
        out = out + weight[..., None] * jnp.dot(u, m["w_down"][e], precision=HI)
    return out, top


def ref_layers(params, tokens, cfg, mutation=None):
    """Per layer in order: its params, its kind; yields nothing of the program."""
    seen = {}
    for w, e in zip(cfg.layer_pattern, cfg.moe_pattern):
        kind = ("window" if w else "global") + ("_experts" if e else "_dense")
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        yield jax.tree.map(lambda a: a[i], params["layers"][kind]), bool(w), bool(e)


def ref_forward(params, tokens, cfg, mutation=None):
    """(logits [B, S, V], each expert layer's chosen experts)."""
    first, held = cfg.held_experts
    x = params["embed"][tokens]
    routes = []
    for lp, windowed, experts in ref_layers(params, tokens, cfg, mutation):
        x = x + ref_attention(lp["attn"], _norm(x, lp["norm_attn"], cfg.norm_eps), cfg, windowed,
                              mutation)
        y = _norm(x, lp["norm_ffn"], cfg.norm_eps)
        if experts:
            out, top = ref_experts(lp["moe"], y, cfg, first, held, mutation)
            routes.append(np.asarray(top))
            x = x + out
        else:
            f = lp["ffn"]
            u = jax.nn.silu(jnp.dot(y, f["w_gate"], precision=HI)) * jnp.dot(
                y, f["w_up"], precision=HI)
            x = x + jnp.dot(u, f["w_down"], precision=HI)
    x = _norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.dot(x, params["lm_head"], precision=HI), routes


def ref_logp(params, tokens, cfg, mutation=None):
    logits, _ = ref_forward(params, tokens, cfg, mutation)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return np.asarray(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0])


# ---------------------------------------------------------------------------
# the program against it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    api = build_model(TINY)
    params, tokens = make_params(), tokens_for()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: token_logprobs(p, t, api))(params, tokens))
        want = ref_logp(params, tokens, TINY)
    return api, params, tokens, got, want


def test_token_logprobs_and_score_match_the_reference(setup):
    api, params, tokens, got, want = setup
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    engine = Engine(api, params, GenerationConfig(max_new_tokens=1, cache_len=S))
    with jax.default_matmul_precision("highest"):
        scores, counters = engine._score(params, tokens, None)
        np.testing.assert_array_equal(engine.score({"tokens": tokens}), scores)
    # a sum of S - 1 log-probs, each within ATOL
    np.testing.assert_allclose(np.asarray(scores), want.sum(axis=1), rtol=0, atol=(S - 1) * ATOL)
    assert counters.shape == (sum(TINY.moe_pattern), 3)


def test_prefill_then_decode_match_the_reference_logits(setup):
    """Prefill 6 tokens, then decode the rest through both kinds of cache,
    past the window: every position's logits are the full forward's."""
    api, params, tokens, _, _ = setup
    with jax.default_matmul_precision("highest"):
        full, _ = ref_forward(params, tokens, TINY)
        engine = Engine(api, params, GenerationConfig(max_new_tokens=1, cache_len=S))
        logits, state = engine._prefill(params, {"tokens": tokens[:, :6]})
        assert state.k_cache.shape == (2, B, S, 2 * 24) and state.v_cache.shape == (2, B, S, 2 * 16)
        assert state.window_k.shape == (3, B, S, 4 * 24) and state.window_v.shape == (3, B, S, 4 * 16)
        np.testing.assert_allclose(logits, full[:, 5], rtol=0, atol=ATOL)
        step = jax.jit(api.decode_step)
        for t in range(6, S):
            logits, state = step(params, state, tokens[:, t:t + 1])
            np.testing.assert_allclose(logits, full[:, t], rtol=0, atol=ATOL, err_msg=f"t={t}")
    assert int(state.pos) == S


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_each_mutation_fails_the_comparison(setup, mutation):
    _, params, tokens, got, _ = setup
    with jax.default_matmul_precision("highest"):
        wrong = ref_logp(params, tokens, TINY, mutation)
    assert np.max(np.abs(got - wrong)) > 10 * ATOL, mutation


def test_v_width_equal_to_qk_fails_the_comparison(setup):
    """A v head as wide as q/k gives a parameter tree the weights do not fit."""
    api = setup[0]
    shapes = lambda a: jax.tree.map(lambda x: x.shape, a.abstract_params())
    wide = build_model(dataclasses.replace(TINY, v_head_dim=0))
    assert shapes(wide) != shapes(api)
    assert shapes(wide)["layers"]["window_experts"]["attn"]["wv"] == (3, 64, 4 * 24)


def test_shares_sum_to_the_uncut_layer():
    """Four shares of 4 experts each, with attention counted once, add up to
    the layer that holds all 16."""
    one = dict(num_layers=1, layer_pattern=(1,), moe_pattern=(1,))
    uncut = dataclasses.replace(TINY, **one, first_held_expert=0, experts_held=16)
    params = make_params(uncut, seed=1)
    x = embed_tokens(params, tokens_for(uncut), uncut)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, x, pos, uncut, None)
        lp = jax.tree.map(lambda a: a[0], params["layers"]["window_experts"])
        a, _, _ = kind_attention(lp["attn"], rms_norm(x, lp["norm_attn"], 1e-5), pos, uncut,
                                 attn_kind(uncut, True))
        h = x + a
        y = rms_norm(h, lp["norm_ffn"], 1e-5)
        total = h
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(uncut, first_held_expert=first, experts_held=4)
            m = dict(lp["moe"], **{w: lp["moe"][w][first:first + 4]
                                   for w in ("w_gate", "w_up", "w_down")})
            out, counters = moe_mod.held_moe_ffn(m, y, share)
            total = total + out
        got = rms_norm(total, params["final_norm"], 1e-5)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_no_assignment_is_dropped_when_every_token_picks_the_held_experts():
    params = make_params()
    for kind in ("window_experts", "global_experts"):
        bias = params["layers"][kind]["moe"]["router_bias"]
        params["layers"][kind]["moe"]["router_bias"] = bias.at[:, 4:8].add(100.0)
    api, tokens = build_model(TINY), tokens_for()
    engine = Engine(api, params, GenerationConfig(max_new_tokens=1, cache_len=S))
    with jax.default_matmul_precision("highest"):
        scores, counters = engine._score(params, tokens, None)
        want = ref_logp(params, tokens, TINY)
        _, routes = ref_forward(params, tokens, TINY)
    for top in routes:  # the reference's router sends every token to experts 4-7
        assert np.array_equal(np.sort(top, axis=-1), np.broadcast_to([4, 5, 6, 7], top.shape))
    np.testing.assert_allclose(np.asarray(scores), want.sum(axis=1), rtol=0, atol=(S - 1) * ATOL)
    np.testing.assert_array_equal(np.asarray(counters), [[B * S * 4, B * S, 0]] * 4)


def test_counters_equal_a_recount_of_the_reference_router(setup):
    api, params, tokens, _, _ = setup
    engine = Engine(api, params, GenerationConfig(max_new_tokens=1, cache_len=S))
    with jax.default_matmul_precision("highest"):
        _, counters = engine._score(params, tokens, None)
        _, routes = ref_forward(params, tokens, TINY)
    first, held = TINY.held_experts
    want = []
    for top in routes:
        load = np.array([np.sum(top == first + e) for e in range(held)])
        want.append([load.sum(), load.max(), 0])
    assert np.asarray(want)[:, 0].sum() > 0
    np.testing.assert_array_equal(np.asarray(counters), want)


# ---------------------------------------------------------------------------
# parameter counts
# ---------------------------------------------------------------------------

#: (param_count, active_param_count) of every other registry entry, as they
#: read before layers of several kinds were counted
PINNED_COUNTS = {
    "glm4-9b": (9399767040, 9399767040),
    "granite-moe-3b-a800m": (3374295552, 958376448),
    "hymba-1.5b": (1640508800, 1640508800),
    "internvl2-1b": (629636224, 629636224),
    "kimi-k2-1t-a32b": (1041166988288, 31061144576),
    "llama3-8b": (8030261248, 8030261248),
    "llama3.2-1b": (1235814400, 1235814400),
    "mamba2-130m": (128768448, 128768448),
    "smollm-360m": (361821120, 361821120),
    "whisper-medium": (1012314112, 1012314112),
}


@pytest.mark.parametrize("name", sorted(PINNED_COUNTS))
def test_param_counts_of_the_other_entries_are_unchanged(name):
    api = build_model(get_config(name))
    assert (api.param_count(), api.active_param_count()) == PINNED_COUNTS[name]


def test_param_counts_of_mixed_layers_and_held_experts():
    whole = build_model(get_config("mimo-v2-flash"))
    # 309B-A15B as published (the MTP modules left out)
    assert whole.param_count() == 308_778_780_864
    assert whole.active_param_count() == 15_445_936_320
    # the judge stage the benchmark runs: layers 0-6, experts 0-7 of 256
    stage = build_model(dataclasses.replace(
        get_config("mimo-v2-flash"), num_layers=7, layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_pattern=(0,) + (1,) * 6, experts_held=8))
    assert stage.param_count() == 3_315_660_608
    assert round(stage.param_count() * 2 / 1e9, 2) == 6.63  # RewardService.state_gb
    expert = 3 * 4096 * 2048
    assert stage.param_count() - stage.active_param_count() == 6 * expert * 8 * 248 // 256


# ---------------------------------------------------------------------------
# the system's normal path
# ---------------------------------------------------------------------------


def test_live_driver_scores_with_an_expert_judge_and_reads_its_counters():
    """LiveGrpoDriver -> Tangram -> RewardService.score with the MiMo judge;
    the counters are read once, after the update."""
    from repro.core.cluster import paper_testbed
    from repro.rl.driver import LiveGrpoDriver, build_tangram

    policy, judge = get_config("smollm-360m").reduced(), get_config("mimo-v2-flash").reduced()
    driver = LiveGrpoDriver(policy, judge, group_size=2, seed=0)
    prompts = np.random.default_rng(0).integers(0, policy.vocab_size, (2, 8)).astype(np.int32)
    tangram = build_tangram(paper_testbed(cpu_nodes=1, gpu_nodes=1), services=["judge"],
                            service_state_gb=driver.judge.state_gb)
    rep = driver.run_step(prompts, tangram)
    names = [s.name for s in rep.spans]
    assert names.count("judge.counters") == 1
    assert names.index("judge.counters") > names.index("grpo.apply")
    assert rep.spans[rep.spans[names.index("judge.counters")].parent].name == "grpo.step"
    c = rep.counters
    n_rows, n_tok = rep.sequences.shape
    assert c["judge_calls"] == n_rows and c["held_experts"] == judge.held_experts[1]
    # the reduced judge holds all of its experts: every assignment lands
    assert c["held_assignments"].tolist() == [n_rows * n_tok * judge.experts_per_token]
    assert c["dropped_assignments"].tolist() == [0]
    assert driver.judge.counters is None and driver.judge.calls == 0


def test_a_dense_judge_returns_and_reads_no_counters():
    from repro.serving.reward_service import deploy_reward_service

    svc = deploy_reward_service("judge", get_config("smollm-360m").reduced())
    scores = svc.score(jnp.zeros((1, 8), jnp.int32))
    assert scores.shape == (1,) and svc.counters is None and svc.read_counters() is None


def test_the_service_keeps_one_running_sum_of_the_counters(setup):
    """Three calls: assignments and drops summed, the largest load the max,
    one [expert layers, 3] array on the device; a read starts again."""
    from repro.serving.reward_service import RewardService

    api, params, tokens, _, _ = setup
    engine = Engine(api, params, GenerationConfig(max_new_tokens=1, cache_len=S))
    svc = RewardService("judge", TINY, engine, state_gb=0.0)
    calls = [tokens, tokens[:1], tokens[:, ::-1]]
    each = [np.asarray(engine._score(params, t, None)[1]) for t in calls]
    for t in calls:
        svc.score(t)
    assert svc.counters.shape == (sum(TINY.moe_pattern), 3) and svc.calls == 3
    c = svc.read_counters()
    assert c["judge_calls"] == 3 and c["held_experts"] == TINY.held_experts[1]
    np.testing.assert_array_equal(c["held_assignments"], sum(e[:, 0] for e in each))
    np.testing.assert_array_equal(c["held_max_load"], np.max([e[:, 1] for e in each], axis=0))
    np.testing.assert_array_equal(c["dropped_assignments"], [0] * sum(TINY.moe_pattern))
    assert svc.counters is None and svc.calls == 0 and svc.read_counters() is None
