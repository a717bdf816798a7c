"""GRPO (Group Relative Policy Optimization, Shao et al. 2024).

The paper's workloads (§6.1) train with GRPO: for each prompt a *group*
of G trajectories is rolled out; advantages are the group-normalized
rewards; the policy gradient uses a PPO-style clipped ratio against the
rollout-time log-probs, plus a KL penalty to the reference policy.

Rewards come from external resources (test execution on CPUs, reward
models on GPUs) — in this repo those invocations are ARL-Tangram
actions (see rl/driver.py).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.model import ModelApi
from repro.models.layers import logits_fn
from repro.models.transformer import embed_tokens, forward
from repro.sharding.rules import Rules
from repro.training.optimizer import AdamWConfig, adamw_update
from repro.training.train_step import TrainState


def group_advantages(rewards: jax.Array) -> jax.Array:
    """rewards [B, G] -> group-normalized advantages [B, G]."""
    mean = jnp.mean(rewards, axis=1, keepdims=True)
    std = jnp.std(rewards, axis=1, keepdims=True)
    return (rewards - mean) / (std + 1e-6)


def token_logprobs(
    params: dict, tokens: jax.Array, api: ModelApi, rules: Optional[Rules] = None
) -> jax.Array:
    """Log-prob of each realized next token; [N, S-1]."""
    cfg = api.cfg
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h, _ = forward(params, x, pos, cfg, rules)
    logits = logits_fn(params, h[:, :-1, :], cfg)  # [N, S-1, V] f32
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def grpo_loss(
    params: dict,
    batch: Dict[str, jax.Array],
    api: ModelApi,
    rules: Optional[Rules] = None,
    clip_eps: float = 0.2,
    kl_coef: float = 0.02,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: tokens [N,S], mask [N,S-1] (1 on generated positions),
    advantages [N], old_logp [N,S-1], ref_logp [N,S-1]."""
    tokens = batch["tokens"]
    mask = batch["mask"].astype(jnp.float32)
    adv = batch["advantages"][:, None]  # [N,1] broadcast over positions
    logp = token_logprobs(params, tokens, api, rules)
    ratio = jnp.exp(logp - batch["old_logp"])
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv
    pg = -jnp.minimum(unclipped, clipped)
    # k3 KL estimator (non-negative, unbiased-ish): exp(d) - d - 1
    d = batch["ref_logp"] - logp
    kl = jnp.exp(d) - d - 1.0
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    pg_loss = jnp.sum(pg * mask) / denom
    kl_loss = jnp.sum(kl * mask) / denom
    loss = pg_loss + kl_coef * kl_loss
    return loss, {
        "pg_loss": pg_loss,
        "kl": kl_loss,
        "ratio_mean": jnp.sum(ratio * mask) / denom,
    }


def make_grpo_step(api: ModelApi, opt_cfg: AdamWConfig, rules: Optional[Rules] = None):
    def grpo_step(state: TrainState, batch: Dict[str, jax.Array]):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: grpo_loss(p, batch, api, rules), has_aux=True
        )(state.params)
        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, state.params, grads, state.opt
        )
        return TrainState(new_params, new_opt), {"loss": loss, **metrics, **opt_metrics}

    return grpo_step
