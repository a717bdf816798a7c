"""AdamW in pure JAX with sharding-aware state.

Optimizer state mirrors the parameter tree, so parameter PartitionSpecs
apply verbatim to ``m``/``v`` (first/second moments) — FSDP-sharded
params get FSDP-sharded optimizer state for free.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array  # scalar int32
    m: Any  # like params (f32)
    v: Any  # like params (f32)


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def init_adamw(params: Any) -> AdamWState:
    def zeros():
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    # m and v get buffers of their own, so a step may donate the state
    return AdamWState(jnp.zeros((), jnp.int32), zeros(), zeros())


def abstract_adamw(params: Any) -> AdamWState:
    zeros = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params
    )
    return AdamWState(jax.ShapeDtypeStruct((), jnp.int32), zeros, zeros)


def adamw_state_specs(param_specs: Any):
    """Optimizer-state PartitionSpecs from parameter specs."""
    from jax.sharding import PartitionSpec as P

    return AdamWState(P(), param_specs, param_specs)


def lr_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to ``min_lr_ratio``."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / max(1, cfg.warmup_steps))
    prog = jnp.clip(
        (step - cfg.warmup_steps) / max(1, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


def adamw_update(
    cfg: AdamWConfig,
    params: Any,
    grads: Any,
    state: AdamWState,
) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
    """One AdamW step with global-norm clipping and decoupled decay."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mh = m / bc1
        vh = v / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, state.m, state.v)
    new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return (
        new_params,
        AdamWState(step, new_m, new_v),
        {"grad_norm": gnorm, "lr": lr},
    )
