"""Unified model API: ``build_model(cfg)`` -> :class:`ModelApi`.

One façade across the six architecture families; everything downstream
(training, serving, dry-run, RL driver) goes through this interface.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.models.layers import (
    ParamDef,
    abstract_from_schema,
    init_from_schema,
    specs_from_schema,
)
from repro.sharding.rules import Rules


@dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig
    schema: Dict[str, Any]

    # ---- params ----------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        return init_from_schema(key, self.schema, jnp.dtype(self.cfg.dtype))

    def abstract_params(self) -> dict:
        return abstract_from_schema(self.schema, jnp.dtype(self.cfg.dtype))

    def param_specs(self, rules: Rules) -> dict:
        return specs_from_schema(self.schema, rules)

    def param_count(self) -> int:
        """Parameters this model holds: every kind of layer at its own
        shapes, and of routed experts only the held ones."""
        leaves = jax.tree.leaves(
            self.schema, is_leaf=lambda x: isinstance(x, ParamDef)
        )
        return sum(math.prod(p.shape) for p in leaves)

    def active_param_count(self) -> int:
        """6*N*D roofline uses *active* params for MoE: of each expert layer's
        held experts, the expected share a token routes to (k of E each)."""
        cfg = self.cfg
        total = self.param_count()
        if not cfg.num_experts:
            return total
        E, k = cfg.num_experts, cfg.experts_per_token
        inactive = 0
        for moe in _expert_blocks(self.schema):
            L, n = moe["w_gate"].shape[:2]
            per_expert = sum(math.prod(moe[w].shape[2:]) for w in ("w_gate", "w_up", "w_down"))
            inactive += L * per_expert * n * (E - k) // E
        return total - inactive

    # ---- training --------------------------------------------------------
    def loss_fn(
        self, params: dict, batch: Dict[str, jax.Array], rules: Optional[Rules] = None
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        if self.cfg.family == "audio":
            return encdec.lm_loss(params, batch, self.cfg, rules)
        return transformer.lm_loss(params, batch, self.cfg, rules)

    # ---- serving ---------------------------------------------------------
    def prefill(self, params, batch, rules=None):
        if self.cfg.family == "audio":
            return encdec.prefill(params, batch, self.cfg, rules)
        return transformer.prefill(params, batch, self.cfg, rules)

    def decode_step(self, params, state, token, rules=None, sliding_window: int = 0):
        if self.cfg.family == "audio":
            return encdec.decode_step(
                params, state, token, self.cfg, rules, sliding_window
            )
        return transformer.decode_step(
            params, state, token, self.cfg, rules, sliding_window
        )

    def init_decode_state(self, batch: int, cache_len: int):
        dt = jnp.dtype(self.cfg.dtype)
        if self.cfg.family == "audio":
            return encdec.init_decode_state(self.cfg, batch, cache_len, dt)
        return transformer.init_decode_state(self.cfg, batch, cache_len, dt)

    def abstract_decode_state(self, batch: int, cache_len: int):
        return jax.eval_shape(lambda: self.init_decode_state(batch, cache_len))

    def decode_state_specs(self, rules: Rules, batch: int, cache_len: int):
        if self.cfg.family == "audio":
            return encdec.decode_state_specs(self.cfg, rules, batch, cache_len)
        return transformer.decode_state_specs(self.cfg, rules, batch, cache_len)


def _expert_blocks(schema: Dict[str, Any]):
    """The layer-stacked expert weights of each kind of layer that has them."""
    layers = schema["layers"]
    groups = [layers] if "moe" in layers else [g for g in layers.values() if "moe" in g]
    return [g["moe"] for g in groups]


def build_model(cfg: ModelConfig) -> ModelApi:
    if cfg.family == "audio":
        schema = encdec.model_schema(cfg)
    else:
        schema = transformer.model_schema(cfg)
    return ModelApi(cfg=cfg, schema=schema)
