"""Reward services: real JAX models deployed behind the GPU manager.

The paper's MOPD workload serves many teacher models whose SM activity
averages <3% (§2.2 Fig. 3b) — the motivating waste.  Here each service
is an :class:`~repro.serving.engine.Engine` over a (small) model; the
GPU manager's EOE decides which service is resident on which chunk, and
the profiled DoP scaling supplies the action's elasticity table.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.action import TableElasticity
from repro.core.managers.gpu import ServiceSpec
from repro.models.model import build_model
from repro.serving.engine import Engine, GenerationConfig


@dataclasses.dataclass
class RewardService:
    """A deployable scoring service (LLM-as-judge / teacher log-prob)."""

    name: str
    cfg: ModelConfig
    engine: Engine
    state_gb: float

    def score(self, tokens: jnp.ndarray) -> jnp.ndarray:
        return self.engine.score({"tokens": tokens})

    def spec(self) -> ServiceSpec:
        return ServiceSpec(self.name, self.state_gb, dops=(1, 2, 4, 8))

    # -- profiled elasticity (paper §4.1: profiled in advance) --------------
    @staticmethod
    def profiled_elasticity() -> TableElasticity:
        """TP scaling efficiency measured on teacher-model inference."""
        return TableElasticity(table=((1, 1.0), (2, 0.92), (4, 0.81), (8, 0.62)))


def deploy_reward_service(
    name: str, cfg: ModelConfig, key: Optional[jax.Array] = None
) -> RewardService:
    api = build_model(cfg)
    if key is None:  # crc32, unlike hash(), is the same in every process
        key = jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31)
    params = api.init(key)
    engine = Engine(api, params, GenerationConfig(max_new_tokens=8, cache_len=128))
    n_params = api.param_count()
    state_gb = n_params * 2 / 1e9  # bf16 weights
    return RewardService(name=name, cfg=cfg, engine=engine, state_gb=max(0.5, state_gb))
