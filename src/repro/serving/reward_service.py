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
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.action import TableElasticity
from repro.core.managers.gpu import ServiceSpec
from repro.models.model import build_model
from repro.serving.engine import Engine, GenerationConfig


@jax.jit
def _fold(acc: jax.Array, c: jax.Array) -> jax.Array:
    """Running routing counters [expert layers, 3]: assignments and drops
    summed, the largest load of one held expert kept as the max."""
    return jnp.stack([acc[:, 0] + c[:, 0], jnp.maximum(acc[:, 1], c[:, 1]), acc[:, 2] + c[:, 2]],
                     axis=-1)


@dataclasses.dataclass
class RewardService:
    """A deployable scoring service (LLM-as-judge / teacher log-prob)."""

    name: str
    cfg: ModelConfig
    engine: Engine
    state_gb: float
    #: routing counters summed over the calls since the last read, on the
    #: device (the largest load kept as the max), and the number of calls
    counters: Optional[jax.Array] = None
    calls: int = 0

    def score(self, tokens: jnp.ndarray) -> jnp.ndarray:
        scores, counters = self.engine._score(self.engine.params, tokens, None)
        if counters is not None:
            self.counters = counters if self.counters is None else _fold(self.counters, counters)
            self.calls += 1
        return scores

    def read_counters(self) -> Optional[Dict[str, object]]:
        """The routing counters of the calls since the last read, on the
        host, per expert layer: assignments that landed on held experts
        (summed over calls), the largest load of one held expert (max over
        calls), and assignments dropped (summed).  None for a judge without
        routed experts."""
        if self.counters is None:
            return None
        c = np.asarray(jax.device_get(self.counters))  # [expert layers, 3]
        out = {
            "judge_calls": self.calls,
            "held_experts": self.cfg.held_experts[1],
            "held_assignments": c[:, 0],
            "held_max_load": c[:, 1],
            "dropped_assignments": c[:, 2],
        }
        self.counters, self.calls = None, 0
        return out

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
