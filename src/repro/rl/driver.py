"""Wiring helpers: build ARL-Tangram or baseline stacks for a workload,
run steps, and the live GRPO-with-Tangram loop used by the e2e example.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.baselines import (
    ServerlessLlmSystem,
    StaticGpuServiceSystem,
    TrajectoryStaticCpuSystem,
    UnmanagedApiSystem,
)
from repro.core.cluster import ClusterSpec, paper_testbed
from repro.core.fairqueue import FairSharePolicy
from repro.core.managers.basic import BasicResourceManager
from repro.core.managers.cpu import CpuManager
from repro.core.managers.gpu import GpuManager, ServiceSpec
from repro.core.orchestrator import Orchestrator, SchedulingPolicy
from repro.core.simulator import EventLoop
from repro.core.tangram import Tangram
from repro.rl.rollout import RolloutRunner, StepStats
from repro.rl.tasks import TrajectorySpec, workload_services


def build_managers(
    cluster: ClusterSpec,
    services: Sequence[str] = (),
    service_state_gb: float = 40.0,
    loop: Optional[EventLoop] = None,
) -> Tuple[Dict[str, object], EventLoop]:
    loop = loop or EventLoop()
    managers: Dict[str, object] = {}
    if cluster.cpu_nodes:
        managers["cpu"] = CpuManager(cluster.cpu_nodes)
    if cluster.gpu_nodes:
        managers["gpu"] = GpuManager(
            cluster.gpu_nodes,
            [ServiceSpec(s, service_state_gb) for s in services],
        )
    for api in cluster.apis:
        managers[api.name] = BasicResourceManager(api, loop.clock)
    return managers, loop


def build_orchestrator(
    cluster: ClusterSpec,
    policy: Optional[SchedulingPolicy] = None,
    services: Sequence[str] = (),
    service_state_gb: float = 40.0,
    loop: Optional[EventLoop] = None,
    incremental: bool = True,
    fair_share: Optional[FairSharePolicy] = None,
    shards: Optional[int] = None,
) -> Orchestrator:
    """One orchestrator, swappable policy (ElasticScheduler by default,
    or the FCFS/static baseline policies for ablations).  ``fair_share``
    turns on multi-tenant weighted queueing across task_ids; ``shards``
    switches the round loop to the plan/commit engine (repro.core.shards)
    with that many parallel planners."""
    managers, loop = build_managers(cluster, services, service_state_gb, loop)
    return Orchestrator(
        managers, loop=loop, policy=policy, incremental=incremental,
        fair_share=fair_share, shards=shards,
    )


def build_tangram(
    cluster: ClusterSpec,
    services: Sequence[str] = (),
    service_state_gb: float = 40.0,
    loop: Optional[EventLoop] = None,
    depth: int = 2,
    fair_share: Optional[FairSharePolicy] = None,
) -> Tangram:
    from repro.core.scheduler import ElasticScheduler

    managers, loop = build_managers(cluster, services, service_state_gb, loop)
    tg = Tangram(managers, loop=loop, fair_share=fair_share)
    tg.scheduler = ElasticScheduler(depth=depth, history=tg.history)
    return tg


def run_tangram_step(
    trajectories: Sequence[TrajectorySpec],
    cluster: Optional[ClusterSpec] = None,
    depth: int = 2,
) -> Tuple[StepStats, Tangram]:
    cluster = cluster or paper_testbed()
    services = workload_services(trajectories)
    tg = build_tangram(cluster, services, depth=depth)
    runner = RolloutRunner({"*": tg, "cpu": tg, "gpu": tg,
                            **{a.name: tg for a in cluster.apis}}, tg.loop)
    stats = runner.run_step(trajectories)
    return stats, tg


def run_baseline_step(
    trajectories: Sequence[TrajectorySpec],
    cluster: Optional[ClusterSpec] = None,
    gpu_baseline: str = "static",  # "static" | "serverless"
) -> Tuple[StepStats, Dict[str, object]]:
    """Workload-specific baselines (§6.1): k8s pods for CPU, SGLang-style
    static services (or ServerlessLLM) for GPU, unmanaged API calls."""
    cluster = cluster or paper_testbed()
    loop = EventLoop()
    services = workload_services(trajectories)
    systems: Dict[str, object] = {}
    cpu_sys = TrajectoryStaticCpuSystem(total_cores=cluster.total_cores, loop=loop)
    systems["cpu"] = cpu_sys
    if services:
        if gpu_baseline == "static":
            per = max(1, cluster.total_devices // 4 // max(1, len(services)))
            gpu_sys = StaticGpuServiceSystem({s: per for s in services}, tp=4, loop=loop)
        else:
            gpu_sys = ServerlessLlmSystem(
                cluster.total_devices, {s: 40.0 for s in services}, loop=loop
            )
        systems["gpu"] = gpu_sys
    api_sys = UnmanagedApiSystem(rate_limit=64, loop=loop)
    for api in cluster.apis:
        systems[api.name] = api_sys
    systems["*"] = cpu_sys
    runner = RolloutRunner(systems, loop)
    stats = runner.run_step(trajectories)
    return stats, systems


# ---------------------------------------------------------------------------
# Live end-to-end: GRPO training with rewards through ARL-Tangram
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LiveStepReport:
    grpo_loss: float
    mean_reward: float
    mean_act: float
    rollout_wall_s: float
    update_wall_s: float
    rewards: np.ndarray  # [B*G] judge score of each sequence
    sequences: np.ndarray  # [B*G, S0 + new] prompt + completion tokens


class LiveGrpoDriver:
    """Trains a small policy with GRPO; reward computation executes REAL
    JAX inference while resource occupancy/latency is accounted through
    ARL-Tangram's scheduler (measured durations feed the DES).  Every
    program compiles on the first step; later steps of the same shape
    compile nothing."""

    def __init__(self, policy_cfg, judge_cfg, group_size: int = 4, seed: int = 0):
        import jax
        import jax.numpy as jnp

        from repro.models import build_model
        from repro.serving.engine import Engine, GenerationConfig
        from repro.serving.reward_service import deploy_reward_service
        from repro.training import AdamWConfig, init_train_state, make_grpo_step
        from repro.training.grpo import token_logprobs

        self.jax, self.jnp = jax, jnp
        self.api = build_model(policy_cfg)
        self.state = init_train_state(self.api, jax.random.PRNGKey(seed))
        self.group_size = group_size
        self.gen_cfg = GenerationConfig(max_new_tokens=16, temperature=1.0, cache_len=64)
        self.engine = Engine(self.api, self.state.params, self.gen_cfg)
        self.judge = deploy_reward_service("judge", judge_cfg)
        # the old state is dead after the update: donate its buffers
        self.grpo_step = jax.jit(
            make_grpo_step(self.api, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=100)),
            donate_argnums=0,
        )
        self._logprobs = jax.jit(lambda p, t: token_logprobs(p, t, self.api))
        self._key = jax.random.PRNGKey(seed + 1)

    def run_step(self, prompts: np.ndarray, tangram: Tangram) -> LiveStepReport:
        """prompts: [B, S0] int32.  One rollout + reward + GRPO update."""
        jnp = self.jnp
        t0 = time.perf_counter()
        B, S0 = prompts.shape
        G = self.group_size
        self.engine.params = self.state.params
        # group rollouts: repeat each prompt G times
        rep = np.repeat(prompts, G, axis=0)
        self._key, sub = self.jax.random.split(self._key)
        gen_toks, gen_logps = self.engine.generate({"tokens": jnp.asarray(rep)}, key=sub)
        seqs = np.concatenate([rep, np.asarray(gen_toks)], axis=1)
        rollout_s = time.perf_counter() - t0

        # rewards through Tangram: real judge scoring, measured duration
        rewards = np.zeros(B * G, np.float32)

        def score_fn(idx):
            def run(dop: int) -> float:
                t = time.perf_counter()
                s = float(self.judge.score(jnp.asarray(seqs[idx : idx + 1]))[0])
                rewards[idx] = s
                return time.perf_counter() - t

            return run

        from repro.core.action import Action, ResourceRequest
        from repro.rl.tasks import GPU_ELASTICITY

        futs = []
        for i in range(B * G):
            a = Action(
                name="reward:judge",
                cost={"gpu": ResourceRequest("gpu", (1, 2, 4, 8))},
                key_resource="gpu",
                elasticity=GPU_ELASTICITY,
                base_duration=0.05,
                duration_sampler=score_fn(i),
                service="judge",
                task_id="live",
                trajectory_id=f"live-{i}",
            )
            futs.append(tangram.submit(a))
        tangram.run()
        mean_act = tangram.telemetry.mean_act()

        # GRPO update (real)
        from repro.training import group_advantages

        adv = group_advantages(jnp.asarray(rewards.reshape(B, G))).reshape(-1)
        tokens = jnp.asarray(seqs)
        old_logp = self._logprobs(self.state.params, tokens)
        mask = np.zeros((B * G, seqs.shape[1] - 1), np.float32)
        mask[:, S0 - 1 :] = 1.0  # only generated positions train
        batch = {
            "tokens": tokens,
            "mask": jnp.asarray(mask),
            "advantages": adv,
            "old_logp": old_logp,
            "ref_logp": old_logp,
        }
        t1 = time.perf_counter()
        self.state, metrics = self.grpo_step(self.state, batch)
        loss = float(metrics["loss"])
        update_s = time.perf_counter() - t1
        return LiveStepReport(
            grpo_loss=loss,
            mean_reward=float(rewards.mean()),
            mean_act=mean_act,
            rollout_wall_s=rollout_s,
            update_wall_s=update_s,
            rewards=rewards,
            sequences=seqs,
        )
