"""End-to-end agentic RL: GRPO training with rewards through ARL-Tangram.

A tiny policy model generates groups of completions; a (real, JAX)
judge model scores them — each scoring call is an ARL-Tangram *action*
on the GPU pool with elastic DoP and EOE service caching; group-relative
advantages drive a GRPO update.  This is the paper's Figure-2 loop at
laptop scale with real compute in the reward path.

Run: PYTHONPATH=src python examples/agentic_rl_e2e.py --steps 5
"""

import argparse

import numpy as np

from repro.configs import get_config
from repro.core.cluster import paper_testbed
from repro.launch.compilation import enable_compile_cache
from repro.rl.driver import LiveGrpoDriver, build_tangram


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--group", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    policy_cfg = get_config("smollm-360m").reduced()
    judge_cfg = get_config("llama3.2-1b").reduced()
    driver = LiveGrpoDriver(policy_cfg, judge_cfg, group_size=args.group)

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        cluster = paper_testbed(cpu_nodes=1, gpu_nodes=1)
        tangram = build_tangram(cluster, services=["judge"], service_state_gb=1.0)
        prompts = rng.integers(0, policy_cfg.vocab_size, size=(args.batch, 8)).astype(
            np.int32
        )
        rep = driver.run_step(prompts, tangram)
        gpu = tangram.managers["gpu"]
        print(
            f"step {step}: grpo_loss={rep.grpo_loss:+.4f} "
            f"mean_reward={rep.mean_reward:.2f} mean_ACT={rep.mean_act:.3f}s "
            f"EOE_hits={gpu.stats['hits']}/{gpu.stats['hits']+gpu.stats['misses']} "
            f"rollout={rep.rollout_wall_s:.1f}s update={rep.update_wall_s:.1f}s",
            flush=True,
        )


if __name__ == "__main__":
    main()
