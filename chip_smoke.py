#!/usr/bin/env python3
"""Chip smoke: the agentic-RL loop and live mode, on a TPU.

    python chip_smoke.py               # one chip: GRPO phase, then live phase
    python chip_smoke.py --four-chips  # a 4-chip host: the pinned-pool fleet only

GRPO phase.  The paper's Figure-2 loop through ``LiveGrpoDriver``: a
smollm-360m policy rolls out groups of completions, every completion is
scored by a llama3.2-1b judge as an ARL-Tangram action on the
accelerator pool, and a GRPO update follows.  Both models run at their
full published width with random weights from a fixed seed.  Checks:
every action completes, loss and rewards are finite, rewards differ,
each reward matches a direct batched score of its sequence, and steps
after the first compile nothing.

Live phase.  The ``live_smoke`` scenario through ``run_live_scenario``,
its pools mapped round-robin onto the chips, each action running the
compiled ``rmsnorm`` kernel.  Check: the launch order per pool equals
the simulator's.  ``--four-chips`` runs it with one pool per chip and
checks that each pool's output is on its own chip.

The script runs in one process and uses the chip only from there.  It
exits 1, printing no result, when JAX finds no TPU: it never falls back
to the CPU.  Wall times it prints are bring-up readings, not benchmark
numbers.  The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

POLICY = "smollm-360m"
JUDGE = "llama3.2-1b"
#: max |reward - direct score| / max(1, |direct score|): bf16 activations,
#: batched against single-sequence programs
REWARD_RTOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(tag: str, **fields) -> None:
    print(f"[{tag}] " + json.dumps(fields, default=str), flush=True)


def grpo_phase(policy_cfg, judge_cfg, *, steps: int = 3, group: int = 4,
               n_prompts: int = 4, prompt_len: int = 8, seed: int = 0) -> dict:
    """``steps`` GRPO steps with judge rewards scheduled by Tangram."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cluster import paper_testbed
    from repro.launch.compilation import CompileCounter
    from repro.rl.driver import LiveGrpoDriver, build_tangram
    from repro.training.grpo import token_logprobs

    t0 = time.perf_counter()
    driver = LiveGrpoDriver(policy_cfg, judge_cfg, group_size=group, seed=seed)
    jax.block_until_ready((driver.state.params, driver.judge.engine.params))
    log("grpo", event="init", policy=policy_cfg.name, judge=judge_cfg.name,
        policy_params=driver.api.param_count(),
        judge_params=driver.judge.engine.api.param_count(),
        init_wall_s_bring_up=time.perf_counter() - t0)

    rng = np.random.default_rng(seed)
    n_actions = n_prompts * group
    reports = []
    for step in range(1, steps + 1):
        tangram = build_tangram(paper_testbed(cpu_nodes=1, gpu_nodes=1),
                                services=["judge"],
                                service_state_gb=driver.judge.state_gb)
        prompts = rng.integers(0, policy_cfg.vocab_size,
                               size=(n_prompts, prompt_len)).astype(np.int32)
        t0 = time.perf_counter()
        with CompileCounter() as cc:
            rep = driver.run_step(prompts, tangram)
        wall = time.perf_counter() - t0
        tel = tangram.telemetry
        log("grpo", step=step, loss=rep.grpo_loss, mean_reward=rep.mean_reward,
            reward_spread=float(np.ptp(rep.rewards)), actions=len(tel.records),
            failed=sum(r.failed for r in tel.records), retries=tel.retries,
            timeouts=tel.timeouts, lowered=cc.lowered, compiled=cc.compiled,
            step_wall_s_bring_up=wall, rollout_wall_s_bring_up=rep.rollout_wall_s,
            update_wall_s_bring_up=rep.update_wall_s)
        check(len(tel.records) == n_actions,
              f"step {step}: {len(tel.records)} of {n_actions} actions recorded")
        check(not any(r.failed for r in tel.records) and tel.retries == 0
              and tel.timeouts == 0, f"step {step}: an action failed or retried")
        check(np.isfinite(rep.grpo_loss), f"step {step}: loss {rep.grpo_loss}")
        check(bool(np.all(np.isfinite(rep.rewards))), f"step {step}: non-finite reward")
        check(float(np.ptp(rep.rewards)) > 0, f"step {step}: all rewards equal")
        if step > 1:
            check(cc.lowered == 0, f"step {step} lowered {cc.lowered} new programs")
        reports.append(rep)

    judge_api = driver.judge.engine.api
    direct = jax.jit(lambda p, t: jnp.sum(token_logprobs(p, t, judge_api), axis=-1))
    worst = 0.0
    for rep in reports:
        ref = np.asarray(direct(driver.judge.engine.params, jnp.asarray(rep.sequences)))
        err = np.abs(rep.rewards - ref) / np.maximum(1.0, np.abs(ref))
        worst = max(worst, float(err.max()))
    log("grpo", event="reward_vs_direct_score", max_rel_err=worst, rtol=REWARD_RTOL)
    check(worst <= REWARD_RTOL, f"reward differs from direct score by {worst}")
    return {"steps": steps, "actions_per_step": n_actions,
            "final_loss": reports[-1].grpo_loss, "reward_max_rel_err": worst}


def live_phase(devices: list, *, n_pools: int = 4, time_scale: float = 0.25,
               pinned: bool = False) -> dict:
    """The live_smoke scenario on ``devices``; its structural trace must
    equal the simulator's.  ``pinned``: one pool per device, and each
    pool's output must be on its own device."""
    from repro.core import scenarios
    from repro.core.live import run_live_scenario
    from repro.core.orchestrator import Orchestrator
    from repro.core.simulator import EventLoop

    spec = scenarios.live_smoke_spec(n_pools=n_pools)
    compiled = scenarios.compile_scenario(spec, time_scale=time_scale)
    loop = EventLoop()
    sim = Orchestrator(scenarios.build_managers(spec, loop), loop=loop,
                       policy=scenarios.build_policy(spec), incremental=True,
                       fair_share=scenarios.build_fair_share(spec))
    scenarios.install_scenario(compiled, sim)
    sim.run()
    sim_trace = scenarios.structural_trace(sim.telemetry.records)
    sim.close()

    t0 = time.perf_counter()
    live = run_live_scenario(compiled, devices=devices, wall_limit_s=300.0)
    wall = time.perf_counter() - t0
    live_trace = scenarios.structural_trace(live.telemetry.records)
    placement = {pool: sorted(d.id for d in devs)
                 for pool, devs in sorted(live.payload_devices.items())}
    log("live", pools=n_pools, devices=[d.id for d in devices],
        records=len(live.telemetry.records), sim_records=len(sim.telemetry.records),
        failed=sum(r.failed for r in live.telemetry.records),
        trace_equal=live_trace == sim_trace, placement=placement,
        wall_s_bring_up=wall)
    check(len(live.telemetry.records) == len(sim.telemetry.records),
          "live and sim record counts differ")
    check(live_trace == sim_trace, "live launch order differs from the sim's")
    check(len(placement) == n_pools, f"outputs seen for {len(placement)} of {n_pools} pools")
    for k in range(n_pools):
        want = devices[k % len(devices)].id
        check(placement[f"dev{k}"] == [want],
              f"pool dev{k} output on {placement[f'dev{k}']}, expected [{want}]")
    if pinned:
        check(len({tuple(v) for v in placement.values()}) == n_pools,
              "two pools share a device")
    return {"records": len(live.telemetry.records), "trace_equal": True,
            "placement": placement}


def kernel_is_compiled(device) -> bool:
    """The live payload's kernel lowers to a Mosaic call on ``device``."""
    import jax
    import jax.numpy as jnp

    from repro.core.live import PAYLOAD_SHAPE
    from repro.kernels.ops import rmsnorm_op

    x = jax.device_put(jnp.ones(PAYLOAD_SHAPE, jnp.float32), device)
    w = jax.device_put(jnp.ones(PAYLOAD_SHAPE[-1:], jnp.float32), device)
    text = rmsnorm_op.lower(x, w, interpret=False).compile().as_text()
    return "tpu_custom_call" in text


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet phase: one live pool per chip "
                         "(needs a host with 4 chips)")
    args = ap.parse_args()
    try:
        from repro.launch.compilation import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 1

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees platform "
              f"{devices[0].platform!r}); refusing to run on it", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    dev = devices[0]
    log("device", platform=dev.platform, kind=dev.device_kind, count=len(devices),
        jax=jax.__version__, compile_cache=cache_dir)

    try:
        check(kernel_is_compiled(dev), "rmsnorm did not lower to a Mosaic kernel")
        if args.four_chips:
            check(len(devices) >= 4, f"--four-chips needs 4 chips, found {len(devices)}")
            live_phase(devices[:4], n_pools=4, pinned=True)
        else:
            from repro.configs import get_config

            grpo_phase(get_config(POLICY), get_config(JUDGE))
            stats = dev.memory_stats() or {}
            log("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                bytes_limit=stats.get("bytes_limit"))
            live_phase(devices, n_pools=4)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
