"""Live mode on real devices: payload failures, warm-up, pool placement,
and the one-process rule for the accelerator.

Runs on the CPU (interpret-mode kernels).  A test that needs more than
the one host device this process has runs a child with its own
``--xla_force_host_platform_device_count``.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.core import scenarios
from repro.core.live import (
    LiveEventLoop,
    LiveModeError,
    LiveOrchestrator,
    kernel_payload_factory,
    run_live_scenario,
    warm_devices,
)
from repro.core.scenarios import ActionTemplate
from repro.core.scheduler import ElasticScheduler
from repro.launch.compilation import CompileCounter

REPO = Path(__file__).resolve().parent.parent


def _run_child(code: str, **env) -> str:
    full_env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu", **env)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], env=full_env,
                         capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def _raising_on(pool: str):
    """A payload factory whose actions on ``pool`` raise."""
    def factory(template: ActionTemplate):
        def fn() -> None:
            if template.rtype == pool:
                raise FloatingPointError(f"kernel on {pool} failed")
        return fn
    return factory


def test_payload_that_raises_fails_its_action():
    spec = scenarios.live_smoke_spec()
    compiled = scenarios.compile_scenario(spec, time_scale=0.02)
    loop = LiveEventLoop()
    orch = LiveOrchestrator(scenarios.build_managers(spec, loop), loop=loop,
                            policy=ElasticScheduler(), incremental=True)
    scenarios.install_scenario(compiled, orch, payload=_raising_on("dev1"))
    orch.run(until=60.0)
    orch.close()
    records = orch.telemetry.records
    failed = [r for r in records if r.failed]
    assert failed and all(r.trajectory_id.startswith("k1-") for r in failed)
    assert len(orch.payload_errors) == len(failed)
    assert all(isinstance(e, FloatingPointError) for _, e in orch.payload_errors)
    # the other pools ran to completion
    assert all(not r.failed for r in records if not r.trajectory_id.startswith("k1-"))


def test_payload_that_raises_fails_the_run(monkeypatch):
    import repro.core.live as live

    monkeypatch.setattr(live, "warm_devices", lambda devices: None)
    monkeypatch.setattr(live, "kernel_payload_factory",
                        lambda devices, pool_device, placements: _raising_on("dev2"))
    compiled = scenarios.compile_scenario(scenarios.live_smoke_spec(), time_scale=0.02)
    with pytest.raises(LiveModeError, match="FloatingPointError"):
        run_live_scenario(compiled, devices=["unused"], wall_limit_s=60.0)


def test_warm_up_compiles_the_payload_shape():
    """After warm-up, the timed payload compiles nothing."""
    devs = jax.devices()[:1]
    warm_devices(devs)
    placements = {}
    fn = kernel_payload_factory(devs, {"dev0": 0}, placements)(
        ActionTemplate(name="kernel", rtype="dev0", units=(1,), base_duration=0.0))
    with CompileCounter() as cc:
        fn()
    assert (cc.lowered, cc.compiled) == (0, 0)
    assert placements == {"dev0": {devs[0]}}


def test_compile_counter_sees_a_new_shape():
    import jax.numpy as jnp

    with CompileCounter() as cc:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((3, 5, 7)))
    assert cc.lowered >= 1


def test_pools_map_round_robin_onto_fewer_devices():
    """4 pools on 2 devices: pool k's kernel output is on device k % 2,
    and the live launch order still equals the sim's."""
    line = _run_child("""
        import json
        from repro.core import scenarios
        from repro.core.live import live_devices, run_live_scenario
        from repro.core.orchestrator import Orchestrator
        from repro.core.simulator import EventLoop

        devs = live_devices(2)
        spec = scenarios.live_smoke_spec(n_pools=4)
        compiled = scenarios.compile_scenario(spec, time_scale=0.02)
        loop = EventLoop()
        sim = Orchestrator(scenarios.build_managers(spec, loop), loop=loop,
                           policy=scenarios.build_policy(spec), incremental=True)
        scenarios.install_scenario(compiled, sim)
        sim.run()
        live = run_live_scenario(compiled, devices=devs, wall_limit_s=60.0)
        print(json.dumps({
            "placement": {p: sorted(d.id for d in ds)
                          for p, ds in live.payload_devices.items()},
            "trace_equal": scenarios.structural_trace(live.telemetry.records)
                           == scenarios.structural_trace(sim.telemetry.records),
        }))
    """)
    got = json.loads(line)
    assert got["placement"] == {"dev0": [0], "dev1": [1], "dev2": [0], "dev3": [1]}
    assert got["trace_equal"]


def test_process_transport_spawns_once_jax_is_imported():
    from repro.core.remote import default_start_method

    assert "jax" in sys.modules
    assert default_start_method() == "spawn"


def test_shard_worker_never_imports_jax():
    """A spawned shard worker re-imports only this chain; it must not
    reach for the accelerator the parent holds."""
    line = _run_child("""
        import sys
        import repro.core, repro.core.remote, repro.core.transport
        print("jax" in sys.modules)
    """)
    assert line == "False"
