"""Spans and real-clock action stamps: the recorder's tree, one GRPO step
of the live driver, the clock a profiler trace shares with the spans, and
scheduling time that leaves out the payloads a round runs."""

import glob
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.action import Action, fixed
from repro.core.cluster import ApiResourceSpec, CpuNodeSpec
from repro.core.managers.basic import BasicResourceManager
from repro.core.managers.cpu import CpuManager
from repro.core.orchestrator import Orchestrator
from repro.core.simulator import EventLoop
from repro.core.telemetry import SpanLog, span

#: the benchmark harness's own span names, which no program span may take
HARNESS_SPANS = {"step", "rollout", "reward", "judge_score", "update"}


def _busy(seconds: float) -> None:
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


def test_span_tree_parents_and_self_time():
    with SpanLog() as log:
        with span("a"):
            with span("a.b"):
                _busy(0.002)
            with span("a.c"):
                with span("a.c.d"):
                    _busy(0.001)
            _busy(0.003)
        with span("e"):
            pass
    with span("after"):  # no active log: nothing recorded
        pass
    names = [s.name for s in log.spans]
    assert names == ["a", "a.b", "a.c", "a.c.d", "e"]
    assert [s.parent for s in log.spans] == [None, 0, 0, 2, None]
    assert log.children(0) == [1, 2] and log.children(2) == [3] and log.children(4) == []
    for s in log.spans:
        assert s.start_ns <= s.end_ns
        if s.parent is not None:
            p = log.spans[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
    a, b, c = log.spans[:3]
    assert log.self_ns(0) == a.dur_ns - b.dur_ns - c.dur_ns
    assert log.self_ns(0) >= 3_000_000 and b.dur_ns >= 2_000_000
    assert log.self_ns(3) == log.spans[3].dur_ns >= 1_000_000
    assert log.named("a.c") == [c]


def test_nested_logs_keep_their_own_spans():
    with SpanLog() as outer:
        with span("x"):
            with SpanLog() as inner:
                with span("y"):
                    pass
            with span("z"):
                pass
    assert [s.name for s in inner.spans] == ["y"] and inner.spans[0].parent is None
    assert [(s.name, s.parent) for s in outer.spans] == [("x", None), ("z", 0)]


# ---------------------------------------------------------------------------
# one step of the live GRPO driver
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_step():
    from repro.configs import get_config
    from repro.core.cluster import paper_testbed
    from repro.rl.driver import LiveGrpoDriver, build_tangram

    cfg = get_config("smollm-360m").reduced()
    driver = LiveGrpoDriver(cfg, cfg, group_size=2, seed=0)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    tangram = build_tangram(paper_testbed(cpu_nodes=1, gpu_nodes=1), services=["judge"],
                            service_state_gb=driver.judge.state_gb)
    report = driver.run_step(prompts, tangram)
    return driver, tangram, report


def test_step_records_the_span_tree(one_step):
    driver, tangram, rep = one_step
    spans = rep.spans
    names = [s.name for s in spans]
    assert spans[0].name == "grpo.step" and spans[0].parent is None
    assert not HARNESS_SPANS & set(names)

    def parent_of(name):
        return {spans[s.parent].name for s in spans if s.name == name}

    for child in ("grpo.rollout", "grpo.reward", "grpo.update"):
        assert names.count(child) == 1 and parent_of(child) == {"grpo.step"}
    for child in ("grpo.advantages", "grpo.old_logp", "grpo.apply"):
        assert names.count(child) == 1 and parent_of(child) == {"grpo.update"}
    n = driver.gen_cfg.max_new_tokens
    # one dispatch per token, sampling inside it: no eager sampling, no join
    assert names.count("engine.prefill") == 1 and names.count("engine.decode") == n
    assert names.count("engine.sample") == names.count("engine.collect") == 0
    for name in ("engine.prefill", "engine.decode"):
        assert parent_of(name) == {"grpo.rollout"}
    # one judge call per action, each inside Tangram's run()
    assert names.count("judge.call") == len(tangram.telemetry.records) == rep.sequences.shape[0]
    assert parent_of("judge.call") == {"grpo.reward"}
    # the report's wall times are its spans'
    (rollout,) = [s for s in spans if s.name == "grpo.rollout"]
    (update,) = [s for s in spans if s.name == "grpo.update"]
    assert rep.rollout_wall_s == rollout.dur_ns / 1e9
    assert rep.update_wall_s == update.dur_ns / 1e9


def test_step_actions_carry_real_clock_stamps(one_step):
    _, tangram, rep = one_step
    assert len(rep.actions) == rep.sequences.shape[0]
    step = rep.spans[0]
    for submit, start, end in rep.actions:
        assert step.start_ns <= submit <= start <= end <= step.end_ns
    # Tangram runs the judge in its round, one payload after another
    runs = sorted((start, end) for _, start, end in rep.actions)
    assert all(b[0] >= a[1] for a, b in zip(runs, runs[1:]))
    # each payload holds its judge call
    calls = sorted((s.start_ns, s.end_ns) for s in rep.spans if s.name == "judge.call")
    assert all(s0 <= c0 and c1 <= e0 for (s0, e0), (c0, c1) in zip(runs, calls))
    assert rep.mean_act == statistics.fmean(e - s for s, _, e in rep.actions) / 1e9
    assert [(r.submit_ns, r.start_ns, r.end_ns) for r in tangram.telemetry.records] == rep.actions


def test_step_programs_are_named(one_step):
    driver, _, _ = one_step
    named = {
        "engine_prefill": driver.engine._prefill,
        "engine_token": driver.engine._token,
        "engine_score": driver.judge.engine._score,
        "grpo_old_logp": driver._logprobs,
        "grpo_step": driver.grpo_step,
    }
    for name, fn in named.items():
        assert fn.__name__ == name


# ---------------------------------------------------------------------------
# the clock: spans land on their profiler-trace copies
# ---------------------------------------------------------------------------


def test_spans_land_on_their_trace_annotations(tmp_path):
    from jax.profiler import ProfileData

    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with SpanLog() as log:
            for _ in range(8):
                with span("clock.outer"):
                    with span("clock.inner"):
                        jax.block_until_ready(x @ x)
                    _busy(0.0005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    copies = sorted((int(e.start_ns), int(e.duration_ns), e.name)
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for e in line.events
                    if e.name.startswith("clock."))
    assert [n for _, _, n in copies] == [s.name for s in log.spans]
    offset = copies[0][0] - log.spans[0].start_ns
    for (start, dur, _), s in zip(copies, log.spans):
        assert abs(start - offset - s.start_ns) < 200_000
        assert abs(start + dur - offset - s.end_ns) < 200_000


# ---------------------------------------------------------------------------
# scheduling time leaves out the payloads a round runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [None, 2], ids=["serial", "sharded"])
def test_sampler_time_is_not_scheduling_time(shards):
    loop = EventLoop()
    managers = {
        "cpu": CpuManager([CpuNodeSpec("n0", cores=8)]),
        "api": BasicResourceManager(
            ApiResourceSpec("api", mode="quota", quota=4, period_s=5.0), loop.clock),
    }
    orch = Orchestrator(managers, loop=loop, shards=shards)

    def sleep_50ms(units: int) -> float:
        time.sleep(0.05)
        return 1.0

    # two partitions dirty in one round: the sharded path commits both
    for rtype in ("cpu", "api"):
        orch.submit(Action(name=f"{rtype}:sleep", cost={rtype: fixed(rtype, 1)},
                           duration_sampler=sleep_50ms))
    orch.run()
    recs = orch.telemetry.records
    assert len(recs) == 2 and not any(r.failed for r in recs)
    for r in recs:
        assert r.submit_ns <= r.start_ns and r.end_ns - r.start_ns >= 50_000_000
    assert orch.telemetry.sched_wall_s < 0.05
    if shards:
        assert orch.stats["sharded_rounds"] >= 1


def test_live_payload_is_stamped_on_the_same_clock():
    from repro.core.live import LiveEventLoop, LiveOrchestrator

    loop = LiveEventLoop()
    orch = LiveOrchestrator({"cpu": CpuManager([CpuNodeSpec("n0", cores=8)])}, loop=loop)
    before = time.perf_counter_ns()
    orch.submit(Action(name="live", cost={"cpu": fixed("cpu", 1)}, base_duration=0.02,
                       fn=lambda: time.sleep(0.02)))
    orch.run()
    orch.close()
    (r,) = orch.telemetry.records
    assert not r.failed
    assert before <= r.submit_ns <= r.start_ns
    assert r.end_ns - r.start_ns >= 20_000_000 and r.end_ns <= time.perf_counter_ns()


def test_des_action_without_payload_has_no_payload_stamps():
    loop = EventLoop()
    orch = Orchestrator({"cpu": CpuManager([CpuNodeSpec("n0", cores=8)])}, loop=loop)
    orch.submit(Action(name="tool", cost={"cpu": fixed("cpu", 1)}, base_duration=1.0))
    orch.run()
    (r,) = orch.telemetry.records
    assert r.submit_ns is not None and r.start_ns is None and r.end_ns is None
