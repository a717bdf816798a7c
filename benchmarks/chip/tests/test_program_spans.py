"""The readers of the program's own spans and action stamps, on a hand-made
run with known answers: the offset that puts a step's spans on the
trace's clock (``trace.traced_steps``), the None when the offsets at its
start and end disagree, and each reader's value."""

import dataclasses
from types import SimpleNamespace

from chip import trace
from chip.harness import Run, StepRecord
from chip.metrics import act_wait_ms, update_idle_ms
from repro.core.telemetry import Span

MS = 1_000_000
#: the trace's clock less the program's (any constant does)
OFF = -99_000_000_000


def _step(t0_s: float, spans, actions) -> StepRecord:
    base = round(t0_s * 1e9)
    spans = [Span(n, base + a, base + b, p) for n, a, b, p in spans]
    actions = [(base + s, None if a is None else base + a, None if e is None else base + e)
               for s, a, e in actions]
    return StepRecord(t0=t0_s, t1=t0_s + 0.1, report=SimpleNamespace(spans=spans, actions=actions))


def _run(end_skew_ns: int = -3000) -> Run:
    s0 = _step(100.0, [("grpo.step", 10_000, 90 * MS, None),
                       ("grpo.rollout", 20_000, 40 * MS, 0),
                       ("engine.decode", 1 * MS, 2 * MS, 1),
                       ("engine.decode", 3 * MS, 4 * MS, 1),
                       ("grpo.update", 50 * MS, 80 * MS, 0)],
               [(0, 1 * MS, 2 * MS), (100, 3 * MS + 100, 4 * MS), (200, None, None)])
    s1 = _step(101.0, [("grpo.step", 10_000, 90 * MS, None),
                       ("engine.decode", 1 * MS, 3 * MS, 0),
                       ("grpo.update", 50 * MS, 60 * MS, 0)],
               [(0, 2 * MS, 3 * MS)])
    # not traced: its spans would add idle time if read
    s2 = _step(102.0, [("grpo.step", 10_000, 90 * MS, None),
                       ("engine.decode", 1 * MS, 11 * MS, 0),
                       ("grpo.update", 50 * MS, 60 * MS, 0)],
               [(0, 6 * MS, 7 * MS)])

    def on_trace(t_s: float, ns: int) -> int:
        return round(t_s * 1e9) + ns + OFF

    ops0 = [["fusion.1", on_trace(100.0, 1_500_000), 2 * MS],  # covers 1.5-3.5 ms of step 0
            ["fusion.2", on_trace(100.0, 60 * MS), 10 * MS],
            ["fusion.3", on_trace(101.0, 45 * MS), 20 * MS]]
    ops1 = [["busy", on_trace(100.0, 0), 3_000 * MS]]  # another device, always busy
    spans = [["step", on_trace(100.0, 2000), 100 * MS - 5000],
             ["rollout", on_trace(100.0, 30_000), 30 * MS],
             ["step", on_trace(101.0, 2000), 100 * MS - 2000 + end_skew_ns],
             ["judge_score", on_trace(101.0, 42 * MS), MS]]
    events = {"devices": {"/device:TPU:0": ops0, "/device:TPU:1": ops1}, "spans": spans}
    return Run(cell=None, setup_s=0.0, window_s=0.3, steps=[s0, s1, s2], compiles=0, work={},
               peak={}, trace={"steps": 2}, events=events)


def test_one_offset_puts_each_step_on_the_trace_clock():
    steps = trace.traced_steps(_run())
    assert [(rec.t0, off) for rec, off in steps] == [(100.0, OFF + 2000), (101.0, OFF + 2000)]


def test_offsets_that_disagree_give_none():
    # the offset at the start is OFF + 2000 ns; at the end, OFF + the skew
    ok, bad = _run(end_skew_ns=2000 + 499_000), _run(end_skew_ns=2000 - 501_000)
    assert trace.traced_steps(ok) is not None
    assert trace.traced_steps(bad) is None
    assert trace.idle_ms(bad, "engine.decode") is None and update_idle_ms.read(bad) is None


def test_update_idle_reads_device_zero_inside_the_update_span():
    # step 0: 30 ms span with 10 ms of ops; step 1: covered by an op
    assert update_idle_ms.read(_run()) == (20.0 + 0.0) / 2


def test_act_wait_is_the_mean_wait_over_the_window():
    # every step of the window, traced or not; an action that never started is left out
    assert act_wait_ms.read(_run()) == (1.0 + 3.0 + 2.0 + 6.0) / 4


def test_a_program_without_spans_or_stamps_reads_none():
    run = _run()
    for rec in run.steps:
        rec.report = SimpleNamespace(rewards=None)
    assert trace.idle_ms(run, "engine.decode") is None
    assert update_idle_ms.read(run) is None
    assert act_wait_ms.read(run) is None
    untraced = dataclasses.replace(_run(), trace=None, events=None)
    assert trace.idle_ms(untraced, "grpo.update") is None and update_idle_ms.read(untraced) is None
