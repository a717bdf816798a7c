"""Runs one cell once: set-up, a measured window of GRPO steps, the check.

The window drives the program's ``LiveGrpoDriver.run_step``, closed loop,
each step with a fresh ``build_tangram`` whose accelerator pool serves
the judge.  The harness measures from outside the program: it wraps the
driver's rollout (``engine.generate``), update (``_logprobs`` then
``grpo_step``), the judge's ``score`` and the Tangram's ``submit`` and
``run``, blocks on each one's outputs, and stamps it on the host clock
(and, in a traced run, as a profiler span).  A reward action's completion
time (ACT) runs from the step's first submit to the instant the judge
call that scored its row has its score on the host.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from chip import check, flops, spec, weights


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# records the metric readers take
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    rollout_s: float = 0.0
    update_s: float = 0.0
    reward_s: float = 0.0  # Tangram's run(), judge calls included
    judge_s: float = 0.0  # inside judge calls
    submit_t: Optional[float] = None
    acts: List[float] = dataclasses.field(default_factory=list)
    submitted: int = 0
    calls: List = dataclasses.field(default_factory=list)  # (tokens, scores)
    futures: List = dataclasses.field(default_factory=list)
    report: object = None
    tangram: object = None


@dataclasses.dataclass
class Run:
    cell: spec.Cell
    setup_s: float
    window_s: float
    steps: List[StepRecord]
    compiles: int
    work: Dict[str, float]  # model FLOPs of one step by phase, judge least time
    peak: Dict
    trace: Optional[Dict] = None  # trace.summarize(), plus the traced actions
    events: Optional[Dict] = None  # the trace as trace.load gives it


def percentile(xs, q: float) -> float:
    """Nearest rank: the smallest sample with at least q% at or below it."""
    s = sorted(xs)
    return s[max(0, math.ceil(q / 100 * len(s)) - 1)]


# ---------------------------------------------------------------------------
# the program, its weights and its traffic
# ---------------------------------------------------------------------------


def model_config(section: Dict):
    """The program's config for a model section: the registry entry with
    the section's ``as_run`` values; refuses one that still differs."""
    from repro.configs import get_config

    arch = spec.arch_of(section)
    base = get_config(section["registry"])
    as_run = section["as_run"]
    cfg = dataclasses.replace(base, **{k: as_run[k] for k in arch.KEYS})
    got = {k: arch.program_value(cfg, k) for k in arch.KEYS}
    bad = {k: (got[k], as_run[k]) for k in arch.KEYS if got[k] != as_run[k]}
    if bad:
        raise ValueError(f"{section['registry']}: program config differs from the file: {bad}")
    return cfg


def prompts(seed: int, step: int, traffic: Dict, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed) % 2**64, step])
    return rng.integers(0, vocab, size=(traffic["prompts_per_step"], traffic["prompt_len"]),
                        dtype=np.int64).astype(np.int32)


def _same_tree(ours, theirs, what: str) -> None:
    import jax

    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), ours)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), theirs)
    if a != b:
        raise ValueError(f"{what}: the program's parameter tree is not the benchmark's")


def build_driver(cell: spec.Cell, seed: int):
    """The program's driver, with the benchmark's weights and lengths."""
    import jax

    from repro.rl.driver import LiveGrpoDriver
    from repro.serving.engine import Engine, GenerationConfig
    from repro.training.optimizer import init_adamw
    from repro.training.train_step import TrainState

    pcfg, jcfg = model_config(cell.config["policy"]), model_config(cell.config["judge"])
    if jcfg.vocab_size < pcfg.vocab_size:
        raise ValueError("the judge's vocabulary does not cover the policy's tokens")
    t = cell.traffic
    driver = LiveGrpoDriver(pcfg, jcfg, group_size=t["group_size"], seed=int(seed) % (2**31 - 1))
    # the benchmark's weights replace the driver's own: drop those first
    driver.state = driver.engine = None
    driver.judge.engine.params = None
    gc.collect()
    words = weights.seed_words(seed)
    pdims, jdims = spec.dims(cell.config["policy"]), spec.dims(cell.config["judge"])
    policy = check.make_policy(words, pdims)
    _same_tree(policy, driver.api.abstract_params(), "policy")
    judge = check.make_judge(words, jdims)
    _same_tree(judge, driver.judge.engine.api.abstract_params(), "judge")
    driver.state = TrainState(policy, jax.jit(init_adamw)(policy))
    driver.judge.engine.params = judge
    driver.gen_cfg = GenerationConfig(max_new_tokens=t["new_tokens"], temperature=t["temperature"],
                                      cache_len=t["prompt_len"] + t["new_tokens"])
    driver.engine = Engine(driver.api, policy, driver.gen_cfg)
    return driver


def new_tangram(cell: spec.Cell, driver):
    from repro.core.cluster import paper_testbed
    from repro.rl.driver import build_tangram

    c = cell.traffic["cluster"]
    return build_tangram(paper_testbed(cpu_nodes=c["cpu_nodes"], gpu_nodes=c["gpu_nodes"]),
                         services=[c["service"]], service_state_gb=driver.judge.state_gb)


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------


class Probe:
    """Wraps the driver's calls once; ``step`` runs one stamped GRPO step."""

    def __init__(self, driver):
        import jax
        from jax.profiler import TraceAnnotation

        self.ann = TraceAnnotation
        self.rec: Optional[StepRecord] = None
        self.last_logp = None
        self._update_t0 = 0.0
        self._update_span = None
        gen, logprobs, grpo, score = (driver.engine.generate, driver._logprobs,
                                      driver.grpo_step, driver.judge.score)

        def generate(batch, key=None):
            with self.ann("rollout"):
                t0 = time.perf_counter()
                toks, logps = gen(batch, key=key)
                jax.block_until_ready((toks, logps))
                self.rec.rollout_s += time.perf_counter() - t0
            self.last_logp = logps
            return toks, logps

        def old_logprobs(params, tokens):
            self._update_span = self.ann("update")
            self._update_span.__enter__()
            self._update_t0 = time.perf_counter()
            return logprobs(params, tokens)

        def grpo_step(state, batch):
            out = grpo(state, batch)
            jax.block_until_ready(out)
            self.rec.update_s += time.perf_counter() - self._update_t0
            self._update_span.__exit__(None, None, None)
            return out

        def judge_score(tokens):
            with self.ann("judge_score"):
                t0 = time.perf_counter()
                scores = np.asarray(score(tokens))
                t1 = time.perf_counter()
            self.rec.judge_s += t1 - t0
            self.rec.acts.extend([t1 - self.rec.submit_t] * int(scores.shape[0]))
            self.rec.calls.append((tokens, scores))
            return scores

        driver.engine.generate = generate
        driver._logprobs = old_logprobs
        driver.grpo_step = grpo_step
        driver.judge.score = judge_score

    def _wrap_tangram(self, tg) -> None:
        submit, run = tg.submit, tg.run

        def stamped_submit(action, *a, **kw):
            if self.rec.submit_t is None:
                self.rec.submit_t = time.perf_counter()
            fut = submit(action, *a, **kw)
            self.rec.submitted += 1
            self.rec.futures.append(fut)
            return fut

        def stamped_run(*a, **kw):
            with self.ann("reward"):
                t0 = time.perf_counter()
                out = run(*a, **kw)
                self.rec.reward_s += time.perf_counter() - t0
            return out

        tg.submit, tg.run = stamped_submit, stamped_run

    def step(self, driver, tangram, prompt_ids: np.ndarray, index: int) -> StepRecord:
        from jax.profiler import StepTraceAnnotation

        self._wrap_tangram(tangram)
        self.rec = rec = StepRecord(t0=time.perf_counter(), t1=0.0, tangram=tangram)
        with StepTraceAnnotation("step", step_num=index):
            rec.report = driver.run_step(prompt_ids, tangram)
        rec.t1 = time.perf_counter()
        return rec


def lost_and_misplaced(rec: StepRecord) -> Dict[str, int]:
    """Control plane: actions not completed exactly once, and rewards not on
    the row of the sequence the judge scored.

    A group can sample one sequence on several rows (a policy trained for
    some tens of steps does so often), and the judge's answer names its
    sequence, not its row.  So each distinct sequence is due as many
    answers as it has rows, and the rewards on those rows have to be the
    answers it got, as a multiset."""
    seqs, rewards = rec.report.sequences, rec.report.rewards
    n = seqs.shape[0]
    due: Dict[bytes, Counter] = {}
    for row, r in zip(seqs, rewards):
        due.setdefault(np.asarray(row, np.int64).tobytes(), Counter())[np.float32(r).item()] += 1
    got: Dict[bytes, Counter] = {}
    misplaced = 0
    for tokens, scores in rec.calls:
        for row, s in zip(np.asarray(tokens), scores):
            key = np.asarray(row, np.int64).tobytes()
            if key in due:
                got.setdefault(key, Counter())[np.float32(s).item()] += 1
            else:
                misplaced += 1
    unanswered = 0
    for key, on_rows in due.items():
        answers = got.get(key, Counter())
        unanswered += abs(sum(answers.values()) - sum(on_rows.values()))
        misplaced += sum((answers - on_rows).values())
    records = rec.tangram.telemetry.records
    lost = (abs(rec.submitted - n) + _futures_failed(rec) + unanswered
            + abs(len(records) - n) + sum(1 for r in records if r.failed))
    return {"actions_lost": lost, "rewards_misplaced": misplaced}


def repeated_rows(rec: StepRecord) -> int:
    """Rows of the step whose sequence an earlier row of it already holds."""
    seqs = rec.report.sequences
    return seqs.shape[0] - np.unique(seqs, axis=0).shape[0]


def _ok(fut) -> bool:
    if not fut.done():
        return False
    try:
        fut.result(timeout=0)
    except Exception:  # the action failed, timed out or was cancelled
        return False
    return True


def _futures_failed(rec: StepRecord) -> int:
    return sum(1 for f in rec.futures if not _ok(f))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def enable_cache() -> None:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    when set, else its fixed ``.jax_cache/`` in the checkout), with every
    program kept however fast it compiled."""
    import jax

    from repro.launch.compilation import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[0], len(devs)


def warm_up(cell: spec.Cell, driver, probe: Probe, seed: int):
    """The first GRPO steps, through the window's own call: they compile
    every program and give the check its training readings.  Returns the
    steps with their rollout log-probs, the readings, and the seconds the
    readings took (not set-up)."""
    t, vocab = cell.traffic, driver.api.cfg.vocab_size
    b1 = cell.config["optimizer"]["beta1"]
    warm, readings, check_s = [], {}, 0.0
    for k in range(check.WARM_STEPS):
        rec = probe.step(driver, new_tangram(cell, driver), prompts(seed, k, t, vocab), k)
        c0 = time.perf_counter()
        warm.append((rec, np.asarray(probe.last_logp)))
        if k == 0:  # Adam's first moment is (1 - b1) times the clipped gradient
            readings["grad_norms"] = {p: n / (1 - b1)
                                      for p, n in check.ref.leaf_norms(driver.state.opt.m).items()}
        if k == check.WARM_STEPS - 1:
            theta0 = check.make_policy(weights.seed_words(seed), spec.dims(cell.config["policy"]))
            readings["change_norms"] = check.ref.leaf_norms(driver.state.params, minus=theta0)
            del theta0
        check_s += time.perf_counter() - c0
    return warm, readings, check_s


def window(cell: spec.Cell, driver, probe: Probe, seed: int, seconds: float, trace_dir):
    """Whole steps, closed loop, until ``seconds`` have passed; with a
    ``trace_dir``, the first ``trace_steps`` of them are traced.  Returns
    the steps, the window's seconds and the programs lowered in it."""
    import jax

    from repro.launch.compilation import CompileCounter

    t, vocab = cell.traffic, driver.api.cfg.vocab_size
    trace_steps = t["trace_steps"] if trace_dir else 0
    steps: List[StepRecord] = []
    with CompileCounter() as cc:
        t_w = time.perf_counter()
        k = check.WARM_STEPS
        while True:
            if trace_steps and not steps:
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level = 2
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            steps.append(probe.step(driver, new_tangram(cell, driver), prompts(seed, k, t, vocab), k))
            k += 1
            if trace_steps and len(steps) == trace_steps:
                jax.profiler.stop_trace()
            if time.perf_counter() - t_w >= seconds and len(steps) >= trace_steps:
                break
    return steps, steps[-1].t1 - t_w, cc.lowered


def judge_sample(steps: List[StepRecord], seed: int, k: int):
    """``k`` of the window's judge answers, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % 2**64, 7])
    pool = [(rec.report.sequences[j], rec.report.rewards[j])
            for rec in steps for j in range(rec.report.sequences.shape[0])]
    take = rng.choice(len(pool), size=min(k, len(pool)), replace=False)
    return np.stack([pool[x][0] for x in take]), np.array([pool[x][1] for x in take])


def read_trace(trace_dir: str, steps: List[StepRecord], trace_steps: int):
    import glob

    from chip import trace as trace_mod

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    events = trace_mod.load(files[0])
    summary = trace_mod.summarize(events)
    summary["actions"] = sum(r.submitted for r in steps[:trace_steps])
    return summary, events


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
             require_tpu: bool = True, log=print, keep: Optional[Dict] = None) -> Dict:
    """One run; returns the result line's dict (``checks`` last).  ``keep``,
    when given, receives what the program produced, the reference's
    readings and, in a traced run, the trace (``produced``, ``want``,
    ``events``)."""
    import tempfile

    dev, count = device_info(cell.chips, require_tpu)
    peak = flops.peaks(dev.device_kind) if require_tpu else flops.peaks("TPU v5 lite")
    t = cell.traffic
    b0 = time.perf_counter()
    driver = build_driver(cell, seed)
    log(f"driver and weights {time.perf_counter() - b0} s (process at {b0 - t_start} s)")
    probe = Probe(driver)
    warm, readings, check_s = warm_up(cell, driver, probe, seed)
    setup_s = time.perf_counter() - t_start - check_s

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        steps, window_s, lowered = window(cell, driver, probe, seed, seconds, tmp if traced else None)
        mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        trace_summary = events = None
        if traced:
            trace_summary, events = read_trace(tmp, steps, t["trace_steps"])

    # control plane over every step, and the judge's answers to check
    cp = {"actions_lost": 0, "rewards_misplaced": 0}
    for rec in [r for r, _ in warm] + steps:
        for key, v in lost_and_misplaced(rec).items():
            cp[key] += v
    log(f"repeated rows: {sum(repeated_rows(r) for r in steps)} in the window, "
        f"{sum(repeated_rows(r) for r, _ in warm)} in set-up; judge rows scored "
        f"{sum(len(s) for r in steps for _, s in r.calls)} for "
        f"{sum(r.report.sequences.shape[0] for r in steps)} rows in the window")
    attempted = sum(r.submitted for r in steps)
    failed = sum(_futures_failed(r) for r in steps)
    sample_seqs, sample_rewards = judge_sample(steps, seed, t["judge_sample"])
    produced = check.Produced(
        seqs=[r.report.sequences for r, _ in warm], rollout_logp=[lp for _, lp in warm],
        rewards=[r.report.rewards for r, _ in warm], losses=[r.report.grpo_loss for r, _ in warm],
        sample_seqs=sample_seqs, sample_rewards=sample_rewards, **readings)

    # the program's state goes before the reference runs
    for rec in steps + [r for r, _ in warm]:
        rec.tangram = rec.futures = rec.calls = None
    del driver, probe, warm
    gc.collect()
    r0 = time.perf_counter()
    want = check.follow(cell.config, t, seed, produced.seqs, produced.sample_seqs)
    numbers = {**check.compare(produced, want, t), **cp}
    log(f"reference {time.perf_counter() - r0} s; widest leaves {check.worst_leaves(produced, want)}")
    if keep is not None:
        keep.update(produced=produced, want=want, events=events)
    checks = check.verdict(numbers, {**cell.limits, "actions_lost": 0, "rewards_misplaced": 0})

    pdims, jdims = spec.dims(cell.config["policy"]), spec.dims(cell.config["judge"])
    work = flops.step(pdims, jdims, t)
    n, S = t["prompts_per_step"] * t["group_size"], t["prompt_len"] + t["new_tokens"]
    judge_bytes = spec.arch_of(jdims).judge_step_bytes(jdims, n, S)
    work["judge_least_s"] = flops.least_time_s(work["judge"], judge_bytes, peak)
    run = Run(cell, setup_s, window_s, steps, lowered, work, peak, trace_summary, events)
    metrics = {}
    for m in cell.per_layer if traced else cell.end_to_end:
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": count,
              "memory_peak_bytes": mem_peak}
    out = {"correct": check.passed(checks), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in trace_summary["device_ops"]],
                            "idle_gaps": [list(x) for x in trace_summary["idle_gaps"]]}
    out["checks"] = checks
    step_ts = sorted(r.t1 - r.t0 for r in steps)
    slow = max(steps, key=lambda r: r.t1 - r.t0)
    log(f"slowest step: rollout {slow.rollout_s} update {slow.update_s} reward {slow.reward_s} s")
    log(f"window {len(steps)} steps in {window_s} s; setup {setup_s} s; "
        f"attempted {attempted}; failed {failed}; lowered in window {lowered}; step s "
        f"min {step_ts[0]} median {percentile(step_ts, 50)} max {step_ts[-1]}")
    return out
