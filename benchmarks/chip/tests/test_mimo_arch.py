"""The MiMo-V2-Flash architecture (``arch/mimo_v2_flash.py``) against the
program, at the cell's shapes and at a tiny size on the CPU: the same
parameter tree, the same log-probs, work counts at most what XLA counts,
the judge's bytes counting each weight once, the expert-load reader, and
the cell found as new files beside the benchmark as it stood."""

import copy
import dataclasses
import json
import shutil
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import CHIP, REPO

from chip import check, flops, harness, spec, weights
from chip import reference as ref
from chip.harness import Run, StepRecord

CELL = "mimoflash.deepsearch"
#: the files the cell brings, beside the benchmark's own
CELL_FILES = ("arch/mimo_v2_flash.py", "configs/smollm360m-mimoflash.json",
              "limits/mimoflash.deepsearch.json", "metrics/judge_expert_load.py")


def section():
    return spec.load_cell(CELL).config["judge"]


def tiny_section():
    """The cell's judge section at d 64: a dense global lead, then windowed
    and global expert layers; window 5; 16 experts, 4 chosen, 4-7 held."""
    s = copy.deepcopy(section())
    s["as_run"].update(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, head_dim=24,
                       v_head_dim=16, d_ff=96, vocab_size=300, layer_pattern=[0, 1, 1, 0],
                       moe_pattern=[0, 1, 1, 1], window=5, window_kv_heads=4, num_experts=16,
                       experts_per_token=4, expert_d_ff=32, first_held_expert=4, experts_held=4,
                       dtype="float32")
    return s


def program(sec, **extra):
    from repro.models import build_model

    return build_model(dataclasses.replace(harness.model_config(sec), **extra))


def _shapes(tree):
    return jax.tree.map(lambda s: tuple(s[1:]) if s[0] == "ones" else tuple(s), tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def test_shapes_are_the_programs_tree_for_the_cell():
    sec = section()
    d = spec.dims(sec)
    assert d["arch"] == "mimo_v2_flash"
    api = program(sec)
    assert _shapes(spec.arch_of(d).shapes(d)) == jax.tree.map(lambda x: tuple(x.shape),
                                                              api.abstract_params())
    # the stage: 2.066 B parameters in layers, 1.250 B in the embedding and head
    assert api.param_count() == 3_315_660_608


def test_token_logp_matches_the_program():
    sec = tiny_section()
    d = spec.dims(sec)
    api = program(sec)
    params = check.make_judge(weights.seed_words(2**40 + 3), d)
    harness._same_tree(params, api.abstract_params(), "tiny")
    # sharpen the router and the sinks so that each mechanism moves the result
    for kind in ("window_experts", "global_experts"):
        m = params["layers"][kind]["moe"]
        m["router"], m["router_bias"] = m["router"] * 20, m["router_bias"] * 10
    params["layers"]["window_experts"]["attn"]["sink"] *= 50
    tokens = np.random.default_rng(4).integers(0, 300, (3, 20)).astype(np.int32)
    want = ref.logp_rows(params, tokens, d, "f32", rows=2)
    from repro.training.grpo import token_logprobs

    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, t: token_logprobs(p, t, api))(params, tokens))
    # float32 both sides, the same equations in another order: a few ulps
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # and the fp8 control is far off
    assert np.max(np.abs(ref.logp_rows(params, tokens, d, "fp8", rows=2) - want)) > 1e-3


def xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return float(cost["flops"])


def test_forward_and_judge_action_at_most_xla():
    """With each run of layers unrolled (XLA's cost analysis counts a loop
    body once)."""
    from repro.serving.engine import _score
    from repro.training.grpo import token_logprobs

    sec = tiny_section()
    d = spec.dims(sec)
    api = program(sec, scan_unroll=d["num_layers"])
    p = api.abstract_params()
    S = 24
    t = jax.ShapeDtypeStruct((1, S), jnp.int32)
    ours = flops.judge_action(d, S)
    assert 0 < ours <= xla_flops(lambda p, t: _score(api, None, p, t, None), p, t)
    n = 3
    tn = jax.ShapeDtypeStruct((n, S), jnp.int32)
    fwd = spec.arch_of(d).forward(d, n, S, S - 1)
    assert 0 < fwd <= xla_flops(lambda p, t: token_logprobs(p, t, api), p, tn)


def test_judge_bytes_count_each_touched_weight_once():
    sec = section()
    d = spec.dims(sec)
    arch = spec.arch_of(d)
    S = 256
    one, many = arch.judge_step_bytes(d, 1, S), arch.judge_step_bytes(d, 16, S)
    assert many - one == 15 * S * (2 * d["d_model"] + 4)
    # every weight but the embedding (rows gathered) once, in bf16
    weights_read = program(sec).param_count() - d["vocab_size"] * d["d_model"]
    assert one == 2 * weights_read + S * (2 * d["d_model"] + 4)


def _run(counters):
    steps = [StepRecord(t0=0.0, t1=1.0, report=SimpleNamespace(counters=c)) for c in counters]
    return Run(cell=None, setup_s=0.0, window_s=1.0, steps=steps, compiles=0, work={}, peak={})


def test_judge_expert_load_reads_the_counters():
    from chip.metrics import judge_expert_load

    def c(calls, held):
        return {"judge_calls": calls, "held_experts": 8, "held_assignments": np.array(held),
                "held_max_load": np.array([9] * len(held)), "dropped_assignments": np.zeros(len(held))}

    # 2 steps of 16 calls, 6 expert layers: 64 + 2 assignments a layer in the first step
    run = _run([c(16, [16 * 64 + 2] * 6), c(16, [16 * 60] * 6)])
    want = (6 * (16 * 64 + 2) + 6 * 16 * 60) / (2 * 16 * 6 * 8)
    assert judge_expert_load.read(run) == pytest.approx(want)
    # a judge without routed experts, or a program that does not count
    assert judge_expert_load.read(_run([None])) is None
    assert judge_expert_load.read(Run(None, 0.0, 1.0, [StepRecord(0.0, 1.0, report=SimpleNamespace())],
                                      0, {}, {})) is None


def test_the_cell_loads_beside_the_benchmark_as_it_stood(tmp_path):
    """The benchmark without the cell's files and entries still loads its
    other cells; the cell's new files laid over it make every file what
    it is here, and the cell loads there."""
    from test_discovery import assert_benchmark_files_unchanged

    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    config = next(c for c in bench["configs"] if c["name"] == "smollm360m-mimoflash")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    metric = next(m for m in bench["per_layer"] if m["name"] == "judge_expert_load")
    assert (cell["config"], cell["chips"], metric["workloads"]) == (config["name"], 1, [CELL])

    # as it stood: the cell's files and entries taken away
    held = {rel: (chip / rel).read_bytes() for rel in CELL_FILES}
    for rel in CELL_FILES:
        (chip / rel).unlink()
    old = copy.deepcopy(bench)
    old["configs"].remove(config)
    old["workloads"].remove(cell)
    old["per_layer"].remove(metric)
    for m in old["end_to_end"] + old["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].remove(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(old))
    for other in (w["name"] for w in old["workloads"]):
        assert "judge_expert_load" not in [m.name for m in spec.load_cell(other, root).per_layer]

    # the cell's files laid over it: nothing else differs, and the cell loads
    for rel, data in held.items():
        (chip / rel).write_bytes(data)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert_benchmark_files_unchanged(chip)
    got = spec.load_cell(CELL, root)
    assert got.chips == 1 and got.config["name"] == "smollm360m-mimoflash"
    assert got.traffic == spec.load_cell("glm4judge.deepsearch", root).traffic
    names = [m.name for m in got.per_layer]
    assert "judge_expert_load" in names and "judge_roofline" in names
    assert set(got.limits) == {"rollout_logp_gap", "judge_score_gap", "grad_norm_gap",
                               "param_change_gap"}
    # the policy is glm4judge's, unchanged
    glm = json.loads((CHIP / "configs" / "smollm360m-glm4judge.json").read_text())
    assert got.config["policy"] == glm["policy"]
