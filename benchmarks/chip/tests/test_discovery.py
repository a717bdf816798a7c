"""A configuration, a mix, a cell, a metric and an architecture added as
new files plus new entries in ``BENCHMARK.json`` are found without an
edit to any file that is already there."""

import filecmp
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from conftest import CHIP

from chip import check, flops, harness, spec, weights
from chip import reference as ref

METRIC = '''"""steps_in_window: whole steps the window ran (count)."""


def read(run):
    return len(run.steps)
'''

TOY_ARCH = '''"""toy: the dense decoder with a learned shift added to the residual
stream before each layer, one extra leaf per layer."""

import jax
import jax.numpy as jnp

from chip import reference as ref
from chip import spec

dense = spec.arch("dense")
KEYS = dense.KEYS
program_value = dense.program_value
head_params = dense.head_params


def shapes(dims):
    tree = dense.shapes(dims)
    tree["layers"]["shift"] = (dims["num_layers"], dims["d_model"])
    return tree


def token_logp(params, tokens, dims, prec="f32", remat=False):
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        lp = ref.f32(lp)
        return dense.block(x + lp["shift"], lp, dims, prec), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = ref.rms_norm(x, params["final_norm"].astype(jnp.float32), dims["norm_eps"])
    head = params["embed"].T if dims["tie_embeddings"] else params["lm_head"]
    logits = ref.mm("bsd,dv->bsv", x[:, :-1], head.astype(jnp.float32), prec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def forward(d, seqs, seq_len, head_positions):
    shifts = seqs * seq_len * d["num_layers"] * d["d_model"]
    return dense.forward(d, seqs, seq_len, head_positions) + shifts


def decode(d, seqs, position):
    return dense.decode(d, seqs, position) + seqs * d["num_layers"] * d["d_model"]


def judge_step_bytes(d, seqs, seq_len):
    return dense.judge_step_bytes(d, seqs, seq_len) + 2 * d["num_layers"] * d["d_model"]
'''


def assert_benchmark_files_unchanged(chip):
    """Every file the benchmark had is as it was in the scratch checkout."""
    for f in CHIP.rglob("*"):
        if f.is_file() and "tests" not in f.parts and "__pycache__" not in f.parts:
            assert filecmp.cmp(f, chip / f.relative_to(CHIP), shallow=False), f


def test_added_files_are_found(tmp_path):
    from conftest import make_root

    root = make_root(tmp_path)  # adds configs/tiny.json, traffic/mix.json, limits/tiny.mix.json
    chip = root / "benchmarks" / "chip"
    (chip / "metrics" / "steps_in_window.py").write_text(METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "steps_in_window", "unit": "count", "better": "higher",
                                "bound": 0.25, "source": "host_clock", "workloads": ["tiny.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert_benchmark_files_unchanged(chip)

    cell = spec.load_cell("tiny.mix", root)
    assert cell.config["name"] == "tiny" and cell.traffic["why"] == "tiny"
    assert [m.name for m in cell.end_to_end][-1] == "steps_in_window"
    assert cell.limits["grad_norm_gap"] > 0
    # the repo's cells do not see the new metric
    assert "steps_in_window" not in [m.name for m in spec.load_cell("glm4judge.deepsearch", root).end_to_end]

    out = harness.run_cell(cell, 11, 0.3, False, time.perf_counter(), require_tpu=False,
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["metrics"]["steps_in_window"]["value"] >= 1
    assert {"setup_s", "step_s", "act_mean_ms", "act_p95_ms"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"


def test_added_architecture_is_found(tmp_path):
    """``arch/toy.py`` and a configuration whose judge names it: its weights,
    reference and work counts come from the new file."""
    from conftest import make_root, tiny_config

    config = tiny_config()
    config["judge"]["arch"] = "toy"
    root = make_root(tmp_path, cells=("toy.mix",), config=config)
    chip = root / "benchmarks" / "chip"
    (chip / "arch" / "toy.py").write_text(TOY_ARCH)
    assert_benchmark_files_unchanged(chip)

    cell = spec.load_cell("toy.mix", root)
    pd, jd = spec.dims(cell.config["policy"]), spec.dims(cell.config["judge"])
    assert (pd["arch"], jd["arch"]) == ("dense", "toy")
    dense_jd = dict(jd, arch="dense")

    # the toy tree: the dense leaves, made alike from their paths, and a shift
    words = weights.seed_words(2**33 + 5)
    judge, plain = check.make_judge(words, jd), check.make_judge(words, dense_jd)
    L, d = jd["num_layers"], jd["d_model"]
    shift = judge["layers"]["shift"]
    assert shift.shape == (L, d) and float(jnp.abs(shift).max()) > 0
    shared = dict(judge, layers={k: v for k, v in judge["layers"].items() if k != "shift"})
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), shared, plain))

    # the reference runs the toy's layer: the dense one where the shift is 0
    tokens = np.random.default_rng(3).integers(0, jd["vocab_size"], (3, 8)).astype(np.int32)
    zero = dict(judge, layers=dict(judge["layers"], shift=jnp.zeros((L, d), jnp.float32)))
    want = ref.judge_scores(plain, tokens, dense_jd)
    np.testing.assert_allclose(ref.judge_scores(zero, tokens, jd), want, rtol=1e-6)
    assert not np.allclose(ref.judge_scores(judge, tokens, jd), want, rtol=1e-4)

    # and the step's work counts the toy's judge
    t = cell.traffic
    n, S = t["prompts_per_step"] * t["group_size"], t["prompt_len"] + t["new_tokens"]
    toy, dense = spec.arch_of(jd), spec.arch_of(dense_jd)
    work = flops.step(pd, jd, t)
    assert work["judge"] == n * toy.forward(jd, 1, S, S - 1)
    assert work["judge"] == n * (dense.forward(jd, 1, S, S - 1) + S * L * d)
    assert work["rollout"] == flops.step(pd, dense_jd, t)["rollout"]


def test_frozen_hashes_dims_holding_a_list():
    dims = {"arch": "toy", "d_model": 64, "layer_pattern": [0, 1, 1], "groups": [[0, 1], [2]]}
    f = ref.frozen(dims)
    assert hash(f) == hash(ref.frozen(dict(reversed(list(dims.items())))))
    assert dict(f)["layer_pattern"] == (0, 1, 1) and dict(f)["groups"] == ((0, 1), (2,))
    assert ref.frozen(dict(dims, layer_pattern=[1, 1, 0])) != f
