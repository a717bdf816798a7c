"""The dense architecture (``arch/dense.py``) is the code it was moved
from: at a fixed seed and fixed tokens it makes the same weights, bit for
bit, and the same reference log-probs, and it counts the same work for
both configurations under both mixes, on the same parameter trees.  The
pinned values were computed by the code before the move, from the same
inputs."""

import hashlib
import json

import jax
import numpy as np
import pytest
from conftest import CHIP, tiny_config

from chip import check, flops, spec, weights
from chip import reference as ref

SEED = 3_141_592_653_589  # both words of the seed non-zero

#: sha256 of the weights (path, dtype, shape and bytes of each leaf)
PINNED_SHA256 = {
    "policy": "414e18ca86ed16b6036be716289d9bb0adedb05fbc2ea67e4e339c21493acca9",
    "judge": "46d9dc17c71625f3421c0272d503bfad3e56ca34885cbd6585814ee5429a5566",
}
#: ``logp_rows`` of 3 rows of 9 tokens (``default_rng(5)``), 2 rows a block
PINNED_LOGP = {
    "policy": [
        -5.453879356384277, -5.867402076721191, -5.426263809204102, -5.779373645782471,
        -5.421804428100586, -5.474225044250488, -5.743189334869385, -5.59754753112793,
        -5.844202995300293, -5.573475360870361, -5.5083394050598145, -5.606541633605957,
        -5.442195415496826, -5.484485626220703, -5.619479179382324, -5.763598442077637,
        -5.6458353996276855, -5.513295650482178, -5.438850402832031, -5.417781829833984,
        -5.378195285797119, -5.767521858215332, -5.4741034507751465, -5.798057556152344,
    ],
    "judge": [
        -5.639331340789795, -5.8334174156188965, -5.943030834197998, -5.832156181335449,
        -5.792764663696289, -5.83790922164917, -5.689111232757568, -5.8220720291137695,
        -5.741374492645264, -5.579193592071533, -5.662619113922119, -5.797197341918945,
        -5.805306434631348, -5.863399505615234, -5.706958770751953, -5.64438009262085,
        -5.6708173751831055, -5.592714786529541, -5.626036167144775, -5.932746887207031,
        -5.607173919677734, -5.841011047363281, -5.704242706298828, -5.896612167358398,
    ],
}

#: sha256 of the parameter tree's shapes (``json.dumps``, keys sorted), policy and judge
PINNED_TREE = {
    "smollm360m-glm4judge": ("1b24e3fa67748e9c7eb2151a1ab1ee5de96b80efe43fe69a9a0742cdd661572c",
                             "ae0d73bca0940189ad0c8eb73ee6384f5399943af0b36fd81d1a7e6e78ec3137"),
    "smollm360m-rm360m": ("1b24e3fa67748e9c7eb2151a1ab1ee5de96b80efe43fe69a9a0742cdd661572c",
                          "1b24e3fa67748e9c7eb2151a1ab1ee5de96b80efe43fe69a9a0742cdd661572c"),
}
#: ``flops.step`` and the judge's least bytes, per configuration and mix
PINNED_WORK = [
    ("smollm360m-glm4judge", "deepsearch", {"rollout": 2727723663360, "judge": 18500303192064,
        "old_logp": 3026693652480, "update": 9080080957440}, 4538392576),
    ("smollm360m-glm4judge", "reasoning", {"rollout": 5980909731840, "judge": 37157909561344,
        "old_logp": 6183746273280, "update": 18551238819840}, 4571963392),
    ("smollm360m-rm360m", "deepsearch", {"rollout": 2727723663360, "judge": 3026693652480,
        "old_logp": 3026693652480, "update": 9080080957440}, 731522944),
    ("smollm360m-rm360m", "reasoning", {"rollout": 5980909731840, "judge": 6183746273280,
        "old_logp": 6183746273280, "update": 18551238819840}, 739403648),
]


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)}{x.dtype}{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("role", ["policy", "judge"])
def test_dense_weights_and_reference_are_the_old_code(role):
    dims = tiny_config()[role]["as_run"]
    make = check.make_policy if role == "policy" else check.make_judge
    params = make(weights.seed_words(SEED), dims)
    assert digest(params) == PINNED_SHA256[role]
    tokens = np.random.default_rng(5).integers(0, dims["vocab_size"], (3, 9)).astype(np.int32)
    got = ref.logp_rows(params, tokens, dims, "f32", rows=2)
    # float32 on the CPU: the same equations in the same order read the same
    # to an ulp or two (values near -5.6, an ulp 4.8e-7)
    np.testing.assert_allclose(got.ravel(), PINNED_LOGP[role], rtol=0, atol=1e-6)


@pytest.mark.parametrize("config, mix, step, judge_bytes", PINNED_WORK,
                         ids=[f"{c}-{m}" for c, m, _, _ in PINNED_WORK])
def test_dense_trees_and_work_counts_are_the_old_code(config, mix, step, judge_bytes):
    cfg = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    traffic = json.loads((CHIP / "traffic" / f"{mix}.json").read_text())
    pd, jd = spec.dims(cfg["policy"]), spec.dims(cfg["judge"])
    assert pd["arch"] == jd["arch"] == "dense"
    trees = [json.dumps(spec.arch_of(d).shapes(d), sort_keys=True).encode() for d in (pd, jd)]
    assert tuple(hashlib.sha256(t).hexdigest() for t in trees) == PINNED_TREE[config]
    assert flops.step(pd, jd, traffic) == step
    n = traffic["prompts_per_step"] * traffic["group_size"]
    S = traffic["prompt_len"] + traffic["new_tokens"]
    assert spec.arch_of(jd).judge_step_bytes(jd, n, S) == judge_bytes
