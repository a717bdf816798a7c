"""Work a GRPO step needs, counted from the configuration's shapes, and the
chip's peaks.

Model FLOPs count the matrix products a step cannot do without: two per
multiply-add over each layer's weights and the head, plus causal
attention (each position against itself and the positions before it).
Norms, softmax, sampling and the optimizer are left out, and so is
recomputation, so every count is at most what the compiled programs do.
The counts of one forward pass, one decoded token and the head are the
architecture's (``arch/<name>.py``); this module puts them together into
a step's phases.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from chip import spec

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind`` (as JAX names it); unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def rollout(d: Dict, seqs: int, prompt_len: int, new_tokens: int) -> int:
    """Prefill (head at the last position) and the decodes whose logits are
    sampled: the last decoded token's logits are never used."""
    a = spec.arch_of(d)
    return a.forward(d, seqs, prompt_len, 0) + 2 * seqs * a.head_params(d) + sum(
        a.decode(d, seqs, prompt_len + j) for j in range(new_tokens - 1))


def judge_action(d: Dict, seq_len: int) -> int:
    """Scoring one sequence: every position's log-prob of the next token."""
    return spec.arch_of(d).forward(d, 1, seq_len, seq_len - 1)


def step(policy: Dict, judge: Dict, traffic: Dict) -> Dict[str, int]:
    """Model FLOPs of one GRPO step, by phase."""
    n = traffic["prompts_per_step"] * traffic["group_size"]
    s0, new = traffic["prompt_len"], traffic["new_tokens"]
    S = s0 + new
    fwd = spec.arch_of(policy).forward(policy, n, S, S - 1)
    return {
        "rollout": rollout(policy, n, s0, new),
        "judge": n * judge_action(judge, S),
        "old_logp": fwd,
        "update": 3 * fwd,
    }


def least_time_s(flops: float, nbytes: float, peak: Dict) -> float:
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
