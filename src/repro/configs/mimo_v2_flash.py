"""mimo-v2-flash [https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json].

309B-A15B: 48L d_model=4096, 64 q heads of q/k width 192 and v width 128.
Layers 0, 5, 11, ..., 47 attend globally (4 kv heads, rope theta 5e6);
the rest attend over a 128-token sliding window (8 kv heads, theta 1e4)
with a learned sink logit per head.  Rotary covers the first
int(192 * 0.334) = 64 dims of a q/k head; attention output is scaled by
0.707.  Layer 0 has a dense SwiGLU of 16384; every other layer routes to
8 of 256 experts of 2048 by sigmoid scores, the top 8 of scores plus a
correction bias, weighted by the chosen scores normalised over the 8.
Untied head, vocab 152576, RMSNorm eps 1e-5.

Departures: the 3 MTP modules are left out (scoring uses the main head);
RMSNorm and no QK norm are assumed; rotary over the first 64 dims with
the two halves paired, as ``apply_rope`` pairs them, is assumed; the
value scale on both kinds of layer is assumed.
"""

from repro.configs.base import ModelConfig, register

#: hybrid_layer_pattern: 0 global, 1 sliding window
PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7

CONFIG = register(
    ModelConfig(
        name="mimo-v2-flash",
        family="moe",
        num_layers=48,
        d_model=4096,
        num_heads=64,
        num_kv_heads=4,
        head_dim=192,
        v_head_dim=128,
        d_ff=16384,
        expert_d_ff=2048,
        num_experts=256,
        experts_per_token=8,
        vocab_size=152576,
        rope_theta=5_000_000.0,
        norm_eps=1e-5,
        layer_pattern=PATTERN,
        moe_pattern=(0,) + (1,) * 47,
        window=128,
        window_kv_heads=8,
        window_rope_theta=10_000.0,
        partial_rotary_factor=0.334,
        attention_value_scale=0.707,
        source="https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json",
    )
)
