"""The main path's Pallas kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and each kernel is compiled for one of its chips at a real
model width.  Interpret mode (``tests/test_kernels.py``) checks the
kernels' numbers; this file checks what only the chip's compiler
refuses: block shapes off the (8, 128) tiling, or more VMEM than a kernel
may use.  Each compiled program must hold a Mosaic kernel
(``tpu_custom_call``), not an XLA fallback.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import INPUT_SHAPES, get_config
from repro.core.live import PAYLOAD_SHAPE
from repro.kernels import ops

_LLAMA = get_config("llama3.2-1b")
_GRANITE = get_config("granite-moe-3b-a800m")
_MAMBA = get_config("mamba2-130m")
_LLAMA8B = get_config("llama3-8b")
_BF, _F32 = jnp.bfloat16, jnp.float32


def _ssd_shapes(cfg, chunks=4):
    H, hd, N, Q = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk
    return [((chunks, H, Q, hd), _F32), ((chunks, Q, N), _F32),
            ((chunks, Q, N), _F32), ((chunks, H, Q), _F32)]


def _flash_shapes(cfg, S=2048):
    H, KV, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return [((1, H, S, d), _BF), ((1, KV, S, d), _BF), ((1, KV, S, d), _BF)]


#: name -> (jitted kernel op, [(shape, dtype) of each operand])
CASES = {
    "rmsnorm/live_payload": (
        ops.rmsnorm_op, [(PAYLOAD_SHAPE, _F32), (PAYLOAD_SHAPE[-1:], _F32)]),
    "rmsnorm/llama3.2-1b": (
        ops.rmsnorm_op, [((4096, _LLAMA.d_model), _BF), ((_LLAMA.d_model,), _BF)]),
    "moe_matmul/granite-moe-3b-a800m": (
        ops.moe_matmul_op,
        [((_GRANITE.num_experts, 256, _GRANITE.d_model), _BF),
         ((_GRANITE.num_experts, _GRANITE.d_model, _GRANITE.expert_d_ff), _BF)]),
    "ssd_intra_chunk/mamba2-130m": (ops.ssd_intra_chunk_op, _ssd_shapes(_MAMBA)),
    "flash/llama3.2-1b": (ops.flash_attention_op, _flash_shapes(_LLAMA)),
    # K/V stream through VMEM block by block, so the 32k prefill fits too
    "flash/llama3-8b@prefill_32k": (
        ops.flash_attention_op,
        _flash_shapes(_LLAMA8B, INPUT_SHAPES["prefill_32k"].seq_len)),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    op, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = op.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
