"""The served path's Pallas kernels compile for the real chip.

Interpret mode (every other kernel test here) cannot see what the TPU's
compiler refuses: a slice off the tiling, too much VMEM, a kernel that
cannot be partitioned. libtpu is installed, and it compiles for a chip
that is DESCRIBED and not attached — so each kernel is compiled ahead of
time for a `v5e:2x2` topology at llama3-8b / arctic-embed-l widths and
the served dtypes, about two seconds each, at no chip time. Nothing
runs: this says nothing about results or speed (chip_smoke.py does).
Skipped, not failed, where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from generativeaiexamples_tpu.ops.attention import flash_attention
from generativeaiexamples_tpu.ops.encoder_attention import encoder_attention
from generativeaiexamples_tpu.ops.int8_matmul import int8_matmul
from generativeaiexamples_tpu.serving.paged_attention import paged_attention
from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    paged_attention_int8)
from generativeaiexamples_tpu.serving.paged_attention_tree import (
    paged_tree_attention)

# llama3-8b decode: 64 slots, 32 query / 8 kv heads of 128, pages of 128.
B, H, KH, HD, PS, MAXP, L = 64, 32, 8, 128, 128, 4, 2
P = B * MAXP + 1
R, TREE = 4, (3, 4)
TREE_R = 1 + TREE[0] * TREE[1]
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip; the persistent compile cache off around
    the compiles (an entry written for a described chip cannot be read
    back without one, and warns on every later run)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that knows no v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_POOL_INT8 = [((2, L, KH, P, PS, HD), I8), ((2, L, KH, P, PS), F32)]
_POOL_BF16 = [((KH, P, PS, HD), BF16)] * 2
_TABLE = [((B, MAXP), I32), ((B,), I32)]

KERNELS = {
    "paged_decode_int8": (
        lambda q, kv, s, t, ln: paged_attention_int8(q, kv, s, t, ln, 1),
        [((B, H, HD), BF16)] + _POOL_INT8 + _TABLE),
    "paged_verify_int8_qrep4": (
        lambda q, kv, s, t, ln: paged_attention_int8(q, kv, s, t, ln, 1,
                                                     q_rep=R),
        [((B, R, H, HD), BF16)] + _POOL_INT8 + _TABLE),
    "paged_tree_int8_3x4": (
        lambda q, kv, s, t, ln: paged_attention_int8(
            q, kv, s, t, ln, 1, q_rep=TREE_R, tree=TREE),
        [((B, TREE_R, H, HD), BF16)] + _POOL_INT8 + _TABLE),
    "paged_decode_bf16": (
        paged_attention, [((B, H, HD), BF16)] + _POOL_BF16 + _TABLE),
    "paged_tree_bf16_3x4": (
        lambda q, k, v, t, ln: paged_tree_attention(q, k, v, t, ln, TREE),
        [((B, H, TREE_R, HD), BF16)] + _POOL_BF16 + _TABLE),
    "flash_prefill": (
        lambda q, k, v, ln: flash_attention(q, k, v, causal=True,
                                            lengths=ln),
        [((8, H, 512, HD), BF16)] + [((8, KH, 512, HD), BF16)] * 2
        + [((8,), I32)]),
    "encoder_attention_arctic_l": (
        encoder_attention, [((32, 16, 512, 64), BF16)] * 3 + [((32,), I32)]),
    "int8_matmul_mlp": (  # opt-in (ENGINE_PALLAS_INT8), same guard
        int8_matmul,
        [((64, 4096), BF16), ((4096, 14336), I8), ((14336,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
