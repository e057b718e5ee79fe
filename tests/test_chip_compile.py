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

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.attention import flash_attention
from generativeaiexamples_tpu.ops.encoder_attention import encoder_attention
from generativeaiexamples_tpu.serving.paged_attention import paged_attention
from generativeaiexamples_tpu.ops.quant import QuantizedTensor
from generativeaiexamples_tpu.serving.kda_state_update import (
    kda_state_update_pallas)
from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    live_rows, paged_attention_int8, paged_attention_int8_window)
from generativeaiexamples_tpu.serving.paged_attention_mla import (
    paged_attention_mla)
from generativeaiexamples_tpu.serving.paged_attention_sparse import (
    paged_attention_sparse_pallas)
from generativeaiexamples_tpu.serving.sparse_index_scores import (
    sparse_index_scores_pallas)
from generativeaiexamples_tpu.serving.sparse_select import (
    sparse_select_pallas)
from generativeaiexamples_tpu.serving.paged_attention_tree import (
    paged_tree_attention)
from generativeaiexamples_tpu.serving.ssm_state_update import (
    ssm_state_update_pallas)

# llama3-8b decode: 64 slots, 32 query / 8 kv heads of 128, pages of 128.
B, H, KH, HD, PS, MAXP, L = 64, 32, 8, 128, 128, 4, 2
P = B * MAXP + 1
R, TREE = 4, (3, 4)
TREE_R = 1 + TREE[0] * TREE[1]
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 topology; the persistent compile cache off
    around the compiles (an entry written for a described chip cannot be
    read back without one, and warns on every later run)."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip."""
    return SingleDeviceSharding(topo.devices[0])


_POOL_INT8 = [((2, L, KH, P, PS, HD), I8), ((2, L, KH, P, PS), F32)]
_POOL_BF16 = [((KH, P, PS, HD), BF16)] * 2
_TABLE = [((B, MAXP), I32), ((B,), I32)]
_MASK = [((B,), jnp.bool_)]


def _masked(split_kv=None):
    """The kernel as a decode step calls it: with the step's `active`
    mask, turned into the walk's order inside the program (PR 41)."""
    return lambda q, kv, s, t, ln, m: paged_attention_int8(
        q, kv, s, t, ln, 1, split_kv=split_kv, live=live_rows(m))


def _HC_PRE(tokens):
    return [((tokens, 4 * 3584), BF16), ((38, 24, 4 * 3584), BF16),
            ((38, 24), F32), ((38, 3), F32)]


def _HC_POST(tokens):
    return [((tokens, 4 * 3584), BF16), ((tokens, 3584), BF16),
            ((tokens, 128), F32)]


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
    # the benchmark cells' other shapes (PR 34: a block's live count picks
    # one of PAGES_PER_BLOCK unrolled bodies, all of which must fit):
    # Mistral-7B's tables of 20 pages, Ouro's 16 KV heads with a descriptor
    # each for k and v, one chip's 2 KV heads of Mistral-Small-24B at TP=4
    "paged_decode_int8_tables_of_20": (
        lambda q, kv, s, t, ln: paged_attention_int8(q, kv, s, t, ln, 1),
        [((B, H, HD), BF16)] + _POOL_INT8 + [((B, 20), I32), ((B,), I32)]),
    "paged_decode_int8_16_kv_heads_split": (
        lambda q, kv, s, t, ln: paged_attention_int8(q, kv, s, t, ln, 1,
                                                     split_kv=True),
        [((32, 16, HD), BF16), ((2, L, 16, P, PS, HD), I8),
         ((2, L, 16, P, PS), F32), ((32, MAXP), I32), ((32,), I32)]),
    "paged_decode_int8_2_kv_heads": (
        lambda q, kv, s, t, ln: paged_attention_int8(q, kv, s, t, ln, 1),
        [((B, 8, HD), BF16), ((2, L, 2, P, PS, HD), I8),
         ((2, L, 2, P, PS), F32)] + _TABLE),
    # the same three with the step's mask (PR 41: the grid ends at the
    # live rows' count, a bound the program computes)
    "paged_decode_int8_masked_tables_of_20": (
        _masked(), [((B, H, HD), BF16)] + _POOL_INT8
        + [((B, 20), I32), ((B,), I32)] + _MASK),
    "paged_decode_int8_masked_16_kv_heads_split": (
        _masked(split_kv=True),
        [((32, 16, HD), BF16), ((2, L, 16, P, PS, HD), I8),
         ((2, L, 16, P, PS), F32), ((32, MAXP), I32), ((32,), I32),
         ((32,), jnp.bool_)]),
    "paged_decode_int8_masked_2_kv_heads": (
        _masked(), [((B, 8, HD), BF16), ((2, L, 2, P, PS, HD), I8),
                    ((2, L, 2, P, PS), F32)] + _TABLE + _MASK),
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
    # A.X-K1's widths (benchmark/configs/ax-k1-int8-ep16.json): a latent
    # prompt's keys of 192 (padded to 256 lanes) against values of 128;
    # the absorbed paged kernel, 128 slots of 64 heads over rows of 576
    # values in 640 lanes; the grouped matmul over 12 held experts, a
    # decode step's tiles of 32 rows and a prefill's of 128
    "flash_prefill_latent_256_128": (
        lambda q, k, v, ln: flash_attention(q, k, v, causal=True,
                                            lengths=ln, scale=0.13),
        [((4, 64, 384, 256), BF16)] * 2 + [((4, 64, 384, 128), BF16),
                                           ((4,), I32)]),
    "paged_decode_latent": (
        lambda q, pool, t, ln: paged_attention_mla(
            q, pool, 3, t, ln, latent=512, scale=0.13),
        [((128, 64, 640), BF16), ((15, 1408, PS, 640), BF16),
         ((128, 11), I32), ((128,), I32)]),
    "grouped_expert_matmul_decode": (
        lambda *a: _grouped(32, *a),
        [((1024 + 12 * 32, 7168), BF16), ((14, 12, 7168, 4096), I8),
         ((14, 12, 4096), F32), ((44,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill": (
        lambda *a: _grouped(128, *a),
        [((12288 + 12 * 128, 2048), BF16), ((14, 12, 2048, 7168), I8),
         ((14, 12, 7168), F32), ((108,), I32), ((1,), I32)]),
    # granite-4.0-h-small's widths
    # (benchmark/configs/granite-4.0-h-small-int8.json): the in-place
    # state update of one state-space layer over the per-slot pool, 96
    # slots of [128, 64, 128] float32; and the grouped matmul's second
    # shape, 72 whole experts of 768, a decode step's 960 pairs
    "ssm_state_update_96_slots": (
        lambda state, o, n, a, xdt, bv, cv: ssm_state_update_pallas(
            state, 4, o, n, a, xdt, bv, cv),
        [((9, 96, 128, 64, 128), F32), ((96,), I32), ((1,), I32),
         ((96, 128, 128), F32), ((96, 128, 64), F32), ((96, 128), F32),
         ((96, 128), F32)]),
    "grouped_expert_matmul_decode_72_of_768": (
        lambda *a: _grouped(32, *a),
        [((960 + 72 * 32, 4096), BF16), ((10, 72, 4096, 1536), I8),
         ((10, 72, 1536), F32), ((102,), I32), ((1,), I32)]),
    # a prompt group of 4 x 384 tokens in tiles of 64
    # (hybrid_ssm.PREFILL_TILE_ROWS)
    "grouped_expert_matmul_prefill_72_of_768": (
        lambda *a: _grouped(64, *a),
        [((15360 + 72 * 64, 4096), BF16), ((10, 72, 4096, 1536), I8),
         ((10, 72, 1536), F32), ((312,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill_down_72_of_768": (
        lambda *a: _grouped(64, *a),
        [((15360 + 72 * 64, 768), BF16), ((10, 72, 768, 4096), I8),
         ((10, 72, 4096), F32), ((312,), I32), ((1,), I32)]),
    "grouped_expert_matmul_down_72_of_768": (
        lambda *a: _grouped(32, *a),
        [((960 + 72 * 32, 768), BF16), ((10, 72, 768, 4096), I8),
         ((10, 72, 4096), F32), ((102,), I32), ((1,), I32)]),
    # Keye-VL-2.0-30B-A3B's stage
    # (benchmark/configs/keye-vl-2.0-30b-a3b-int8.json): 16 slots, tables
    # of 152 pages, 2,688 pages of 12 rows; a decode step's three kernels
    # with the step's mask, as _sparse_decode_once calls them: the index
    # scores over the transposed bf16 index pages, the selection of 2,048
    # of 19,456, the walk of the int8 pool under the selection's mask
    "sparse_index_scores_masked_tables_of_152": (
        lambda q, w, idx, t, ln, m: sparse_index_scores_pallas(
            q, w, idx, 7, t, ln, live_rows(m)),
        [((16, 16, 64), BF16), ((16, 16), F32), ((12, 2688, 64, PS), BF16),
         ((16, 152), I32), ((16,), I32), ((16,), jnp.bool_)]),
    "sparse_select_masked_2048_of_19456": (
        lambda sc, ln, m: sparse_select_pallas(sc, ln, live_rows(m),
                                               topk=2048),
        [((16, 152, PS), F32), ((16,), I32), ((16,), jnp.bool_)]),
    "paged_attention_sparse_masked_tables_of_152": (
        lambda q, kv, s, t, ln, sel, m: paged_attention_sparse_pallas(
            q, kv, s, t, ln, sel, 7, live_rows(m)),
        [((16, 32, HD), BF16), ((2, 12, 4, 2688, PS, HD), I8),
         ((2, 12, 4, 2688, PS), F32), ((16, 152), I32), ((16,), I32),
         ((16, 152 * PS), jnp.bool_), ((16,), jnp.bool_)]),
    # and the grouped matmul's third shape, 128 whole experts of 768: a
    # decode step's 128 pairs (one a hit expert: tiles mostly padding),
    # and a prompt's 4,096 tokens in tiles of 64
    # (sparse_attn_moe.PREFILL_TILE_ROWS, PREFILL_MOE_ROWS)
    "grouped_expert_matmul_decode_128_of_768": (
        lambda *a: _grouped(32, *a),
        [((128 + 128 * 32, 2048), BF16), ((12, 128, 2048, 1536), I8),
         ((12, 128, 1536), F32), ((132,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill_128_of_768": (
        lambda *a: _grouped(64, *a),
        [((32768 + 128 * 64, 2048), BF16), ((12, 128, 2048, 1536), I8),
         ((12, 128, 1536), F32), ((640,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill_down_128_of_768": (
        lambda *a: _grouped(64, *a),
        [((32768 + 128 * 64, 768), BF16), ((12, 128, 768, 2048), I8),
         ((12, 128, 2048), F32), ((640,), I32), ((1,), I32)]),
    # SmallThinker-21BA3B's stage
    # (benchmark/configs/smallthinker-21b-a3b-int8.json): 64 slots; a
    # WINDOW row's call with the step's mask, as _window_decode_once makes
    # it: a table of 34 pages over the 9-row window pool, a start a row;
    # a prompt's flash attention under the window (blocks behind it
    # skipped); the grouped matmul's fourth shape, 64 whole experts of 768
    # at a width of 2,560: a decode step's 384 pairs and a prompt's 4,096
    # tokens of 6 pairs in tiles of 64
    "paged_decode_int8_window_masked_tables_of_34": (
        lambda q, kv, s, t, ln, st, m: paged_attention_int8_window(
            q, kv, s, t, ln, 7, st, live=live_rows(m)),
        [((64, 28, HD), BF16), ((2, 9, 4, 2211, PS, HD), I8),
         ((2, 9, 4, 2211, PS), F32), ((64, 34), I32), ((64,), I32),
         ((64,), I32), ((64,), jnp.bool_)]),
    "flash_prefill_window_4096_of_8192": (
        lambda q, k, v, ln: flash_attention(q, k, v, causal=True,
                                            lengths=ln, window=4096),
        [((1, 28, 8192, HD), BF16)] + [((1, 4, 8192, HD), BF16)] * 2
        + [((1,), I32)]),
    "grouped_expert_matmul_decode_64_of_768": (
        lambda *a: _grouped(32, *a),
        [((384 + 64 * 32, 2560), BF16), ((12, 64, 2560, 1536), I8),
         ((12, 64, 1536), F32), ((76,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill_64_of_768": (
        lambda *a: _grouped(64, *a),
        [((24576 + 64 * 64, 2560), BF16), ((12, 64, 2560, 1536), I8),
         ((12, 64, 1536), F32), ((448,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill_down_64_of_768": (
        lambda *a: _grouped(64, *a),
        [((24576 + 64 * 64, 768), BF16), ((12, 64, 768, 2560), I8),
         ((12, 64, 2560), F32), ((448,), I32), ((1,), I32)]),
    # Kimi-Linear-48B-A3B's share of an 8-chip group
    # (benchmark/configs/kimi-linear-48b-a3b-int8-ep8.json): 80 slots; the
    # in-place delta-rule update of one KDA layer over the per-slot pool,
    # [32, 128, 128] float32 a slot (three row-to-column relayouts a head,
    # which interpret mode never lowers); the absorbed paged kernel at 32
    # heads over tables of 48 pages and seven rows; a latent prompt of
    # 1,536 at 32 heads; the grouped matmul's fifth shape, 32 held experts
    # of 1,024 at a width of 2,304: a decode step's 640 pairs' worth of
    # tiles (an eighth of them held) and a prompt's 12,288
    "kda_state_update_80_slots": (
        lambda state, o, n, a, k, q, v, b: kda_state_update_pallas(
            state, 4, o, n, a, k, q, v, b),
        [((20, 80, 32, 128, 128), F32), ((80,), I32), ((1,), I32)]
        + [((80, 32, 128), F32)] * 5),
    "paged_decode_latent_32_heads_tables_of_48": (
        lambda q, pool, t, ln: paged_attention_mla(
            q, pool, 3, t, ln, latent=512, scale=0.07),
        [((80, 32, 640), BF16), ((7, 3880, PS, 640), BF16),
         ((80, 48), I32), ((80,), I32)]),
    "flash_prefill_latent_32_heads_1536": (
        lambda q, k, v, ln: flash_attention(q, k, v, causal=True,
                                            lengths=ln, scale=0.07),
        [((1, 32, 1536, 256), BF16)] * 2 + [((1, 32, 1536, 128), BF16),
                                            ((1,), I32)]),
    "grouped_expert_matmul_decode_32_of_1024": (
        lambda *a: _grouped(32, *a),
        [((640 + 32 * 32, 2304), BF16), ((26, 32, 2304, 2048), I8),
         ((26, 32, 2048), F32), ((52,), I32), ((1,), I32)]),
    "grouped_expert_matmul_down_32_of_1024": (
        lambda *a: _grouped(32, *a),
        [((640 + 32 * 32, 1024), BF16), ((26, 32, 1024, 2304), I8),
         ((26, 32, 2304), F32), ((52,), I32), ((1,), I32)]),
    "grouped_expert_matmul_prefill_32_of_1024": (
        lambda *a: _grouped(128, *a),
        [((12288 + 32 * 128, 2304), BF16), ((26, 32, 2304, 2048), I8),
         ((26, 32, 2048), F32), ((128,), I32), ((1,), I32)]),
    # Xing4.0-29B-A4B's widths
    # (benchmark/configs/xing4.0-29b-a4b-int8-ep4.json): the mixing of
    # four streams of 3,584 around a branch, a decode step's 128 tokens
    # (blocks of 32) and a prefill group's 4 x 256 (blocks of 128), the
    # 38 expert blocks' leaves read whole by the block's index
    "hc_pre_decode_128": (
        lambda *a: _hc_pre(*a), _HC_PRE(128)),
    "hc_pre_prefill_1024": (
        lambda *a: _hc_pre(*a), _HC_PRE(1024)),
    "hc_post_decode_128": (
        lambda *a: _hc_post(*a), _HC_POST(128)),
    "hc_post_prefill_1024": (
        lambda *a: _hc_post(*a), _HC_POST(1024)),
}


def _hc_cfg():
    from generativeaiexamples_tpu.models.latent_moe import LatentMoeConfig
    return LatentMoeConfig(dim=3584, hc_mult=4)


def _hc_pre(x, phi, b, alpha):
    from generativeaiexamples_tpu.serving import hc_mix
    return hc_mix.hc_pre_pallas(_hc_cfg(), x, phi, b, alpha, 5)


def _hc_post(x, y, coef):
    from generativeaiexamples_tpu.serving import hc_mix
    return hc_mix.hc_post_pallas(_hc_cfg(), x, y, coef)


def _grouped(tm, x, q, s, tile_group, n_tiles):
    plan = moe.DispatchPlan(None, None, tile_group, n_tiles, None, tm)
    return moe.grouped_matmul_pallas(x, QuantizedTensor(q, s), 5, plan)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the decode program's q, k and v projections (PR 30) -----------------
# Where the head split and the rotary embedding are fused into the dot,
# XLA rewrites a projection as a per-head product and wants its weight in
# VMEM, contraction-minor: once a block and layer a `fusion` slices the
# int8 weight out of the stacked parameter in a transposed layout and a
# `copy` turns it back. A long block of a one-pass model amortises that
# over its steps; a short block and a looped model's pass loop cannot,
# and take the direct form (engine_model.direct_qkv): there no `fusion`
# and no `copy` may yield an int8 array of a weight's size. What the
# memory-space assignment prefetches by itself (`slice-start`/`-done`,
# `copy-start`/`-done`, their `ConcatBitcast`: asynchronous, and plentiful
# at two layers, where VMEM has room for everything) is not staging.

def _decoder(looped):
    import dataclasses

    from generativeaiexamples_tpu.models import llama

    cfg = llama.LlamaConfig(  # Mistral-7B-v0.3's widths, two layers
        vocab_size=32768, dim=4096, n_layers=2, n_heads=H, n_kv_heads=KH,
        head_dim=HD, mlp_dim=14336, rope_theta=1e6, rms_eps=1e-5,
        max_seq_len=32768, dtype=BF16)
    if looped:  # Ouro-2.6B's widths, two blocks run twice
        cfg = dataclasses.replace(
            cfg, vocab_size=49152, dim=2048, n_heads=16, n_kv_heads=16,
            mlp_dim=5632, n_passes=2, post_norms=True)
    return cfg


def _staged_weights(text, cfg):
    """(opcode, instruction, type) of every `fusion` and `copy` of the
    entry computation and the while bodies that yields an int8 array as
    large as a q/k/v weight or as the stack of them."""
    import re

    sizes = {cfg.dim * n * l for n in (cfg.n_heads * HD, cfg.n_kv_heads * HD)
             for l in (1, cfg.n_layers)}
    comps, bodies, cur = {}, set(), None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            if head.group(1):
                bodies.add(head.group(2))
        elif cur is not None:
            cur.append(line)
            bodies.update(re.findall(r"body=%?([\w.\-]+)", line))
    found = []
    for name in bodies:
        for line in comps[name]:
            m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) (fusion|copy)\(",
                         line)
            if not m:
                continue
            for dims in re.findall(r"s8\[([\d,]+)\]", m.group(2)):
                n = 1
                for d in dims.split(","):
                    n *= int(d)
                if n in sizes:
                    found.append((m.group(3), m.group(1), m.group(2)))
    return found


@functools.cache
def _compiled_decode(chip, looped, n_steps):
    """(cfg, compiled text, memory analysis, the pool's bytes) of the
    int8 `decode_multi_step` with kernels on, compiled once a module."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    cfg = _decoder(looped)
    slots = 32 if looped else B

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = on_chip(jax.eval_shape(functools.partial(
        llama.init_params_on_device, cfg, quantize=True)))
    pool = on_chip(jax.eval_shape(lambda: PagePool.zeros(
        cfg, slots * MAXP + 1, PS, dtype=I8)))
    compiled = em.decode_multi_step.lower(
        params, cfg, pool, arr((slots,), I32), arr((slots, MAXP), I32),
        arr((slots,), I32), arr((slots,), jnp.bool_), arr((slots,), F32),
        arr((slots,), F32), arr((slots,), I32), arr((2,), jnp.uint32),
        n_steps, True, sampling_flags=(True, False, False)).compile()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    return cfg, compiled.as_text(), compiled.memory_analysis(), pool_bytes


@pytest.mark.parametrize("looped,n_steps", [
    (False, 1), (False, 2), (False, 8), (True, 2)])
def test_decode_projections_stage_no_weight_in_a_short_or_looped_block(
        chip, looped, n_steps):
    from generativeaiexamples_tpu.serving import engine_model as em

    cfg, text, _, _ = _compiled_decode(chip, looped, n_steps)
    assert "tpu_custom_call" in text
    # the choice reads the passes and the block's length, nothing else
    assert em.direct_qkv(cfg, n_steps) == (
        looped or n_steps <= em.DIRECT_QKV_MAX_STEPS)
    assert not em.direct_qkv(_decoder(False), 8)  # the long block: as it was
    staged = _staged_weights(text, cfg)
    if em.direct_qkv(cfg, n_steps):
        assert not staged, staged
    else:
        assert {op for op, _, _ in staged} == {"fusion", "copy"}, staged


@pytest.mark.parametrize("looped,n_steps", [
    (False, 2), (False, 8), (True, 2)])
def test_decode_program_writes_its_new_row_in_place_and_with_no_scatter(
        chip, looped, n_steps):
    """The served decode programs write the new K and V in place, with no
    XLA scatter anywhere, the pool aliased through every call and nothing
    the size of the pool a temporary. A looped model's program holds ONE
    Pallas call a step and cache row, the attention's, which writes the
    row itself (PR 46: no kv_append_int8 call); a one-pass model's blocks,
    short and long, keep PR 32's two, the append's and the attention's
    (`engine_model.fuses_append`)."""
    import re

    cfg, text, mem, pool_bytes = _compiled_decode(chip, looped, n_steps)
    assert " scatter(" not in text

    def calls(name):
        return len(re.findall(rf"^\s*(?:ROOT )?%?{name}[\w.]* = ", text,
                              re.M))

    # a looped model's passes are a loop around its blocks
    rows = n_steps * cfg.n_layers
    fused = looped
    assert calls("paged_attention_int8") == rows
    assert calls("kv_append_int8") == (0 if fused else rows)
    assert ("kv_append_int8" in text) == (not fused)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        rows if fused else 2 * rows)
    # the step's mask is turned into the kernels' walk ONCE a program
    # (`active` does not change inside a block): one sort, whatever the
    # steps and layers (PR 41)
    assert len(re.findall(r" sort\(", text)) == 1, "live_rows, once"
    assert mem.alias_size_in_bytes >= pool_bytes
    # (staged weights and logits are a quarter of this small pool)
    assert mem.temp_size_in_bytes < pool_bytes // 2, mem.temp_size_in_bytes


# -- the decode step's K/V append (PR 32) ---------------------------------
# One in-place Pallas call a cache row (serving/kv_append_int8.py) where
# XLA ran four scatters. What interpret mode cannot see: that the tile and
# scale-row DMAs are aligned to the chip's tiling at the cells' shapes,
# that the pool is ALIASED through the call (no temporary: a copy of a 6
# or 11 GB pool is "two scatter traps" of docs/ENGINEERING_NOTES.md in a
# new coat), and that it partitions under shard_map.

# (cache rows, kv heads, slots, pages): the three shapes the cells run
APPEND_SHAPES = {
    "mistral-7b": (32, 8, 64, 768),
    "ouro-2.6b": (192, 16, 32, 112),       # a half over SPLIT_KV_BYTES
    "mistral-small-tp4": (40, 8, 64, 3072),  # 2 kv heads a chip
}


@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
@pytest.mark.parametrize("name", sorted(APPEND_SHAPES))
def test_kv_append_kernel_compiles_in_place_for_v5e(topo, chip, name, masked):
    """... and with the step's mask (PR 41: the loops run over the live
    slots), replicated under the mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from generativeaiexamples_tpu.serving.kv_cache import QuantPagePool

    rows, kv_heads, slots, pages = APPEND_SHAPES[name]
    mesh = None
    if name.endswith("tp4"):  # the 2x2 topology, kv heads on "tensor"
        mesh = Mesh(np.array(topo.devices), ("tensor",))
    specs = {"pool": PartitionSpec(None, None, "tensor"),
             "new": PartitionSpec(None, "tensor"), None: PartitionSpec()}

    def arr(shape, dtype, kind=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=(
            NamedSharding(mesh, specs[kind]) if mesh is not None else chip))

    def append(kv, s, row, page_idx, offset, codes, scales, active):
        # through the pool's own dispatch: shard_map under the mesh
        pool = QuantPagePool(kv, s, PS)._append_kernel(
            row, page_idx, offset, mesh, codes, scales,
            live_rows(active) if masked else None)
        return pool.kv, pool.s

    compiled = jax.jit(append, donate_argnums=(0, 1)).lower(
        arr((2, rows, kv_heads, pages, PS, HD), I8, "pool"),
        arr((2, rows, kv_heads, pages, PS), F32, "pool"),
        arr((), I32), arr((slots,), I32), arr((slots,), I32),
        arr((2, kv_heads, slots, HD), I8, "new"),
        arr((2, kv_heads, slots), F32, "new"),
        arr((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "kv_append_int8" in text and "tpu_custom_call" in text
    assert " scatter(" not in text
    mem = compiled.memory_analysis()  # of one device
    pool_bytes = 2 * rows * kv_heads * pages * PS * (HD + 4)
    if mesh is not None:
        pool_bytes //= mesh.size
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 1000, mem


@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
@pytest.mark.parametrize("name", sorted(APPEND_SHAPES))
def test_the_attention_call_writes_the_new_row_in_place_for_v5e(
        topo, chip, name, masked):
    """PR 46: the attention call with the step's new row as an operand,
    through the dispatch (shard_map under the mesh), at the three pools'
    shapes and the cells' table widths: what interpret mode cannot see
    is that the tile's dynamic sublane slice and its words of four rows
    lower, that the write tiles fit beside the blocks' buffers in VMEM
    (Ouro's 541 KB pages, split descriptors), and that the pool is
    ALIASED through the call, with no temporary."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_dispatch)

    rows, kv_heads, slots, pages = APPEND_SHAPES[name]
    heads, width = {"mistral-7b": (32, 20), "ouro-2.6b": (16, 4),
                    "mistral-small-tp4": (32, 4)}[name]
    mesh = None
    if name.endswith("tp4"):  # the 2x2 topology, kv heads on "tensor"
        mesh = Mesh(np.array(topo.devices), ("tensor",))
    specs = {"pool": PartitionSpec(None, None, "tensor"),
             "new": PartitionSpec(None, "tensor"), None: PartitionSpec()}

    def arr(shape, dtype, kind=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=(
            NamedSharding(mesh, specs[kind]) if mesh is not None else chip))

    def attend(q, kv, s, table, lengths, codes, scales, active):
        return paged_attention_dispatch(
            q, kv, None, table, lengths, k_scales=s, layer=1,
            use_pallas=True, mesh=mesh, new=(codes, scales),
            live=live_rows(active) if masked else None)

    compiled = jax.jit(attend, donate_argnums=(1, 2)).lower(
        arr((slots, heads, HD), BF16, "new"),
        arr((2, rows, kv_heads, pages, PS, HD), I8, "pool"),
        arr((2, rows, kv_heads, pages, PS), F32, "pool"),
        arr((slots, width), I32), arr((slots,), I32),
        arr((2, kv_heads, slots, HD), I8, "new"),
        arr((2, kv_heads, slots), F32, "new"),
        arr((slots,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "paged_attention_int8" in text and "tpu_custom_call" in text
    assert "kv_append_int8" not in text and " scatter(" not in text
    assert "all-gather" not in text and "all-reduce" not in text
    mem = compiled.memory_analysis()  # of one device
    pool_bytes = 2 * rows * kv_heads * pages * PS * (HD + 4)
    if mesh is not None:
        pool_bytes //= mesh.size
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 1000, mem


@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
def test_paged_attention_int8_partitions_over_the_kv_heads(topo, masked):
    """Mistral-Small-24B's decode attention under TP=4 through the
    dispatch's shard_map, 2 KV heads a chip, with and without the step's
    mask (replicated, as the tables are)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_dispatch)

    mesh = Mesh(np.array(topo.devices), ("tensor",))
    specs = {"heads": PartitionSpec(None, "tensor"),
             "pool": PartitionSpec(None, None, "tensor"),
             None: PartitionSpec()}

    def arr(shape, dtype, kind=None):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, specs[kind]))

    def attend(q, kv, s, table, lengths, active):
        return paged_attention_dispatch(
            q, kv, None, table, lengths, k_scales=s, layer=1,
            use_pallas=True, mesh=mesh,
            live=live_rows(active) if masked else None)

    text = jax.jit(attend).lower(
        arr((B, H, HD), BF16, "heads"),
        arr((2, L, KH, P, PS, HD), I8, "pool"),
        arr((2, L, KH, P, PS), F32, "pool"), arr((B, MAXP), I32),
        arr((B,), I32), arr((B,), jnp.bool_)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


# -- a prefill program computes only its live rows (PR 38) -----------------
# engine_model.prefill_batch_step with ONE prompt picks, by one lax.switch
# on its length, the program on tokens[:, :S_k] for the few S_k of its
# bucket (engine_model.prefill_row_counts). What only the chip's compiler
# says: that the page pool still goes through the conditional IN PLACE (a
# copy of a 6 GB pool would not fit), at the two configurations that have
# the 2,048 bucket; that a group of several is the one program it was; and
# that the 128 bucket, with its one height, lowers to the parent's text.


def _prefill_lowered(chip, config_name, n, bucket):
    import json

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           config_name + ".json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    mcfg, params, pool, _ = architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    lowered = em.prefill_batch_step.lower(
        params, mcfg, pool, arr((n, bucket), I32), arr((n,), I32),
        arr((n, bucket // ecfg.page_size), I32), arr((n,), F32),
        arr((n,), F32), arr((n,), I32), arr((2,), jnp.uint32), True,
        sampling_flags=(True, False, False))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    return lowered, pool_bytes, ecfg.page_size


@pytest.mark.parametrize("config_name,n", [
    ("mistral-7b-v0.3-int8", 1), ("mistral-7b-v0.3-int8", 4),
    ("rag-arctic-l-mistral-7b", 1), ("rag-arctic-l-mistral-7b", 4)])
def test_prefill_2048_switches_between_its_heights_in_place(
        chip, config_name, n):
    from generativeaiexamples_tpu.serving import engine_model as em

    lowered, pool_bytes, ps = _prefill_lowered(chip, config_name, n, 2048)
    heights = em.prefill_row_counts(2048, ps, n)
    assert heights == ((1024, 1280, 1536, 1792, 2048) if n == 1 else (2048,))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == (n == 1)
    # every height holds its own flash kernel
    assert text.count("tpu_custom_call") == len(heights)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # activations only (0.27 GiB for one row of 2,048, 1.1 GiB for four)
    assert mem.temp_size_in_bytes < pool_bytes // 4, mem.temp_size_in_bytes


def _without_kernel_payload(text):
    """A lowered program's text less the serialized Mosaic kernel of its
    custom calls (which holds the kernel's source lines)."""
    import re

    return re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)


# taken on PR 38's PARENT (31359e9), by this file's own functions
PARENT_PREFILL_128 = {1: "f6cd2d59cc3fef48", 4: "57275932bba9e5c5"}
PARENT_FLASH_ONE_BLOCK = "323dd06547d6f8a1"


@pytest.mark.parametrize("n", sorted(PARENT_PREFILL_128))
def test_prefill_128_lowers_to_the_text_the_parent_did(chip, n):
    import hashlib

    lowered, _, _ = _prefill_lowered(chip, "mistral-7b-v0.3-int8", n, 128)
    text = _without_kernel_payload(lowered.as_text())
    assert "stablehlo.case" not in text and "tpu_custom_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_PREFILL_128[n]


def test_a_one_block_flash_call_is_the_kernel_the_parent_had():
    """... and the kernel inside it: with one q block and one k block no
    test on `lengths` is added (the jaxpr holds no source line)."""
    import hashlib

    q = jax.ShapeDtypeStruct((1, H, 128, HD), BF16)
    kv = jax.ShapeDtypeStruct((1, KH, 128, HD), BF16)
    jaxpr = jax.make_jaxpr(lambda q, k, v, ln: flash_attention(
        q, k, v, causal=True, lengths=ln))(
            q, kv, kv, jax.ShapeDtypeStruct((1,), I32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == \
        PARENT_FLASH_ONE_BLOCK


# -- the fold moved, the sparse kernel stayed (PR 45) ------------------------
# `_fold_block` lives in serving/paged_attention_int8.py since PR 45 and
# takes a page's mask through a callable; `paged_attention_sparse` at the
# Keye cell's shapes (16 slots, tables of 128, 32/4 heads of 128, twelve
# cache rows) still traces to the jaxpr it had on PR 45's PARENT
# (7ac0c47), kernel body and all: the same operations in the same order.
PARENT_SPARSE_KERNEL = "a7a076a0b6b09518"


def test_the_sparse_kernel_is_the_jaxpr_the_parent_had():
    import hashlib

    from generativeaiexamples_tpu.serving.paged_attention_sparse import (
        paged_attention_sparse_pallas)

    B, L, P, maxp = 16, 12, 2048, 128
    jaxpr = jax.make_jaxpr(
        lambda q, kv, s, t, ln, sel: paged_attention_sparse_pallas(
            q, kv, s, t, ln, sel, 3))(
        jax.ShapeDtypeStruct((B, 32, HD), BF16),
        jax.ShapeDtypeStruct((2, L, 4, P, 128, HD), jnp.int8),
        jax.ShapeDtypeStruct((2, L, 4, P, 128), F32),
        jax.ShapeDtypeStruct((B, maxp), I32),
        jax.ShapeDtypeStruct((B,), I32),
        jax.ShapeDtypeStruct((B, maxp * 128), jnp.bool_))
    text = str(jaxpr)
    assert "exp" in text and "dot_general" in text  # the body is in it
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_SPARSE_KERNEL


# -- the live-row walk leaves a latent model's programs alone (PR 41) -------
# The step's mask reaches the int8 pool's two kernels through
# engine_model._decode_once and _hybrid_decode_once alone. A model whose
# cache row is a latent runs neither kernel (`ax-k1-ep16.decode-closed128`,
# the cell that refused PR 40 by 0.17 point): its decode programs and its
# prefill programs, lowered for the chip with kernels on at A.X-K1's served
# sizes, are the text they were on PR 41's PARENT (d9d8463), taken there by
# these functions.
PARENT_LATENT = {"decode_multi_step_k8": "cb1fb18d90994585",
                 "decode_step": "bb7290bf71fda8b3",
                 "prefill_1x128": "c15570a7b01b3675",
                 "prefill_4x384": "7d5d82e589e7ad08"}


def _latent_lowered(chip):
    programs, mcfg = _step_programs_lowered(chip, "ax-k1-int8-ep16")
    assert mcfg.latent_row is not None
    return programs


def _step_programs_lowered(chip, name):
    """(a configuration's decode block, decode step, lone prefill of 128
    and group of 4 x 384, lowered for the chip with kernels on at its
    served sizes; its model configuration)."""
    import json

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    mcfg, params, pool, _ = architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, maxp = ecfg.max_batch_size, ecfg.max_seq_len // ecfg.page_size
    K = ecfg.decode_steps_per_dispatch
    state = (arr((slots,), I32), arr((slots, maxp), I32), arr((slots,), I32))
    return {
        f"decode_multi_step_k{K}": em.decode_multi_step.lower(
            params, mcfg, pool, *state, arr((slots,), jnp.bool_),
            arr((slots,), F32), arr((slots,), F32), arr((slots,), I32),
            arr((2,), jnp.uint32), K, True,
            sampling_flags=(True, False, False)),
        "decode_step": em.decode_step.lower(params, mcfg, pool, *state, True),
        "prefill_1x128": _prefill_lowered(chip, name, 1, 128)[0],
        "prefill_4x384": _prefill_lowered(chip, name, 4, 384)[0],
    }, mcfg


def test_a_latent_models_programs_lower_to_the_text_the_parent_did(chip):
    import hashlib
    import json

    got = {k: hashlib.sha256(_without_kernel_payload(
        v.as_text()).encode()).hexdigest()[:16]
        for k, v in _latent_lowered(chip).items()}
    assert got == PARENT_LATENT, json.dumps(got)


# -- a latent pool under the state pool leaves granite's programs alone (PR 48)
# `kv_cache.HybridPool` may hold a LatentPagePool since PR 48, the state
# kernels share their walk over the live slots
# (`ssm_state_update.live_slots`, `walked_slot`), and `latent_moe` took
# three options. `granite4h-small.decode-closed96` runs the first two: its
# decode and prefill programs, lowered for the chip with kernels on at the
# configuration's served sizes, are the text they were on PR 48's PARENT
# (a75da57), taken there by `_step_programs_lowered`.
PARENT_HYBRID = {"decode_multi_step_k8": "06f0ddb75bf23d60",
                 "decode_step": "6066480c1075772b",
                 "prefill_1x128": "c659e638f18dc785",
                 "prefill_4x384": "1e8212f55cfde0fc"}


def test_a_hybrid_models_programs_lower_to_the_text_the_parent_did(chip):
    import hashlib
    import json

    programs, mcfg = _step_programs_lowered(chip, "granite-4.0-h-small-int8")
    assert mcfg.recurrent_state.layers == 9
    got = {k: hashlib.sha256(_without_kernel_payload(
        v.as_text()).encode()).hexdigest()[:16] for k, v in programs.items()}
    assert got == PARENT_HYBRID, json.dumps(got)


# -- the residual seam leaves the one-stream programs alone (PR 57) ----------
# `latent_moe`'s branches return their OUTPUT since PR 57 and
# `hyper_connections.open` / `close` put it into the stream; with one
# stream (`hc_mult` 1) that is `x` and `x + y`. A.X-K1's four programs are
# held above (PARENT_LATENT: unchanged since PR 41's parent). Kimi-Linear's
# walk takes its expert layers from the same functions: its decode and
# prefill programs, lowered for the chip with kernels on at the
# configuration's served sizes, are the text they were on PR 57's PARENT
# (ecb6f1b), taken there by `_step_programs_lowered`.
PARENT_LINEAR = {"decode_multi_step_k8": "b12e2ed0705a6323",
                 "decode_step": "f84ec239f473fda5",
                 "prefill_1x128": "53b3b1e7a18d36f4",
                 "prefill_4x384": "76063bae9767719e"}


def test_a_linear_models_programs_lower_to_the_text_the_parent_did(chip):
    import hashlib
    import json

    programs, mcfg = _step_programs_lowered(
        chip, "kimi-linear-48b-a3b-int8-ep8")
    assert mcfg.recurrent_state.layers == 20
    got = {k: hashlib.sha256(_without_kernel_payload(
        v.as_text()).encode()).hexdigest()[:16] for k, v in programs.items()}
    assert got == PARENT_LINEAR, json.dumps(got)


# -- linear attention beside latent attention: the configuration's own
# shapes (PR 48). The decode program of `kimi-linear-48b-a3b-int8-ep8`,
# lowered for the chip from its architecture entry's `compile_shapes` with
# kernels on: a KDA layer updates its slots' state through a kernel of its
# own name (`trace_kernel` matches by substring: `ssm_state_update` must
# not find it), a latent layer attends through A.X-K1's, and every expert
# layer runs the grouped matmul twice.
def test_the_linear_decode_program_runs_its_kernels_under_their_names(chip):
    import json

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import (
        HybridPool, LatentPagePool)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b-int8-ep8.json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    mcfg, params, pool, mesh = architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])
    assert mesh is None and mcfg.latent_row == (512, 64)
    assert isinstance(pool, HybridPool)
    assert isinstance(pool.pages, LatentPagePool)
    assert pool.state.shape == (20, 80, 32, 128, 128)
    assert pool.state.dtype == F32
    assert pool.tail.shape == (20, 3, 80, 12288) and pool.tail.dtype == BF16
    assert pool.pages.c.shape == (7, 3880, 128, 640)
    # weights 7.3 GB, state and tails 3.47 GB, latent rows 4.45 GB
    total = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((params, pool)))
    assert 15.1e9 < total < 15.4e9, total

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, maxp = ecfg.max_batch_size, ecfg.max_seq_len // ecfg.page_size
    assert (slots, maxp) == (80, 48)
    text = em.decode_multi_step.lower(
        params, mcfg, pool, arr((slots,), I32), arr((slots, maxp), I32),
        arr((slots,), I32), arr((slots,), jnp.bool_), arr((slots,), F32),
        arr((slots,), F32), arr((slots,), I32), arr((2,), jnp.uint32), 1,
        True, sampling_flags=(True, False, False)).as_text()
    for kernel, calls in (("kda_state_update", 20),
                          ("paged_attention_mla", 7),
                          ("moe_grouped_matmul_int8", 52)):
        assert text.count(f'kernel_name = "{kernel}"') == calls, kernel
    assert "ssm_state_update" not in text and "kv_append_int8" not in text
    # the step's mask is turned into the state kernel's walk ONCE a step
    # (`kda_state_update.live_slots`), not once a layer
    assert text.count("stablehlo.sort") == 1


# -- learned sparse attention: the configuration's own shapes (PR 42) -------
# The decode program of `keye-vl-2.0-30b-a3b-int8`, lowered for the chip
# from its architecture entry's `compile_shapes` with kernels on: every
# layer appends through the int8 pool's kernel and runs the three new ones,
# each under the name the benchmark's readers look for, and the attention's
# name is not the index kernel's (`trace_kernel` matches by substring).
def test_the_sparse_decode_program_runs_its_kernels_under_their_names(chip):
    import json

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-int8.json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    mcfg, params, pool, mesh = architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])
    assert mesh is None and mcfg.index_row == 64
    assert pool.idx.shape == (12, 2688, 64, 128)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, maxp = ecfg.max_batch_size, ecfg.max_seq_len // ecfg.page_size
    assert (slots, maxp) == (16, 152)
    text = em.decode_multi_step.lower(
        params, mcfg, pool, arr((slots,), I32), arr((slots, maxp), I32),
        arr((slots,), I32), arr((slots,), jnp.bool_), arr((slots,), F32),
        arr((slots,), F32), arr((slots,), I32), arr((2,), jnp.uint32), 1,
        True, sampling_flags=(True, False, False)).as_text()
    # (the three are jitted functions of their own: one body each in the
    # text, called once a layer; the grouped matmul is inlined, two a layer)
    for kernel, bodies in (("sparse_index_scores", 1), ("sparse_select", 1),
                           ("paged_attention_sparse", 1),
                           ("kv_append_int8", 1),
                           ("moe_grouped_matmul_int8", 24)):
        assert text.count(f'kernel_name = "{kernel}"') == bodies, kernel
    for fn in ("sparse_index_scores_pallas", "sparse_select_pallas",
               "paged_attention_sparse_pallas"):
        assert text.count(f"call @{fn}") == 12, fn
    assert "paged_attention" not in "sparse_index_scores sparse_select"


# -- the index scores' walk is one architecture's (PR 56) -------------------
# `serving/sparse_index_scores.py` changed its walk (a chain of copies over
# the live slots, whole blocks, `_walk`'s depth) and `served_sparse.
# decode_once` is its one caller: the sparse decode program above still
# holds ONE body of it called twelve times, the kernel compiles for the
# chip with its new scratch shapes (`sparse_index_scores_masked_tables_of_
# 152`, above), and a Llama's decode block, lowered for the chip with
# kernels on at Mistral-7B's served sizes, is the text it was on PR 56's
# PARENT (c937e79), taken there by these lines.
PARENT_MISTRAL_DECODE_BLOCK = "9ac438bd35f80a0e"


def test_a_llamas_decode_block_lowers_to_the_text_the_parent_did(chip):
    import hashlib
    import json

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mistral-7b-v0.3-int8.json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    mcfg, params, pool, _ = architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, maxp = ecfg.max_batch_size, ecfg.max_seq_len // ecfg.page_size
    text = em.decode_multi_step.lower(
        params, mcfg, pool, arr((slots,), I32), arr((slots, maxp), I32),
        arr((slots,), I32), arr((slots,), jnp.bool_), arr((slots,), F32),
        arr((slots,), F32), arr((slots,), I32), arr((2,), jnp.uint32),
        ecfg.decode_steps_per_dispatch, True,
        sampling_flags=(True, False, False)).as_text()
    assert 'kernel_name = "sparse_index_scores"' not in text
    assert hashlib.sha256(_without_kernel_payload(
        text).encode()).hexdigest()[:16] == PARENT_MISTRAL_DECODE_BLOCK


# -- window and full attention in one model: the configuration's own shapes
# (PR 44). The decode program of `smallthinker-21b-a3b-int8`, lowered for
# the chip from its architecture entry's `compile_shapes` with kernels on:
# every layer appends through the int8 pool's kernel into its own group
# of rows, a global layer attends through the kernel every int8 pool has
# and a window layer through the same body under a name of its own, which
# the benchmark's readers tell apart (`trace_kernel` matches by substring:
# `paged_attention` finds both, `paged_attention_int8_window` the one).
def test_the_window_decode_program_runs_its_kernels_under_their_names(chip):
    import json

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import WindowTables

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "smallthinker-21b-a3b-int8.json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    mcfg, params, pool, mesh = architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])
    assert mesh is None and tuple(mcfg.window_rows) == (4096, 3, 9)
    assert pool.glob.kv.shape == (2, 3, 4, 8448, 128, 128)
    assert pool.win.kv.shape == (2, 9, 4, 2211, 128, 128)
    # each half of each group stays under the one-descriptor limit
    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        SPLIT_KV_BYTES)
    import math
    assert math.prod(pool.glob.kv.shape[1:]) < SPLIT_KV_BYTES
    assert math.prod(pool.win.kv.shape[1:]) < SPLIT_KV_BYTES

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, maxp = ecfg.max_batch_size, ecfg.max_seq_len // ecfg.page_size
    assert (slots, maxp) == (64, 128)
    tables = WindowTables(arr((slots, maxp), I32), arr((slots, 34), I32),
                          arr((slots,), I32))
    text = em.decode_multi_step.lower(
        params, mcfg, pool, arr((slots,), I32), tables,
        arr((slots,), I32), arr((slots,), jnp.bool_), arr((slots,), F32),
        arr((slots,), F32), arr((slots,), I32), arr((2,), jnp.uint32), 1,
        True, sampling_flags=(True, False, False)).as_text()
    # (a jitted function is one body in the text, called once a layer of
    # its kind; the append has a body a group of rows, whose shapes
    # differ; the grouped matmul is inlined, two a layer)
    for kernel, bodies in (("paged_attention_int8_window", 1),
                           ("_int8_kernel", 1), ("kv_append_int8", 2),
                           ("moe_grouped_matmul_int8", 24)):
        assert text.count(f'kernel_name = "{kernel}"') == bodies, kernel
    assert text.count("call @paged_attention_int8_window") == 9
    assert text.count("call @paged_attention_int8(") == 3


# -- gated attention over window and global rows beside a share of the
# experts: the configuration's own shapes (PR 52). The step programs of
# `trinity-large-preview-int8-ep8`, COMPILED for the chip from its
# architecture entry's `compile_shapes` with kernels on: both pools, the
# 20,480-row prefill program (its token-wise parts in five chunks of rows,
# the prompt's flash attention under the window and without it, the
# grouped matmul's sixth shape in tiles of 64 rows) and the decode blocks
# of 1, 2 and 8 steps (tables of 224 and of 34 pages, a score tile of
# 8 x 6); the compiler's own count of a program's arguments and
# temporaries stays under what a v5e offers.
V5E_BYTES = 16.9e9


@pytest.fixture(scope="module")
def gated_window(chip):
    import json

    from benchmark import architectures
    from benchmark.harness import system

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-large-preview-int8-ep8.json")) as fh:
        config = json.load(fh)
    ecfg = system.engine_config(config)
    return (ecfg,) + architectures.load(config).compile_shapes(
        config, ecfg, [next(iter(chip.device_set))])


def _program_bytes(lowered):
    m = lowered.compile().memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_the_gated_window_pools_are_the_configurations(gated_window):
    import math

    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        SPLIT_KV_BYTES)
    ecfg, mcfg, params, pool, mesh = gated_window
    assert mesh is None and tuple(mcfg.window_rows) == (4096, 2, 7)
    assert pool.glob.kv.shape == (2, 2, 8, 7232, 128, 128)
    assert pool.win.kv.shape == (2, 7, 8, 1123, 128, 128)
    # each half of each group stays under the one-descriptor limit
    assert math.prod(pool.glob.kv.shape[1:]) < SPLIT_KV_BYTES
    assert math.prod(pool.win.kv.shape[1:]) < SPLIT_KV_BYTES
    held = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves((params, pool)))
    # weights and pools fill 85 % of the chip: 14.4 GB of 16.9
    assert 0.80 * V5E_BYTES < held < 0.86 * V5E_BYTES


@pytest.mark.parametrize("n_steps", [1, 2, 8])
def test_the_gated_window_decode_block_compiles_for_v5e(chip, gated_window,
                                                        n_steps):
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import WindowTables

    ecfg, mcfg, params, pool, _ = gated_window

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, maxp = ecfg.max_batch_size, ecfg.max_seq_len // ecfg.page_size
    assert (slots, maxp) == (32, 224)
    tables = WindowTables(arr((slots, maxp), I32), arr((slots, 34), I32),
                          arr((slots,), I32))
    lowered = em.decode_multi_step.lower(
        params, mcfg, pool, arr((slots,), I32), tables,
        arr((slots,), I32), arr((slots,), jnp.bool_), arr((slots,), F32),
        arr((slots,), F32), arr((slots,), I32), arr((2,), jnp.uint32),
        n_steps, True, sampling_flags=(True, False, False))
    if n_steps == 1:
        text = lowered.as_text()
        for kernel, bodies in (("paged_attention_int8_window", 1),
                               ("_int8_kernel", 1), ("kv_append_int8", 2),
                               ("moe_grouped_matmul_int8", 16)):
            assert text.count(f'kernel_name = "{kernel}"') == bodies, kernel
        assert text.count("call @paged_attention_int8_window") == 7
        assert text.count("call @paged_attention_int8(") == 2
    assert _program_bytes(lowered) < V5E_BYTES


def test_the_gated_window_longest_prefill_compiles_for_v5e(chip,
                                                           gated_window):
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import WindowTables

    ecfg, mcfg, params, pool, _ = gated_window

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    bucket = max(ecfg.prefill_buckets)
    assert bucket == 20480
    tables = WindowTables(arr((1, bucket // 128), I32),
                          arr((1, bucket // 128), I32))
    lowered = em.prefill_batch_step.lower(
        params, mcfg, pool, arr((1, bucket), I32), arr((1,), I32), tables,
        arr((1,), F32), arr((1,), F32), arr((1,), I32),
        arr((2,), jnp.uint32), True, sampling_flags=(True, False, False))
    assert _program_bytes(lowered) < V5E_BYTES
