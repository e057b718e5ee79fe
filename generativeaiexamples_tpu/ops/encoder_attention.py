"""Pallas TPU kernel for ENCODER (bidirectional, padding-masked)
attention at short sequence lengths.

Why not the flash kernel: BERT-class encoders run head_dim 64 and
S <= 512, where the flash kernel's grid — one step per (batch, head,
q-block, k-block) — costs more in per-grid-step overhead than the
attention math itself (measured ~100 us/step x 512+ steps for
arctic-embed-l; scripts/decompose_bert_forward.py). At S <= 512 a
whole per-head problem fits VMEM, so this kernel runs one grid step
per (batch row, group of g_heads heads) with a STATIC unrolled loop
over the group (a dynamic fori over heads de-pipelines Mosaic —
measured slower than the flash kernel it was meant to beat) and a
plain (not online) softmax over full score rows:

    grid (B, H // g):  blocks [1, g, S, D] -> per head in group:
        scores = q_h @ k_h^T * scale     (f32, [S, S] in VMEM)
        mask keys >= lengths[b] to -inf, softmax, @ v_h

Numerics match ops.attention.mha_reference (tests, interpret mode).
The decode/prefill paths keep the flash kernel — causal masking and
long-S q-offset chunking genuinely need its blocked structure.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _encoder_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, *,
                    scale: float, g_heads: int, seq: int):
    b = pl.program_id(0)
    valid = lengths_ref[b]
    key_mask = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1) < valid
    for g in range(g_heads):  # static unroll: keeps Mosaic pipelined
        # Dots run on the INPUT dtype (bf16 in production: 2x MXU rate)
        # with f32 accumulation — the same contract XLA's bf16
        # attention uses; softmax stays f32.
        q = q_ref[0, g]
        k = k_ref[0, g]
        v = v_ref[0, g]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(key_mask, s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general(
            (p / denom).astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, g] = o.astype(o_ref.dtype)


def encoder_attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,
    v: jax.Array,
    lengths: Optional[jax.Array] = None,  # [B] valid tokens
    *,
    scale: Optional[float] = None,
    g_heads: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    if g_heads is None:
        # Largest group that divides H, capped at 8: measured best for
        # BERT-large (G sweep: 1 -> 223 ms, 8 -> 178 ms full forward at
        # B=32; G=16 overflows VMEM). 6 serves H=12 (BERT-base).
        g_heads = next(g for g in (8, 6, 4, 2, 1) if H % g == 0)
    assert H % g_heads == 0, (H, g_heads)
    kernel = functools.partial(_encoder_kernel, scale=scale,
                               g_heads=g_heads, seq=S)
    blk = pl.BlockSpec((1, g_heads, S, D), lambda b, h, L: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // g_heads),
        in_specs=[blk, blk, blk],
        out_specs=blk,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
