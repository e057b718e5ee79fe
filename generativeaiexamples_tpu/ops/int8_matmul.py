"""Pallas int8 weight-dequant matmul: y = x @ q_int8 * scale.

Motivation: decode is weight-bandwidth-bound and weight-only int8 only
pays off if the weight crosses HBM as int8. Microbenches suggested
XLA's convert(int8)->bf16 dot wasn't capturing that win (llama3.2-1b
decodes 4404 tok/s bf16 vs 4282 int8 — no speedup from halving weight
bytes).

Measured verdict (v5e, llama3-8b int8 decode, B=64): the XLA path does
1811 tok/s; this kernel 1424 (K-blocked) / 1458 (full-K) — XLA's fused
matmul pipeline already saturates the platform's effective bandwidth,
and a hand-tiled kernel only adds overhead. It therefore ships OFF by
default (ENGINE_PALLAS_INT8=1 opts in) and stays as tested substrate
for fused-dequant experiments; the engine keeps the XLA path.

Layout: x [B, K] bf16/f32, q [K, M] int8, scale [M] f32 -> y [B, M].
Two schedules: full-K M-tiles (one big DMA per step) when the weight
block fits VMEM, else K-blocked with a f32 VMEM accumulator.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block(dim: int, candidates=(1024, 512, 256, 128)) -> Optional[int]:
    for c in candidates:
        if dim % c == 0:
            return c
    return None


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # [B, bk]
    w = q_ref[...].astype(x.dtype)  # int8 -> compute dtype, in VMEM
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[0].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _kernel_fullk(x_ref, q_ref, s_ref, o_ref):
    """One M-tile per grid step over the FULL K: a single big int8 DMA
    per step pipelines better than many small K-blocks with a carried
    accumulator."""
    x = x_ref[...]
    w = q_ref[...].astype(x.dtype)
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def int8_matmul(x: jax.Array, q: jax.Array, scale: jax.Array, *,
                out_dtype=None, interpret: bool = False) -> jax.Array:
    """x [B, K] @ q [K, M] int8, scaled per output column. Returns
    [B, M] in out_dtype (default x.dtype). Raises ValueError when the
    shape doesn't tile (callers fall back to the XLA path)."""
    B, K = x.shape
    K2, M = q.shape
    assert K == K2, (x.shape, q.shape)
    out_dtype = out_dtype or x.dtype
    bk = _pick_block(K)
    bm = _pick_block(M)
    # Row tile: the full B (decode batches are 8..256 and fit VMEM).
    if bk is None or bm is None or B % 8 or B > 1024:
        raise ValueError(f"untileable int8 matmul shape {x.shape}x{q.shape}")
    # Full-K M-tiles when the weight block fits a double-buffered VMEM
    # budget; K-blocked accumulation otherwise.
    if K * bm <= 4 << 20:
        out = pl.pallas_call(
            _kernel_fullk,
            grid=(M // bm,),
            in_specs=[
                pl.BlockSpec((B, K), lambda mi: (0, 0)),
                pl.BlockSpec((K, bm), lambda mi: (0, mi)),
                # scale as [1, M]: 1D operands inherit XLA's 1024-lane
                # tiling; 2D tiles (8,128).
                pl.BlockSpec((1, bm), lambda mi: (0, mi)),
            ],
            out_specs=pl.BlockSpec((B, bm), lambda mi: (0, mi)),
            out_shape=jax.ShapeDtypeStruct((B, M), out_dtype),
            interpret=interpret,
        )(x, q, scale.reshape(1, M))
        return out

    n_k, n_m = K // bk, M // bm
    grid = (n_m, n_k)  # K innermost: accumulator carried in scratch
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, bk), lambda mi, ki: (0, ki)),
            pl.BlockSpec((bk, bm), lambda mi, ki: (ki, mi)),
            pl.BlockSpec((1, bm), lambda mi, ki: (0, mi)),
        ],
        out_specs=pl.BlockSpec((B, bm), lambda mi, ki: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((B, M), out_dtype),
        scratch_shapes=[pltpu.VMEM((B, bm), jnp.float32)],
        interpret=interpret,
    )(x, q, scale.reshape(1, M))
    return out
