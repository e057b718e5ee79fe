"""Paged decode attention over a LATENT page pool, absorbed form.

A latent-attention (MLA) layer caches ONE row per token, shared by all
heads: `[c_kv ; k_rope]`, the normed key/value latent (width C) and the
one rotated key head (width R). Decoding never builds per-head keys and
values from it: the key up-projection is absorbed into the query
(`q_lat_h = q_nope_h W_kb_h^T`, outside this kernel) and the value
up-projection into the output (`o_h = o_lat_h W_vb_h`, outside too), so
per slot this is

    s_h(u) = [q_lat_h ; q_rope_h] . [c_kv(u) ; k_rope(u)] * scale
    o_lat_h = sum_u softmax_u(s_h) c_kv(u)

H query heads against one [page_size, C + R] page at a time, online
softmax. Layouts:

  q          [B, H, W]            one token a slot, q_lat ; q_rope ; 0
  pool       [rows, P, ps, W]     the whole latent pool (kv_cache.
                                  LatentPagePool.c); `row` picks the layer;
                                  W = C + R rounded up to the 128 lanes
                                  a DMA's slice is tiled by, the spare
                                  lanes zero in q and pool alike
  page_table [B, maxp] int32, lengths [B] int32 (incl. the new token,
  whose row is already written: write-then-attend)
  returns    [B, H, C]

The kernel walks the slots in grid order and, inside a slot, its pages
PAGES_PER_TURN at a time in a loop of `lengths`' trip count; they stream
HBM -> VMEM through two buffers, the next turn's pages (the next slot's
first, at a slot's end) in flight while this turn's are multiplied. About 121 flop a cached byte:
between the int8 kernel's memory-bound 2 and a prefill's compute-bound
hundreds.
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


def paged_attention_mla_reference(q, pool, row, page_table, lengths, *,
                                  latent: int, scale: float):
    """The same function in XLA: gather the slots' pages, one softmax."""
    B, H, W = q.shape
    ps = pool.shape[2]
    maxp = page_table.shape[1]
    c = pool[row][page_table].reshape(B, maxp * ps, W).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), c) * scale
    valid = jnp.arange(maxp * ps)[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, c[..., :latent]).astype(q.dtype)


# Pages a loop turn copies and multiplies together. A turn on ONE page
# (147 KB) is bound by the latency of its DMA, issued one turn ahead: 0.77
# us a page measured on a v5e at A.X-K1's widths, a fifth of the kernel's
# roofline (PERF.md, PR 33); four pages a turn are four descriptors in
# flight and a [64, 512] score tile for the MXU.
PAGES_PER_TURN = 4


def _mla_kernel(row_ref, lengths_ref, table_ref,     # scalar prefetch
                q_ref,                               # [1, H, W]
                pool_ref,                            # ANY [rows, P, ps, W]
                o_ref,                               # [1, H, C]
                buf, sem, seen, m_ref, l_ref, acc_ref, *,
                scale: float, latent: int, page_size: int, max_pages: int,
                ppt: int):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    ps = page_size

    def n_pages(slot):
        return jnp.clip(pl.cdiv(lengths_ref[slot], ps), 1, max_pages)

    def copies(slot, turn, into, act):
        """`act` on the copy of every page the slot HAS in this turn."""
        n = n_pages(slot)
        for p in range(ppt):
            page = turn * ppt + p

            @pl.when(page < n)
            def _():
                pid = table_ref[slot * max_pages + page]
                act(pltpu.make_async_copy(
                    pool_ref.at[row_ref[0], pid],
                    buf.at[into, pl.ds(p * ps, ps)], sem.at[into]))

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    @pl.when(b == 0)
    def _():
        seen[0] = 0
        # a turn multiplies its whole buffer and masks by position: what
        # a short turn leaves uncopied must be finite, so never garbage
        buf[...] = jnp.zeros_like(buf)
        copies(0, 0, 0, start)

    first = seen[0]  # turns taken before this slot: picks the buffer
    turns = pl.cdiv(n_pages(b), ppt)
    length = lengths_ref[b]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]

    def turn(j, _):
        cur = (first + j) % 2

        @pl.when(j + 1 < turns)
        def _():
            copies(b, j + 1, 1 - cur, start)

        @pl.when((j + 1 == turns) & (b + 1 < nb))
        def _():
            copies(b + 1, 0, 1 - cur, start)

        copies(b, j, cur, wait)
        rows = buf[cur]                                       # [ppt*ps, W]
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [H, ppt*ps]
        valid = (j * ppt * ps
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < length)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :latent],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        return 0

    jax.lax.fori_loop(0, turns, turn, 0)
    seen[0] = first + turns
    denom = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_mla(q, pool, row, page_table, lengths, *, latent: int,
                        scale: float, interpret: bool = False):
    """The Pallas kernel (see the module docstring)."""
    B, H, W = q.shape
    _, _, ps, _ = pool.shape
    maxp = page_table.shape[1]
    ppt = min(PAGES_PER_TURN, maxp)
    kernel = functools.partial(_mla_kernel, scale=scale, latent=latent,
                               page_size=ps, max_pages=maxp, ppt=ppt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, r, ln, t: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, latent), lambda b, r, ln, t: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppt * ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, latent), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_mla",
    )(jnp.asarray(row, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      page_table.reshape(-1).astype(jnp.int32), q.astype(pool.dtype), pool)


def paged_attention_mla_dispatch(q, pool, row, page_table, lengths, *,
                                 latent: int, scale: float,
                                 use_pallas: Optional[bool] = None):
    """The kernel on a TPU, the XLA form elsewhere (or where the page
    and the latent's widths are not what the kernel's tiles take)."""
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    ps, W = pool.shape[2], pool.shape[3]
    if use_pallas and (ps % 16 or latent % 128 or W % 128):
        log_kernel_declined(
            "paged_attention_mla", "the XLA gather form",
            f"page_size {ps} must be a multiple of 16, the latent width "
            f"{latent} and the row width {W} of 128")
        use_pallas = False
    if use_pallas:
        return paged_attention_mla(q, pool, row, page_table, lengths,
                                   latent=latent, scale=scale)
    return paged_attention_mla_reference(q, pool, row, page_table, lengths,
                                         latent=latent, scale=scale)
