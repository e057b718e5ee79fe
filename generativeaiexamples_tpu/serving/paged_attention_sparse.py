"""Paged decode attention over the SELECTED tokens of the int8 KV pool.

Learned sparse attention (models/sparse_attn_moe.py): a decode step's
query attends to the `index_topk` cached tokens its indexer scored
highest, which `select_mask` hands over as one float32 row a slot,
[maxp, ps], 1 at a selected token and 0 elsewhere (and 0 at and past the
slot's length). The kernel is paged_attention_int8's walk (the same pool,
descriptors, block bodies, cross-step buffering and live list; its
docstring says why each is as it is) with that row where the other masks
by length: a slot's pages are streamed WHOLE, 16 KB a head and page, and
a token that was not selected gets no weight.

The other form in reach, gathering the selected rows (2,048 x 4 heads x 2
reads of 128 B a slot and layer in this layout), reads a third to an
eighth of the bytes at the contexts this is served at and issues 16,384
descriptors a slot and layer where this issues two a page. Alone on a v5e
at 16 slots (PERF.md section 5, PR 42; scripts/check_sparse_on_chip.py
--phases kernels reads them again): the walk 227 / 372 / 590 us a call at
contexts of 6k / 10k / 16k, what paged_attention_int8 itself takes at this
shape (the mask costs nothing), and XLA's gather of the selected rows
3,502 us at each.

Off the chip the same function is a gather of the slot's pages and a
dense masked softmax in XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    BLOCKS_AHEAD, NEG_INF, PAGES_PER_BLOCK, SPLIT_KV_BYTES, LiveRows,
    every_row)
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

_ASK_SLOT, _ASK_ROW, _ASK_BLOCK, _TAKE_SLOT = range(4)


def paged_attention_sparse_reference(q, kv_pages, kv_scales, page_table,
                                     selected, layer, *, scale=None):
    """The XLA form: q [B, H, Hd], the whole fused pool [2, L, KH, P, ps,
    Hd] int8 and its scales [2, L, KH, P, ps], selected [B, maxp * ps]
    bool -> [B, H, Hd]; a slot that selects nothing gets zeros."""
    B, H, Hd = q.shape
    KH = kv_pages.shape[2]
    scale = Hd ** -0.5 if scale is None else scale
    codes = kv_pages[:, layer][:, :, page_table]       # [2, KH, B, maxp, ps, Hd]
    scales = kv_scales[:, layer][:, :, page_table]     # [2, KH, B, maxp, ps]
    kv = codes.astype(jnp.float32) * scales[..., None]
    kv = kv.reshape(2, KH, B, -1, Hd)
    qg = q.astype(jnp.float32).reshape(B, KH, H // KH, Hd) * scale
    s = jnp.einsum("bkgd,kbsd->bkgs", qg, kv[0])
    keep = selected[:, None, None, :]
    s = jnp.where(keep, s, NEG_INF)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,kbsd->bkgd", p, kv[1])
    return (o / jnp.where(denom == 0.0, 1.0, denom)).reshape(
        B, H, Hd).astype(q.dtype)


def _sparse_kernel(
    lengths_ref,   # scalar prefetch [B]
    tables_ref,    # scalar prefetch [B * maxp]
    layer_ref,     # scalar prefetch [1]
    order_ref,     # scalar prefetch [B]: LiveRows.order
    n_live_ref,    # scalar prefetch [1]: the rows to walk, the grid's size
    q_ref,         # [1, KH, G, Hd] f32 (scale pre-folded)
    sel_ref,       # [1, maxp, ps] f32: 1 at a selected token
    kv_hbm,        # [2, L, KH, P, ps, Hd] int8 (ANY)
    s_hbm,         # [2, L, KH, P, 1, ps] f32 (ANY)
    o_ref,         # [1, KH, G, Hd]
    kv_buf,        # VMEM [ahead + 1, ppcb, 2, KH, ps, Hd] int8
    s_buf,         # VMEM [ahead + 1, ppcb, 2, KH, 1, ps] f32
    sem,           # DMA sems [ahead + 1]
    state,         # SMEM [4]
    *,
    ppcb: int,
    maxp: int,
    page_size: int,
    ahead: int,
):
    """paged_attention_int8._int8_kernel for one query row a slot, a
    page's scores masked by the slot's selection row instead of by its
    length (the selection is inside the length already)."""
    k = pl.program_id(0)
    n_live = n_live_ref[0]
    b = order_ref[k]
    ps = page_size
    KH, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    layer = layer_ref[0]

    def pages_of(row):
        return jnp.clip(lax.div(lengths_ref[row] + (ps - 1), ps), 1, maxp)

    def by_live_count(n_row, i, branch, *operands):
        live = jnp.minimum(ppcb, n_row - i * ppcb)
        return lax.switch(live - 1,
                          [branch(c) for c in range(1, ppcb + 1)], *operands)

    def after(slot):
        return jnp.where(slot == ahead, 0, slot + 1)

    def copies(row, i, slot, count, act):
        for j in range(count):
            pid = tables_ref[row * maxp + i * ppcb + j]
            act(pltpu.make_async_copy(
                kv_hbm.at[:, layer, :, pid], kv_buf.at[slot, j],
                sem.at[slot]))
            act(pltpu.make_async_copy(
                s_hbm.at[:, layer, :, pid], s_buf.at[slot, j], sem.at[slot]))

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    def ask():
        at, i = state[_ASK_ROW], state[_ASK_BLOCK]

        @pl.when(at < n_live)
        def _():
            row = order_ref[at]
            n_row = pages_of(row)
            slot = state[_ASK_SLOT]
            by_live_count(n_row, i, lambda count: lambda: copies(
                row, i, slot, count, start))
            state[_ASK_SLOT] = after(slot)
            more = (i + 1) * ppcb < n_row
            state[_ASK_ROW] = jnp.where(more, at, at + 1)
            state[_ASK_BLOCK] = jnp.where(more, i + 1, 0)

    @pl.when(k == 0)
    def _first():
        for field in range(4):
            state[field] = 0
        for _ in range(ahead):
            ask()

    n = pages_of(b)
    q = q_ref[0].astype(jnp.float32)  # [KH, G, Hd]

    def body(i, carry):
        slot = state[_TAKE_SLOT]
        ask()

        def page(j, carry):
            m_prev, l_prev, acc = carry
            kq = kv_buf[slot, j, 0].astype(jnp.float32)  # [KH, ps, Hd]
            vq = kv_buf[slot, j, 1].astype(jnp.float32)
            ks = s_buf[slot, j, 0]                       # [KH, 1, ps]
            vs = s_buf[slot, j, 1]
            s = jax.lax.dot_general(
                q, kq, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * ks  # [KH, G, ps]
            keep = sel_ref[0, pl.ds(i * ppcb + j, 1), :] > 0.5   # [1, ps]
            keep = jnp.broadcast_to(keep[None], s.shape)
            s = jnp.where(keep, s, NEG_INF)
            m_curr = jnp.max(s, axis=2, keepdims=True)
            m_new = jnp.maximum(m_prev, m_curr)
            alpha = jnp.exp(m_prev - m_new)
            # (a page with nothing selected before anything was: every
            # score is NEG_INF and so is m_new, and exp(0) would count)
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                p * vs, vq, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [KH, G, Hd]
            return m_new, l_new, acc * alpha + pv

        def block(count):
            def run(carry):
                copies(b, i, slot, count, wait)
                for j in range(count):
                    carry = page(j, carry)
                return carry
            return run

        carry = by_live_count(n, i, block, carry)
        state[_TAKE_SLOT] = after(slot)
        return carry

    init = (jnp.full((KH, G, 1), NEG_INF, jnp.float32),
            jnp.zeros((KH, G, 1), jnp.float32),
            jnp.zeros((KH, G, Hd), jnp.float32))
    m, l, acc = lax.fori_loop(0, pl.cdiv(n, ppcb), body, init)
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_sparse_pallas(q, kv_pages, kv_scales, page_table,
                                  lengths, selected, layer,
                                  live: Optional[LiveRows] = None, *,
                                  scale: Optional[float] = None,
                                  interpret: bool = False):
    B, H, Hd = q.shape
    two, L, KH, P, ps, _ = kv_pages.shape
    assert two == 2, kv_pages.shape
    if L * KH * P * ps * Hd >= SPLIT_KV_BYTES:
        raise ValueError("paged_attention_sparse: a page's k and v move in "
                         "one descriptor, which a pool half of 4 GiB or "
                         "more cannot take")
    maxp = page_table.shape[1]
    G = H // KH
    s = scale if scale is not None else Hd ** -0.5
    qk = (q.astype(jnp.float32) * s).reshape(B, KH, G, Hd)
    ppcb = min(PAGES_PER_BLOCK, maxp)
    s2 = kv_scales.reshape(2, L, KH, P, 1, ps)
    ahead = BLOCKS_AHEAD
    kernel = functools.partial(_sparse_kernel, ppcb=ppcb, maxp=maxp,
                               page_size=ps, ahead=ahead)

    def qmap(k, Ln, T, LY, order, n_walk):
        return (order[k], 0, 0, 0)

    def selmap(k, Ln, T, LY, order, n_walk):
        return (order[k], 0, 0)

    rows = every_row(B) if live is None else live
    n_walk = jnp.maximum(rows.n_live, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_walk[0],),
        in_specs=[
            pl.BlockSpec((1, KH, G, Hd), qmap),
            pl.BlockSpec((1, maxp, ps), selmap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KH, G, Hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((ahead + 1, ppcb, 2, KH, ps, Hd), jnp.int8),
            pltpu.VMEM((ahead + 1, ppcb, 2, KH, 1, ps), kv_scales.dtype),
            pltpu.SemaphoreType.DMA((ahead + 1,)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_sparse",
    )(lengths, page_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), rows.order, n_walk,
      qk, selected.astype(jnp.float32).reshape(B, maxp, ps), kv_pages, s2)
    if live is not None:
        out = jnp.where(live.mask[:, None, None, None], out, 0.0)
    return out.reshape(B, H, Hd).astype(q.dtype)


def paged_attention_sparse(q, kv_pages, kv_scales, page_table, lengths,
                           selected, layer, *, scale=None,
                           use_pallas: Optional[bool] = None,
                           live: Optional[LiveRows] = None):
    """Slot b's query heads q [B, H, Hd] over the cached tokens
    `selected` [B, maxp * ps] (bool; nothing at or past `lengths[b]`) of
    cache row `layer` of the fused int8 pool -> [B, H, Hd]; zeros for a
    slot that is not live."""
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    ps, Hd = kv_pages.shape[-2:]
    if use_pallas and (ps % 128 or Hd % 128):
        log_kernel_declined(
            "paged_attention_sparse", "a gather of the pages and a dense "
            "masked softmax",
            f"page_size {ps} and head_dim {Hd} must both be multiples of 128")
        use_pallas = False
    if use_pallas:
        return paged_attention_sparse_pallas(
            q, kv_pages, kv_scales, page_table, lengths, selected, layer,
            live, scale=scale)
    out = paged_attention_sparse_reference(
        q, kv_pages, kv_scales, page_table, selected, layer, scale=scale)
    if live is not None:
        out = jnp.where(live.mask[:, None, None], out, 0.0)
    return out
