"""Paged decode attention over the SELECTED tokens of the int8 KV pool.

Learned sparse attention (models/sparse_attn_moe.py): a decode step's
query attends to the `index_topk` cached tokens its indexer scored
highest, which `select_mask` hands over as one float32 row a slot,
[maxp, ps], 1 at a selected token and 0 elsewhere (and 0 at and past the
slot's length). The kernel is paged_attention_int8's walk (the same pool,
descriptors, cross-step buffering and live list; its docstring says why
each is as it is) with that row where the other masks by length: a slot's
pages are streamed WHOLE, 16 KB a head and page, and a token that was not
selected gets no weight.

The unit of the softmax is the BLOCK of pages: ONE online update a block
(`paged_attention_int8._fold_block`, first written here, PR 43; since PR
45 that kernel folds its blocks through it too), where the walk this was
copied from made one a page, each waiting for the one before (the
maximum, `alpha`, the weights, their sum, the weights' dot, the rescaled
accumulator). A row here has 40 to 150 pages, and the chain was a third
of a page's time. Alone on a v5e at 16 slots, a pool of the Keye
cell's shape, twelve calls a program (PERF.md section 5, PR 43;
scripts/check_sparse_on_chip.py --phases kernels --attn-widths 4,8,16
reads them again), us a call at contexts of 6k | 10k | 16k (us a page):

  an update a page, blocks of 4, 2 ahead (PR 42)   223.0 | 367.4 | 584.1  (0.290 | 0.287 | 0.285)
  an update a block, blocks of 4, 2 ahead (served) 143.2 | 234.6 | 371.7  (0.186 | 0.183 | 0.182)
  ... blocks of 8, 2 ahead                         143.2 | 234.7 | 371.6
  ... blocks of 8, 1 ahead                         148.9 | 243.5 | 385.7
  ... blocks of 16 (the rest in 4s), 2 ahead       143.5 | 235.2 | 372.5
  ... blocks of 16 (the rest in 4s), 1 ahead       143.9 | 235.1 | 372.4

A page is 135,168 B: 0.165 us at the HBM's 819 GB/s. With one update a
block the walk is 0.1785 us a page and 0.38 us a row, 92 % of that rate,
at every width: wider blocks buy nothing more, so the width stays the
smallest (the fewest bodies, the least VMEM).

The other form in reach, gathering the selected rows (2,048 x 4 heads x 2
reads of 128 B a slot and layer in this layout), reads a third to an
eighth of the bytes at the contexts this is served at and issues 16,384
descriptors a slot and layer where this issues two a page: XLA's gather of
the selected rows takes 3,497 us at each of those contexts.

Off the chip the same function is a gather of the slot's pages and a
dense masked softmax in XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    NEG_INF, SPLIT_KV_BYTES, LiveRows, _fold_block, every_row)
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

# This kernel's own walk, read on a v5e at the Keye cell's shape (the table
# above; paged_attention_int8 keeps its constants, read at its cells'
# shapes): the pages a block copies
# together and folds into the softmax in ONE update, by a body unrolled
# over exactly its pages, and the blocks whose copies are in flight while
# one is multiplied (VMEM holds one buffer more). With one update a block
# the walk runs at the HBM's rate at 4 pages already; 8 and 16 read the
# same, and one block ahead 4 % slower at 8.
BLOCK_PAGES = 4
BLOCKS_AHEAD = 2

_ASK_SLOT, _ASK_ROW, _ASK_BLOCK, _TAKE_SLOT = range(4)


def _walk(maxp: int, walk=None) -> tuple[int, int, int]:
    """(width, tail, ahead) for a table of maxp pages a row: the module's
    constants, or a probe's `walk` of the three; no block wider than a row
    can be. A row's pages go in blocks of `width` while it has that many
    left and the rest in blocks of up to `tail`, which is `width` but for
    a probe: a switch of more than nine bodies overflows the TPU
    compiler's stack, so a width of 16 is read with a tail of 4."""
    width, tail, ahead = walk or (BLOCK_PAGES, BLOCK_PAGES, BLOCKS_AHEAD)
    width = min(width, maxp)
    return width, min(tail, width), ahead


def walk_counts(lengths, page_size: int, max_pages: int, mask=None,
                walk=None) -> tuple[int, int]:
    """On the host, for a batch's `lengths` (numpy, any shape) and its
    `mask` of live rows (broadcast against them; None: every row): the
    pages the kernel copies and multiplies (each live row's n, an idle
    row's none) and the blocks it walks them in, which is the softmax
    updates and `fori_loop` turns it makes: the kernel's rule for a row
    (`_sparse_kernel`: n // width whole blocks, the rest in blocks of up
    to `tail`). The engine's `sparse_attn_pages_walked` /
    `sparse_attn_blocks_walked`."""
    width, tail, _ = _walk(max_pages, walk)
    n = np.clip(-(-np.asarray(lengths, np.int64) // page_size), 1, max_pages)
    blocks = n // width + -(-(n % width) // tail)
    if mask is not None:
        live = np.asarray(mask, bool)
        n, blocks = n * live, blocks * live
    return int(n.sum()), int(blocks.sum())


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
    kv_buf,        # VMEM [ahead + 1, width, 2, KH, ps, Hd] int8
    s_buf,         # VMEM [ahead + 1, width, 2, KH, 1, ps] f32
    sem,           # DMA sems [ahead + 1]
    state,         # SMEM [4]
    *,
    width: int,
    tail: int,
    maxp: int,
    page_size: int,
    ahead: int,
):
    """paged_attention_int8._int8_kernel's walk (descriptors, cross-step
    buffering, live list: its docstring says why each is as it is) for one
    query row a slot, with the BLOCK as the unit of the softmax update
    (`_fold_block`) and a page's scores masked by the slot's selection row
    instead of by its length (the selection is inside the length already).

    A row of n pages is n // width whole blocks and then its n % width
    last pages in blocks of up to `tail` (`_walk`); a block's page count
    picks the body that starts, waits for and multiplies exactly those: the
    whole one, or one of the 1 .. tail pages a last block can have."""
    k = pl.program_id(0)
    n_live = n_live_ref[0]
    b = order_ref[k]
    ps = page_size
    KH, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    layer = layer_ref[0]
    # the whole block's body first: a long row's usual case
    counts = [width] + list(range(1, min(tail, width - 1) + 1))

    def pages_of(row):
        return jnp.clip(lax.div(lengths_ref[row] + (ps - 1), ps), 1, maxp)

    def blocks_of(n_row):
        wide = lax.div(n_row, width)
        return wide + lax.div(n_row - wide * width + (tail - 1), tail)

    def by_page_count(n_row, i, branch, *operands):
        """`branch(first, count)(*operands)` for block i of a row of n_row
        pages: its first page and the (static) count of its pages."""
        wide = lax.div(n_row, width)
        first = jnp.where(i < wide, i * width,
                          wide * width + (i - wide) * tail)
        count = jnp.where(i < wide, width, jnp.minimum(tail, n_row - first))
        return lax.switch(jnp.where(count == width, 0, count),
                          [branch(first, c) for c in counts], *operands)

    def after(slot):
        return jnp.where(slot == ahead, 0, slot + 1)

    def copies(row, first, slot, count, act):
        for j in range(count):
            pid = tables_ref[row * maxp + first + j]
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
            by_page_count(n_row, i, lambda first, count: lambda: copies(
                row, first, slot, count, start))
            state[_ASK_SLOT] = after(slot)
            more = i + 1 < blocks_of(n_row)
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

        def block(first, count):
            def page(j):
                kv = (kv_buf[slot, j, 0].astype(jnp.float32),
                      kv_buf[slot, j, 1].astype(jnp.float32),
                      s_buf[slot, j, 0], s_buf[slot, j, 1])
                chosen = sel_ref[0, pl.ds(first + j, 1), :] > 0.5  # [1, ps]
                return *kv, lambda shape: jnp.broadcast_to(chosen[None], shape)

            def run(carry):
                copies(b, first, slot, count, wait)
                return _fold_block(q, page, count, carry)
            return run

        carry = by_page_count(n, i, block, carry)
        state[_TAKE_SLOT] = after(slot)
        return carry

    init = (jnp.full((KH, G, 1), NEG_INF, jnp.float32),
            jnp.zeros((KH, G, 1), jnp.float32),
            jnp.zeros((KH, G, Hd), jnp.float32))
    m, l, acc = lax.fori_loop(0, blocks_of(n), body, init)
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "walk", "interpret"))
def paged_attention_sparse_pallas(q, kv_pages, kv_scales, page_table,
                                  lengths, selected, layer,
                                  live: Optional[LiveRows] = None, *,
                                  scale: Optional[float] = None,
                                  walk: Optional[tuple] = None,
                                  interpret: bool = False):
    """`walk` (width, tail, ahead): a probe's or a test's walk in place of
    the module's constants (scripts/check_sparse_on_chip.py)."""
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
    width, tail, ahead = _walk(maxp, walk)
    s2 = kv_scales.reshape(2, L, KH, P, 1, ps)
    kernel = functools.partial(_sparse_kernel, width=width, tail=tail,
                               maxp=maxp, page_size=ps, ahead=ahead)

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
            pltpu.VMEM((ahead + 1, width, 2, KH, ps, Hd), jnp.int8),
            pltpu.VMEM((ahead + 1, width, 2, KH, 1, ps), kv_scales.dtype),
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
