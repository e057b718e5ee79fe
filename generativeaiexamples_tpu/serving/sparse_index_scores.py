"""A decode step's index scores over a slot's cached index keys.

Learned sparse attention (models/sparse_attn_moe.py) scores EVERY cached
token of a slot before it attends to a few: for slot b's query heads
q [Hi, Di] with weights w [Hi] and the cached keys kI,
`I[b, s] = sum_j w[j] * relu(q[j] . kI[s])` for s < length, minus
infinity past it. The keys live in kv_cache.SparseIndexPool.idx
[R, P, Di, ps] bf16, a page transposed: `q @ page` is [Hi, Di] x [Di, ps].

Kernel shape: grid (n_live,), the dynamic bound of the int8 pool's two
kernels (paged_attention_int8.LiveRows): ONE grid step a LIVE slot, a
loop over blocks of pages, each page one contiguous descriptor of
Di x ps x 2 bytes (16 KB at 64 x 128). The walk is paged_attention_int8.
_int8_kernel's, by its measured rules (PR 56; that docstring says where
each was read first), and what a page is here decides the rest:

1. ONE chain of copies over the live slots: what was asked for and what
   was taken persist in SMEM from one grid step to the next, so while a
   slot's last blocks are multiplied the next live slot's first blocks
   are on their way, and no slot starts cold. The chain ends at n_live,
   as the grid does: no dead step to guard.
2. WHOLE blocks, with no test a page: a block's `_walk` pages are copied
   by that many descriptors and waited for by ONE wait (a semaphore
   counts bytes, and a block is always that many pages' worth). A table
   entry past a slot's last page is page 0, the sink, a page that exists
   (kv_cache.py: "padding -> page 0"), an entry past the table is the
   sink's too (the table is padded to whole blocks), and whatever was
   copied from there is masked by POSITION (a select, so a NaN in the
   sink stays out). A switch over the last block's live count, the int8
   kernel's form, reads four times SLOWER here (eight bodies of up to
   eight descriptors, twice).
3. `_walk` blocks are in flight while one is multiplied (VMEM holds one
   buffer more), and a block is asked for AFTER the one in its buffer was
   multiplied: the descriptors' scalar work runs behind the dot, not
   between the wait and it.

The scores of eight pages leave as one aligned [8, ps] store into the
slot's output block, which starts at minus infinity. An idle slot is
never walked: its output row is whatever the buffer held, and
`sparse_index_scores` selects it away.

Alone on a v5e (scripts/check_sparse_on_chip.py --phases kernels --kernels
index --parent ..., PERF.md section 5, PR 56): 16 live slots, a pool of
the Keye cell's shape, twelve calls a program, us a call at contexts of
6,144 | 10,240 | 16,384 (768 | 1,280 | 2,048 pages), the line through
them, and sixteen slots of unequal length (1,522 pages):

  PR 42's walk (a block of 8 asked for one ahead, a slot at a time,
  two tests a page)               48.0 |  73.0 | 110.6  0.049 us a page over 10.4   86.4
  ... and the chain (1) alone     44.3 |  69.8 | 108.4  0.050 over  5.9
  ... 4 blocks ahead alone        39.9 |  61.7 |  94.8  0.043 over  7.0
  ... whole blocks (2) alone      47.0 |  71.7 | 109.0  0.049 over  9.8
  ... asked after (3) alone       49.2 |  74.8 | 112.5  0.049 over 11.2
  all of it, blocks of 8, 2 ahead 31.5 |  48.6 |  74.5  0.034 over  5.7
  ... 4 ahead (8 and 16 the same) 29.2 |  44.8 |  68.7  0.031 over  5.5    54.4
  ... blocks of 16, 4 ahead       24.2 |  36.0 |  54.1  0.023 over  6.2    45.0  (served)
  ... blocks of 24, 3 ahead       24.0 |  38.4 |  55.2                     42.1
  ... blocks of 32, 2 ahead       27.4 |  38.6 |  49.7                     43.5
  the copies without the dot (8)  21.0 |  32.5 |  49.1  0.022 over  4.2    38.9
  the dot without copies (8)      18.3 |  27.0 |  40.1  0.017 over  5.3    32.1

No step pays alone and together they take two fifths off: the tests
stood in the way of the depth and the depth hid nothing while a slot
started cold. A page's bytes are 0.020 us at the HBM's 819 GB/s and the
copies alone run at 0.022; what a call takes beside them is about 0.05
us a BLOCK (the wait, the dot's way through the MXU and back, the
asking) and 0.35 us a slot, so a wider block is faster per page and reads
more pages a slot does not have (half a block a slot): 16 to 24 pages
is the least of both at 40 to 130 pages a slot. In the Keye cell's traced
stretch a call went from 67.0 to 35.4 us (twelve a step).

Off the chip the same function is a gather of the slot's pages and two
einsums in XLA.
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
    BLOCKS_AHEAD, BYTES_IN_FLIGHT, MAX_BLOCKS_AHEAD, LiveRows, every_row)
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

# Pages multiplied in one batched dot: eight rows of the output are one
# aligned float32 tile. A block is one or two of them (`_walk`).
TILE_PAGES = 8
# The most bytes a block of two tiles may have (`_walk`).
BLOCK_BYTES = 512 << 10

# The kernel's state in SMEM, carried from one grid step to the next (as
# paged_attention_int8._int8_kernel's): the buffer and the (place in
# `order`, block) to ask for next, the buffer to take.
_ASK_SLOT, _ASK_ROW, _ASK_BLOCK, _TAKE_SLOT = range(4)


def _walk(index_dim: int, page_size: int, itemsize: int) -> tuple[int, int]:
    """(pages a block, blocks in flight while one is multiplied) from a
    page's shape, index_dim x page_size keys of `itemsize` bytes: 16 pages
    a block while that is BLOCK_BYTES or less (Keye's 16 KB pages: 256
    KB), else 8; and paged_attention_int8.blocks_ahead's rule for the
    depth: as many blocks as put BYTES_IN_FLIGHT (3 MB) on their way, never
    fewer than 2 nor more than 4. At Keye's page that is (16, 4): 1 MB in
    flight, 1.25 MB of VMEM for the five buffers. Read on a v5e (the
    module's table): at 16 KB a page 512 KB in flight is 3-8 % slower than
    768 KB and nothing gains past that; blocks of 16 take a fifth off
    blocks of 8 and blocks of 24 and 32 read within 7 % of 16 either way
    by the slots' lengths. A page over 32 KB keeps blocks of 8 (not read:
    no cell has such a key; a block's fixed 0.05 us is then under a tenth
    of its bytes' time, and half a block of pages a slot does not have
    costs more). The kernel's own rule: no caller sets it."""
    page = index_dim * page_size * itemsize
    pages = 2 * TILE_PAGES if 2 * TILE_PAGES * page <= BLOCK_BYTES \
        else TILE_PAGES
    return pages, min(max(-(-BYTES_IN_FLIGHT // (pages * page)),
                          BLOCKS_AHEAD), MAX_BLOCKS_AHEAD)


def sparse_index_scores_reference(q, w, idx, row, page_table, lengths):
    """The XLA form. q [B, Hi, Di] bf16, w [B, Hi] float32, idx the whole
    pool [R, P, Di, ps], `row` the cache row -> [B, maxp * ps] float32."""
    B, maxp = page_table.shape
    ps = idx.shape[-1]
    pages = idx[row][page_table]                       # [B, maxp, Di, ps]
    dots = jnp.einsum("bhd,bpds->bphs", q.astype(idx.dtype), pages,
                      preferred_element_type=jnp.float32)
    scores = jnp.einsum("bphs,bh->bps", jax.nn.relu(dots), w) + 0.0
    scores = scores.reshape(B, maxp * ps)
    return jnp.where(jnp.arange(maxp * ps)[None, :] < lengths[:, None],
                     scores, -jnp.inf)


def _tile_scores(q, w, pages):
    """The arithmetic, eight pages at a time: q [n, Hi, Di] (one slot's,
    broadcast), w [Hi, 1], pages [n, Di, ps] -> [n, ps] float32. A page's
    scores depend on no other page, so the walk's order moves no bit."""
    dots = lax.dot_general(                          # [n, Hi, ps] float32
        q, pages, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(dots, 0.0) * w[None], axis=1) + 0.0


def _scores_kernel(
    lengths_ref,   # scalar prefetch [B]
    tables_ref,    # scalar prefetch [B * width]: whole blocks of entries
    row_ref,       # scalar prefetch [1]: which cache row
    order_ref,     # scalar prefetch [B]: LiveRows.order
    n_live_ref,    # scalar prefetch [1]: the slots to walk, the grid's size
    q_ref,         # [1, Hi, Di] bf16
    w_ref,         # [1, Hi, 1] float32
    idx_hbm,       # [R, P, Di, ps] bf16 (ANY)
    o_ref,         # [1, width, ps] float32
    buf,           # VMEM [ahead + 1, ppb, Di, ps] bf16
    sem,           # DMA sems [ahead + 1]
    state,         # SMEM [4]: _ASK_SLOT, _ASK_ROW, _ASK_BLOCK, _TAKE_SLOT
    *,
    maxp: int,
):
    k = pl.program_id(0)
    n_live = n_live_ref[0]
    b = order_ref[k]
    row = row_ref[0]
    ahead, ppb, ps = buf.shape[0] - 1, buf.shape[1], buf.shape[-1]
    width = o_ref.shape[1]

    def pages_of(slot):
        return jnp.clip(lax.div(lengths_ref[slot] + (ps - 1), ps), 1, maxp)

    def after(held):
        return jnp.where(held == ahead, 0, held + 1)

    def ask():
        """Start the copies of the next block not yet asked for, where a
        live slot is left: this slot's next if it has one, else the next
        live slot's first. The WHOLE block, with no test a page: an entry
        past the slot's last page is page 0, the sink."""
        at, i = state[_ASK_ROW], state[_ASK_BLOCK]

        @pl.when(at < n_live)
        def _():
            slot = order_ref[at]
            held = state[_ASK_SLOT]
            for j in range(ppb):
                pid = tables_ref[slot * width + i * ppb + j]
                pltpu.make_async_copy(idx_hbm.at[row, pid], buf.at[held, j],
                                      sem.at[held]).start()
            state[_ASK_SLOT] = after(held)
            more = (i + 1) * ppb < pages_of(slot)
            state[_ASK_ROW] = jnp.where(more, at, at + 1)
            state[_ASK_BLOCK] = jnp.where(more, i + 1, 0)

    @pl.when(k == 0)
    def _first():
        for field in range(4):  # the first block goes into buffer 0
            state[field] = 0

        @pl.loop(0, ahead + 1)
        def _(_):
            ask()

    length = lengths_ref[b]
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    q = jnp.broadcast_to(q_ref[0][None], (TILE_PAGES,) + q_ref.shape[1:])
    w = w_ref[0]                                            # [Hi, 1]

    def block(i, carry):
        held = state[_TAKE_SLOT]
        # ONE wait a block: the semaphore counts bytes, and a block's
        # copies are always ppb pages' worth
        pltpu.make_async_copy(idx_hbm.at[row, pl.ds(0, ppb)], buf.at[held],
                              sem.at[held]).wait()
        for tile in range(0, ppb, TILE_PAGES):
            first = i * ppb + tile
            s = _tile_scores(q, w, buf[held, pl.ds(tile, TILE_PAGES)])
            pos = (first + lax.broadcasted_iota(jnp.int32, s.shape, 0)) * ps \
                + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            o_ref[0, pl.ds(pl.multiple_of(first, TILE_PAGES), TILE_PAGES),
                  :] = jnp.where(pos < length, s, -jnp.inf)
        state[_TAKE_SLOT] = after(held)
        ask()  # into the buffer this block was taken from (rule 3)
        return carry

    lax.fori_loop(0, pl.cdiv(pages_of(b), ppb), block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_index_scores_pallas(q, w, idx, row, page_table, lengths,
                               live: Optional[LiveRows] = None, *,
                               interpret: bool = False):
    B, Hi, Di = q.shape
    maxp = page_table.shape[1]
    ps = idx.shape[-1]
    ppb, ahead = _walk(Di, ps, idx.dtype.itemsize)
    width = -(-maxp // ppb) * ppb         # whole blocks of rows and entries
    rows = every_row(B) if live is None else live
    n_walk = jnp.maximum(rows.n_live, 1)
    # a block's entries past the table: the sink's page
    page_table = jnp.pad(page_table, ((0, 0), (0, width - maxp)))

    def at_slot(k, Ln, T, R, order, n):
        return (order[k], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_walk[0],),
        in_specs=[pl.BlockSpec((1, Hi, Di), at_slot),
                  pl.BlockSpec((1, Hi, 1), at_slot),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, width, ps), at_slot),
        scratch_shapes=[pltpu.VMEM((ahead + 1, ppb, Di, ps), idx.dtype),
                        pltpu.SemaphoreType.DMA((ahead + 1,)),
                        pltpu.SMEM((4,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_scores_kernel, maxp=maxp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, width, ps), jnp.float32),
        # Sequential grid: what was asked for and taken threads through
        # SMEM from one grid step to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sparse_index_scores",
    )(jnp.maximum(lengths.astype(jnp.int32), 1),
      page_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(row, jnp.int32).reshape(1), rows.order, n_walk,
      q.astype(idx.dtype), w.astype(jnp.float32)[:, :, None], idx)
    out = out[:, :maxp].reshape(B, maxp * ps)
    if live is not None:  # a slot the grid never served: nothing scored
        out = jnp.where(live.mask[:, None], out, -jnp.inf)
    return out


def sparse_index_scores(q, w, idx, row, page_table, lengths, *,
                        use_pallas: Optional[bool] = None,
                        live: Optional[LiveRows] = None):
    """Slot b's scores of its cached tokens, [B, maxp * ps] float32, minus
    infinity at and past `lengths[b]` and everywhere for a slot that is
    not live. q [B, Hi, Di], w [B, Hi], `idx` the WHOLE index pool
    [R, P, Di, ps] and `row` its cache row (a slice handed to a kernel
    would be copied out first), page_table [B, maxp], lengths [B]
    INCLUDING the current token, whose key is already in the pool."""
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    Di, ps = idx.shape[-2:]
    if use_pallas and (ps % 128 or Di % 16):
        log_kernel_declined(
            "sparse_index_scores", "a gather of the pages and two einsums",
            f"page_size {ps} must be a multiple of 128 and the index key's "
            f"width {Di} of 16")
        use_pallas = False
    if use_pallas:
        return sparse_index_scores_pallas(q, w, idx, row, page_table,
                                          lengths, live)
    out = sparse_index_scores_reference(q, w, idx, row, page_table, lengths)
    if live is not None:
        out = jnp.where(live.mask[:, None], out, -jnp.inf)
    return out
