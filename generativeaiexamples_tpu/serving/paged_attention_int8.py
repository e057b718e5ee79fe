"""Paged decode attention over a FUSED int8 KV pool with narrow scales.

Why this kernel exists (VERDICT r2 next-step #1b): bf16 KV caps the
engine at B=64 on a 16 GB v5e (B=128 OOMs; docs/ENGINEERING_NOTES.md),
and decode throughput is HBM-bandwidth-bound — weights are read once
per step regardless of batch, so doubling the batch nearly doubles
tokens/sec *if the KV pool fits and stays cheap to read*. int8 KV
halves pool bytes. The stdlib JetStream-style kernel's quantized path
is useless for this: it broadcasts f32 scales to head_dim width
(5 B/token-elem effective vs bf16's 2) AND materializes the broadcast
in HBM. Here scales are one f32 per (kv-head, k|v, token): 4 bytes
next to the 128-byte int8 token row — 3% overhead instead of 200%.

Layouts (per layer, matching kv_cache.QuantPagePool):
  q          [B, H, Hd]          softmax scale PRE-FOLDED by the caller
  kv_pages   [2, KH, P, ps, Hd]  int8; [0] = k, [1] = v
  kv_scales  [2, KH, P, ps]      bf16/f32 (amax/127 over Hd at write)
  page_table [B, maxp] int32     page ids (0 = garbage sink)
  lengths    [B] int32           valid tokens INCLUDING the current one
  live       LiveRows or None    the step's `active` mask as the kernels
                                 prefetch it (live_rows); None: every row
  starts     [B] int32 or None   a WINDOW row's first visible token (as
                                 `lengths`, counted from the table's
                                 first page); None: every token
  new        (codes [2, KH, B, Hd] int8, scales [2, KH, B]) or None
                                 a decode step's ONE new row a slot, not
                                 in the pool yet: the call writes it

Who writes a step's new row (PR 46). A LOOPED model's decode step hands
it to this call as `new` (engine_model.fuses_append decides, from the
pool and the step program's static arguments; kv_cache.QuantPagePool.
attend_appending makes the call): the kernel has the row's last page in
VMEM anyway, patches the row in there and writes the 32-row tile back, in
place (rule 6 of `_int8_kernel`), where serving/kv_append_int8.py read
that tile again in a launch of its own, once a cache row. Every other
caller (a one-pass model's step, whose XLA schedule read longer without
that launch, a verify's r rows a slot, a window row, the drawn blocks'
call sites, serving/paged_attention_sparse.py) appends first and attends
a pool that holds the row: `new` None, and the kernel is what it was.

Kernel shape: grid (n_live,), a dynamic bound — ONE grid step per LIVE
batch row (grid step k serves row order[k]) covering ALL kv heads, as a
fori_loop over blocks of PAGES_PER_BLOCK pages. Each page's
k AND v move HBM->VMEM as a SINGLE DMA descriptor strided across the
(KH, 2) axes, and both scale rows as one more — 2 descriptors per page
instead of the 4 an unfused pool needs and the 8 a per-head grid pays.
The copies of the next `blocks_ahead` blocks (about 3 MB: 2 blocks of 541
KB pages, 3 of 270 KB ones, 4 of smaller ones), this row's and then the
next LIVE rows', are in flight while the current one computes
(cross-grid-step buffering), each asked for once the block in its buffer
was multiplied. A block's live pages are folded into the online softmax in
ONE update (`_fold_block`, which serving/paged_attention_sparse.py runs
too): all their score tiles first, one maximum, one `alpha`, one sum, one
rescaled accumulator, where a page at a time made a chain of those a
page, each waiting for the one before. Alone on a v5e, us a call: an
update a page -> an update a block (PERF.md section 5, PR 45) -> a block
asked for after the one in its buffer, `blocks_ahead` of them in flight
(PR 53); scripts/measure_paged_attention.py and
scripts/check_window_on_chip.py --phases kernels, each with --parent,
read them again:

  pool (score tile KH x G; page)  closed mix            every row full            3 long rows
  Mistral-7B   (8 x 4; 270 KB)    60.5 -> 55.7 -> 52.3  459.5 -> 459.2 -> 459.2  17.3 -> 16.9 -> 16.9
  Ouro         (16 x 1; 541 KB)   53.4 -> 53.2 -> 53.2   94.0 ->  93.8 ->  93.8  11.1 -> 11.0 -> 11.0
  a chip of TP4 (2 x 4; 68 KB)    46.9 -> 35.4 -> 31.2   75.0 ->  45.2 ->  45.0   4.1 ->  2.7 ->  2.7
  SmallThinker (4 x 7; 135 KB), 64 rows at 5k | 9k | 16k:
    window rows, tables of 34   578.4 | 578.0 | 578.9 -> 406.4 | 406.8 | 408.0 -> 386.2 | 387.1 | 388.1
    global rows, tables of 128  735.8 | 1315.4 | 2327.7 -> 506.3 | 897.2 | 1581.4 -> 505.1 | 892.7 | 1575.9

The smaller the page, the less its copy hid the chain: 0.274-0.291 us a
page became 0.181-0.191 us a page and 0.375 us a row at SmallThinker's
shape (84-86 % of the HBM's rate by a page's bytes), a quarter to two
fifths of a call went at TP4's, a twelfth of Mistral-7B's closed mix, and
nothing where rows of whole blocks of 270 KB pages were the bytes'
already. Folding 2 or 3 pages an update reads between the two at every
shape, so `fold_pages` is the block's width for every tile. What a
closed-mix call still takes beside its bytes at 2 KV heads (31.2 us for
11.7 us of pages) is the rows' own chains of dot, maximum, exp and dot
and the scalar work of their descriptors, not their grid steps
(`_int8_kernel`, rule 3; PERF.md section 6, PR 53, says what was tried
against it).

A live row has n = clip(cdiv(length + q_rep - 1, ps), 1, maxp) pages, and
an idle one (a decode slot nobody occupies: `active` False) none: it is
never asked for, waited for or multiplied, and its output is zeros. A
page past a row's last is neither copied nor multiplied: a block's count of
live pages picks the body that starts, waits for and multiplies exactly
those (`_int8_kernel`), so no table entry past n is read and a block
need not divide the table's width. The kernel streams the bytes a batch
HAS: on a v5e rows of whole blocks run at 93 % of the HBM's rate, and a
short row costs its pages and about 0.3 us, where walking whole blocks
read page 0 for every page the row lacked, and an idle row took a page
and 0.55 us until the mask reached the kernel
(scripts/measure_paged_attention.py; PERF.md section 5, PR 34, PR 41).

Dequantization never touches head_dim: K scales multiply the score
columns ((q @ k_q^T) * ks == q @ (k_q * ks)^T), V scales fold into the
softmax weights before the PV matmul — the VPU work per block is
O(KH x G x bk), not O(bk x Hd).

No reference-repo counterpart: the reference delegates KV management to
TRT-LLM inside NIM (SURVEY.md §2.3).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def quantize_kv(x: jax.Array, scale_dtype=jnp.float32):
    """Symmetric int8 over the last axis (head_dim): one scale per
    (…, token) row. Returns (q int8, s scale_dtype[...-1])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.round(xf / s).clip(-127, 127).astype(jnp.int8)
    return q, jnp.squeeze(s, -1).astype(scale_dtype)


def dequantize_pages(q_pages: jax.Array, scales: jax.Array,
                     dtype=jnp.float32) -> jax.Array:
    """[..., ps, Hd] int8 + [..., ps] -> float pages (CPU oracle)."""
    return q_pages.astype(dtype) * scales.astype(dtype)[..., None]


def paged_attention_int8_reference(q, k_pages, k_scales, v_pages, v_scales,
                                   page_table, lengths, *, scale=None,
                                   starts=None):
    """Dequantize-then-attend oracle over UNFUSED pages (any backend;
    numerics tests build k/v separately). `starts` [B]: the first token
    a row sees (a window row's); None: every token."""
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_reference)

    k = dequantize_pages(k_pages, k_scales)
    v = dequantize_pages(v_pages, v_scales)
    return paged_attention_reference(q, k, v, page_table, lengths,
                                     scale=scale,
                                     starts=starts).astype(q.dtype)


def paged_attention_int8_reference_fused(q, kv_pages, kv_scales, page_table,
                                         lengths, *, scale=None, starts=None):
    """Oracle over the fused [2, KH, P, ps, Hd] layout."""
    return paged_attention_int8_reference(
        q, kv_pages[0], kv_scales[0], kv_pages[1], kv_scales[1],
        page_table, lengths, scale=scale, starts=starts)


def fuse_kv(kq, ks, vq, vs):
    """Separate quantized k/v ([KH, P, ps, Hd] + [KH, P, ps]) -> the
    fused pool layout (tests + oracle comparisons)."""
    return jnp.stack([kq, vq], axis=0), jnp.stack([ks, vs], axis=0)


# ---------------------------------------------------------------------------
# TPU kernel
# ---------------------------------------------------------------------------


def _tree_keep(pos, length, jrow, r, tree):
    """Tree-verify keep mask over _tree_layout's packed lattice,
    computed ARITHMETICALLY from iota values (Pallas kernels cannot
    capture vector constants, and the lattice is regular enough that
    no table is needed): node 0 is the root, node 1 + m*k + (d-1) is
    branch m's depth-d draft, so t is an ancestor-or-self of j iff
    t == 0, or both sit on the same branch with depth(t) <= depth(j).

    pos: absolute kv slot ids [KH, G, bk-block]; length: row's length
    incl. the root; jrow: query node index per G row (iota // g_base);
    r = 1 + M*k nodes; tree = (k, n_branches) static."""
    k, _branches = tree
    rel = pos - (length - 1)            # kv slot offset into the tree
    in_tree = (rel >= 0) & (rel < r)
    # Clamped to keep the div/mod on non-negative values; the guards
    # (jrow > 0, rel >= 1) exclude every clamped case from mattering.
    jn = jnp.maximum(jrow - 1, 0)
    tn = jnp.maximum(rel - 1, 0)
    same_chain = ((jrow > 0) & (rel >= 1)
                  & (jn // k == tn // k) & (tn % k <= jn % k))
    return (rel < 0) | (in_tree & ((rel == 0) | same_chain))


# From this many bytes in ONE half (k or v) of the fused code pool, the
# kernel copies a page's k and v with a descriptor each (`_int8_kernel`'s
# `copies`).
SPLIT_KV_BYTES = 2 ** 32

# Pages a block copies together and multiplies in one unrolled body, and
# the fewest blocks whose copies are in flight while one is multiplied
# (`blocks_ahead` gives a shape's; VMEM holds one buffer more). Read on a
# v5e at the cells' shapes (PERF.md section 5, PR 34): 4 and 5 pages read
# alike and 8 a tenth slower on rows of 20 pages (a block waits for all
# its copies), and 4 makes the fewest bodies; a second block in flight
# takes a fifth off Mistral-7B's mix and an eighth off Ouro's.
PAGES_PER_BLOCK = 4
BLOCKS_AHEAD = 2
# The bytes `blocks_ahead` keeps on their way, and the most blocks it
# gives (PERF.md section 5, PR 53).
BYTES_IN_FLIGHT = 3 << 20
MAX_BLOCKS_AHEAD = 4
# Pages folded into one online-softmax update (`fold_pages`): the block's.
FOLD_PAGES = 4
# Rows of an int8 tile: what one DMA descriptor can address in the pool,
# so what a step's new row costs to write (`NewRow`, kv_append_int8).
TILE_ROWS = 32
# Tiles a call's write-backs leave from, in turn: a row's write is waited
# for when the row this many after it wants the tile.
WRITES_AHEAD = 2

# The kernel's state in SMEM, carried from one grid step to the next:
# the buffer and the (place in `order`, block) to ask for next, the
# buffer to take.
_ASK_SLOT, _ASK_ROW, _ASK_BLOCK, _TAKE_SLOT = range(4)


class LiveRows(NamedTuple):
    """A decode step's `active` mask as the two int8 pool kernels (this
    one and serving/kv_append_int8.py) prefetch it: they walk
    `order[0 .. n_live)` and never touch another row."""

    mask: jax.Array    # [B] bool
    order: jax.Array   # [B] int32: the live rows' indices first, stable
    n_live: jax.Array  # [1] int32


def live_rows(mask: jax.Array) -> LiveRows:
    """Taken once a step, outside the layer walk: every layer's calls
    walk the same rows."""
    mask = mask.astype(bool)
    order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
    return LiveRows(mask, order, jnp.sum(mask, dtype=jnp.int32).reshape(1))


def every_row(n_rows: int) -> LiveRows:
    """What a caller without a mask gets: the parent's walk, row by row."""
    return LiveRows(jnp.ones((n_rows,), bool),
                    jnp.arange(n_rows, dtype=jnp.int32),
                    jnp.full((1,), n_rows, jnp.int32))


def fold_pages(kv_heads: int, group: int, ppcb: int) -> int:
    """The pages `_int8_kernel` folds into ONE online-softmax update, from
    a page's score tile [kv_heads, group, ps] (group: query heads a KV head
    x q_rep): a block makes cdiv(count, width) updates over its `count`
    live pages, and a width of 1 is a chain a page through the same
    function. A wider fold holds more score tiles in registers at once
    (about kv_heads x cdiv(group, 8) vregs a page), and the tile that could
    have lost by that, Ouro's 16 x 1, did not: at every tile a cell has, 2
    x 4 to 16 x 1, the whole block read fastest or within 0.1 % of it at
    every set of lengths with a live row (the module's table), so the rule
    is a constant and the tile decides nothing yet. A tile that reads otherwise gets its
    width here, and nowhere else: no caller sets it."""
    return min(FOLD_PAGES, ppcb)


def blocks_ahead(kv_heads: int, page_size: int, head_dim: int, ppcb: int,
                 scale_bytes: int = 4) -> int:
    """The blocks whose copies `_int8_kernel` keeps in flight while one is
    multiplied, from the bytes of a block (`ppcb` pages of 2 x kv_heads x
    page_size x head_dim codes and their scale rows): as many as put
    BYTES_IN_FLIGHT on their way, never fewer than BLOCKS_AHEAD nor more
    than MAX_BLOCKS_AHEAD. By a page's bytes at four pages a block: 68 KB
    (2 KV heads, a chip of TP4) and 135 KB (4, SmallThinker) 4 blocks, 270
    KB (8, Mistral-7B, granite, Trinity) 3, 541 KB (16, Ouro) 2; the
    buffers, one more, are 1.4, 2.7, 4.3 and 6.5 MB of VMEM. Read on a v5e
    (PERF.md section 5, PR 53) once a block was asked for AFTER the one in
    its buffer was multiplied (`_int8_kernel`, rule 3): a third block takes
    2.4 us off Mistral-7B's closed mix of 54.7 and a fourth nothing more;
    four for two take 19 us off SmallThinker's window rows' 405; Ouro's
    calls read the same at 2, 3 and 4 (a block is 2.2 MB: two are 5.3 us
    of the HBM's time), and so do a chip of TP4's, whose rows of 150 KB
    wait for their arithmetic, not for their copies. Like `fold_pages`,
    the kernel's own rule: no caller sets it."""
    block = ppcb * 2 * kv_heads * page_size * (head_dim + scale_bytes)
    return min(max(-(-BYTES_IN_FLIGHT // block), BLOCKS_AHEAD),
               MAX_BLOCKS_AHEAD)


def _fold_block(q, page, count: int, carry):
    """ONE online-softmax update over the `count` (static) pages of a
    block. `page(j)` -> (kq, vq [KH, ps, Hd] f32, ks, vs [KH, 1, ps], keep),
    `keep(shape)` the page's mask at its score tile's shape [KH, G, ps]
    (bool; asked for once the scores are there); q [KH, G, Hd]; carry (m, l
    [KH, G, 1], acc [KH, G, Hd]). The pages' score tiles depend neither on
    the carry nor on each other: one maximum over all of them, one `alpha`,
    one sum, one rescaled accumulator, where a page at a time made `count`
    such chains, each waiting for the one before."""
    m_prev, l_prev, acc = carry
    scores, keeps, values = [], [], []
    for j in range(count):
        kq, vq, ks, vs, keep = page(j)
        s = jax.lax.dot_general(
            q, kq, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * ks      # [KH, G, ps]
        keep = keep(s.shape)
        scores.append(jnp.where(keep, s, NEG_INF))
        keeps.append(keep)
        values.append((vq, vs))
    top = functools.reduce(jnp.maximum, scores)
    m_new = jnp.maximum(m_prev, jnp.max(top, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # (a block with nothing kept before anything was, as a window row's
    # whose first page slid out whole: every score is NEG_INF and so is
    # m_new, and exp(0) would count)
    weights = [jnp.where(keep, jnp.exp(s - m_new), 0.0)
               for s, keep in zip(scores, keeps)]
    l_new = alpha * l_prev + jnp.sum(
        functools.reduce(jnp.add, weights), axis=2, keepdims=True)
    pv = functools.reduce(jnp.add, [
        jax.lax.dot_general(
            p * vs, vq, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [KH, G, Hd]
        for p, (vq, vs) in zip(weights, values)])
    return m_new, l_new, acc * alpha + pv


class NewRow(NamedTuple):
    """What `_int8_kernel` holds of the step's new row where it writes the
    row itself (`_int8_append_kernel`)."""

    scales: object   # scalar prefetch [B * 2 * KH] f32: new scales, by slot
    codes: object    # VMEM [1, 2, KH, Hd] int32: row order[k]'s new codes
    kv_tile: object  # VMEM [WRITES_AHEAD, 2, KH, 32, Hd] int8: a patched tile
    s_rows: object   # VMEM [WRITES_AHEAD, 2, KH, 1, ps] f32: its scale rows
    sem: object      # DMA sems [WRITES_AHEAD]: the write-backs'


def _int8_append_kernel(lengths_ref, tables_ref, layer_ref, order_ref,
                        n_live_ref, scales_ref, q_ref, codes_ref, kv_in,
                        s_in, o_ref, kv_hbm, s_hbm, kv_buf, s_buf, sem, state,
                        kv_tile, s_rows, wsem, **static):
    """_int8_kernel for a decode step that has not written its new row yet:
    the pool ALIASED input to output, the row's codes and scales as
    operands, and the write done here (`_int8_kernel`, rule 6).
    `n_live_ref` [2]: the rows to walk, and LiveRows.n_live as it was
    before the clamp that makes a walk of no rows one of one."""
    del kv_in, s_in  # the same buffers as the outputs
    _int8_kernel(lengths_ref, tables_ref, layer_ref, order_ref, n_live_ref,
                 q_ref, kv_hbm, s_hbm, o_ref, kv_buf, s_buf, sem, state,
                 new=NewRow(scales_ref, codes_ref, kv_tile, s_rows, wsem),
                 **static)


def _int8_window_kernel(lengths_ref, tables_ref, layer_ref, order_ref,
                        n_live_ref, starts_ref, *refs, **static):
    """_int8_kernel for a WINDOW row: one scalar prefetch more, a row's
    first visible token (`starts` [B])."""
    _int8_kernel(lengths_ref, tables_ref, layer_ref, order_ref, n_live_ref,
                 *refs, starts_ref=starts_ref, **static)


def _int8_kernel(
    lengths_ref,   # scalar prefetch [B]
    tables_ref,    # scalar prefetch [B * maxp]
    layer_ref,     # scalar prefetch [1] — which layer's pool slice
    order_ref,     # scalar prefetch [B] — LiveRows.order
    n_live_ref,    # scalar prefetch [1] — the rows to walk: the grid's size
    q_ref,         # [1, KH, G, Hd] f32 (scale pre-folded)
    kv_hbm,        # [2, L, KH, P, ps, Hd] int8 (ANY)
    s_hbm,         # [2, L, KH, P, 1, ps] f32 (ANY)
    o_ref,         # [1, KH, G, Hd]
    kv_buf,        # VMEM [ahead + 1, ppcb, 2, KH, ps, Hd] int8
    s_buf,         # VMEM [ahead + 1, ppcb, 2, KH, 1, ps] f32
    sem,           # DMA sems [ahead + 1]
    state,         # SMEM [4]: _ASK_SLOT, _ASK_ROW, _ASK_BLOCK, _TAKE_SLOT
    *,
    ppcb: int,
    fold: int,
    maxp: int,
    page_size: int,
    ahead: int,
    q_rep: int = 1,
    tree=None,
    split_kv: bool = False,
    starts_ref=None,
    new: Optional[NewRow] = None,
):
    """One grid step per LIVE BATCH ROW, all kv heads + k and v together:
    step k serves row order[k] through the q and o index maps, and the
    grid ends at n_live.

    q_rep > 1 (speculative verify): the G axis carries q_rep query
    positions per head group, j-major (row = j * G_base + g); query
    sub-row j sits at sequence position length-1+j and masks
    pos < length + j. The KV stream is read ONCE for all positions —
    the whole point vs folding positions into the batch.

    tree = (k, n_branches) (tree verify; requires q_rep == 1 + M*k):
    the q_rep packed positions are engine_model._tree_layout's lattice
    — node 0 the root at pool slot length-1, node 1 + m*k + (d-1)
    branch m's depth-d draft at slot length-1+node. Query row j then
    attends the committed prefix (pos < length-1) plus its ancestor-
    or-self chain, which for this lattice is ARITHMETIC in the node
    indices (same branch, depth <=) — the whole mask is a handful of
    iota compares per flash block, no captured tables, no gathers
    (Pallas kernels cannot capture vector constants). The KV stream
    is identical to linear verify: the tree only edits the mask.

    Design rules, measured on a v5e (scripts/measure_paged_attention.py):
    1. Only the pages a row has: a page past the row's last is not
       copied, not waited for and not multiplied (what the buffer holds
       there is whatever an earlier row left: a stale SCALE times a zero
       weight would be NaN, so skipping the copy alone is not enough).
       A block's count of live pages picks one of ppcb bodies, each
       unrolled over exactly its pages, for the copies' starts, for
       their waits and for the multiplies alike: a loop of that trip
       count runs a page's chain of dot, max, exp and dot one after the
       other, and rows of whole blocks then take a sixth longer. A body
       folds its pages into the softmax `fold` at a time (`_fold_block`;
       `fold_pages`: the whole block), under each page's own mask: a
       page none of whose tokens a row sees (a window row's first, slid
       out whole; a tree's future) weighs nothing, not exp(0).
    2. Fused pages: 2 descriptors per page.
    3. Latency hiding is CROSS-grid-step (the JetStream scheme): while
       a block is multiplied, the copies of the `ahead` blocks after it
       (this row's, then the next live rows') are in flight in the other
       buffers; what was asked for and taken persists in SMEM across
       grid steps. A block is asked for AFTER the one in its buffer was
       multiplied, not at the head of the block before (PR 53): the
       descriptors' scalar work (a table entry and two descriptors a
       page, a switch a block) then runs while the row's last results
       drain, where at the head it stood between the row's wait
       and its first dot: 4.2 of the 35.4 us of a closed-mix call at 2
       KV heads (64 rows, 141 pages, 11.7 us of bytes). The rest of what
       a live row costs beside its pages there is its chain of dot,
       maximum, exp and dot and this scalar work, NOT the grid step: a
       walk that is a loop of the kernel's own, q and o whole in VMEM,
       read 33.5 us where the grid read 35.4 (PERF.md section 6, PR 53).
       `ahead` is `blocks_ahead` of a block's bytes.
    4. Only the live rows, at no scalar test more for one of them (25 ns
       each, PR 34): the chain of copies ends at n_live where it ended
       at B, and so does the grid, so there is no dead step to guard: no
       `pl.when` around the body, no index map that has to hold the last
       live row's blocks. With all of 64 rows idle a call is 1.84 us
       where it was 35.33 (PERF.md section 5, PR 41).
    5. A WINDOW row (`starts_ref`; q_rep 1, no tree): its table holds only
       the pages that reach into the window, so the walk is the table's
       from its first page, and one compare more masks the tokens of that
       page that slid out already: exact to the token. No other row
       compiles the compare or the prefetch.
    6. The step's NEW row (`new`; q_rep 1, no tree, no window; kv_hbm and
       s_hbm are then the pool aliased input to output): the row's token
       length - 1 lives in its LAST page, which the last block's copies
       have just brought into VMEM, so that buffer is patched where it
       lies (the codes into sublane offset % 32 of the tile, the scales
       into lane offset: kv_append_int8's compare and select) before the
       fold reads it, and the patched 32-row tile and the scale rows go
       back to HBM from a tile of their own (`NewRow.kv_tile`), in one
       write (two code descriptors under `split_kv`). Nothing waits for
       that write but the row WRITES_AHEAD after this one, which wants
       the tile, and the last row, which waits for every one still out.
       A block short of `ppcb` pages is its row's last and writes
       without a test; a whole block tests whether it is. With nobody
       live the one row the grid serves gets its tile back as it came.
       Read on a v5e (PERF.md section 5, PR 46): the write's own DMA
       time stays, 0.1 us a row at 2 KV heads to 0.4 at 16 (2 x KH tiles
       of 4 KB and as many scale rows of 512 B a row), so the call grows
       by four fifths of what the append's kernel took and the pair
       saves that kernel's read and its launch, 3.5-5 us of 70 at the
       closed mixes; patching words of four rows in place of the int32
       round trip, a third write tile, and the codes held whole in VMEM
       each read SLOWER."""
    k = pl.program_id(0)
    n_live = n_live_ref[0]
    b = order_ref[k]
    ps = page_size
    KH, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    g_base = G // q_rep
    layer = layer_ref[0]

    def pages_of(row):
        """The row's pages: those the LAST query row's span reaches."""
        span = lengths_ref[row] + (q_rep - 1)
        return jnp.clip(lax.div(span + (ps - 1), ps), 1, maxp)

    def by_live_count(n_row, i, branch, *operands):
        """`branch(count)(*operands)` for the count of pages a row of
        n_row has in its block i. The switch lowers to a chain of tests,
        a single page first: the idle slots' case, and a short row is
        where a test's 25 ns shows (a whole block hides it behind its
        copies)."""
        live = jnp.minimum(ppcb, n_row - i * ppcb)
        return lax.switch(live - 1,
                          [branch(c) for c in range(1, ppcb + 1)], *operands)

    def after(slot):
        return jnp.where(slot == ahead, 0, slot + 1)

    def copies(row, i, slot, count, act):
        """`act` (start or wait) on the copies of the first `count` pages
        (static) of row's block i, into buffer `slot`. Per page ONE
        STRIDED descriptor covering all kv heads AND both of k/v
        (hbm.at[:, layer, :, pid] on the FULL [2, L, KH, P, ...] pool —
        the layer is indexed inside the descriptor because a host-side
        per-layer slice of the kv-leading layout is non-contiguous and
        XLA would materialize 32 copies of it) and one more for its scale
        rows. The semaphores count bytes, so an identical descriptor
        built later waits for the one that was started: starts and waits
        must run for the same `count`.

        `split_kv`: one descriptor for k and one for v. The single one
        strides from a page's k to its v over L*KH*P*ps*Hd bytes, and a
        pool whose half is 4 GiB or more (a looped model's 192 rows)
        reads wrong pages through it (SPLIT_KV_BYTES)."""
        for j in range(count):
            pid = tables_ref[row * maxp + i * ppcb + j]
            if split_kv:
                for h in (0, 1):
                    act(pltpu.make_async_copy(
                        kv_hbm.at[h, layer, :, pid], kv_buf.at[slot, j, h],
                        sem.at[slot]))
            else:
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
        """Start the copies of the next block not yet asked for, where
        a live row is left: this row's next if it has one, else the next
        live row's first (a live row's length is >= 1, so it has a
        block; an idle row is not in order[0 .. n_live))."""
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
        for field in range(4):  # the first block goes into buffer 0
            state[field] = 0

        @pl.loop(0, ahead + 1)  # a loop: each ask is ppcb bodies of copies
        def _(_):
            ask()

    length = lengths_ref[b]
    n = pages_of(b)
    q = q_ref[0].astype(jnp.float32)  # [KH, G, Hd]

    def write_new_row(slot, j):
        """Rule 6, once the row's last page is page j of buffer `slot`."""
        pid = tables_ref[b * maxp + n - 1]
        off = lax.rem(length - 1, ps)
        tile = pl.multiple_of((off // TILE_ROWS) * TILE_ROWS, TILE_ROWS)
        rows = pl.ds(tile, TILE_ROWS)

        def write_back(w, act):
            """`act` on write tile w's way back to the pool, scale rows
            and all. Built again to wait: a semaphore counts bytes, and
            every row's write has as many."""
            pairs = [(new.s_rows.at[w], s_hbm.at[:, layer, :, pid])]
            if split_kv:
                pairs += [(new.kv_tile.at[w, h],
                           kv_hbm.at[h, layer, :, pid, rows]) for h in (0, 1)]
            else:
                pairs.append((new.kv_tile.at[w],
                              kv_hbm.at[:, layer, :, pid, rows]))
            for pair in pairs:
                act(pltpu.make_async_copy(*pair, new.sem.at[w]))

        w = lax.rem(k, WRITES_AHEAD)

        @pl.when(k >= WRITES_AHEAD)
        def _():  # what left from this tile WRITES_AHEAD rows ago has left
            write_back(w, wait)

        # nobody live (the grid serves an idle row): a place no iota has
        at = jnp.where(n_live_ref[1] > 0, off, -1)
        sub = lax.broadcasted_iota(jnp.int32, (TILE_ROWS, Hd), 0) == at - tile
        lane = lax.broadcasted_iota(jnp.int32, (1, ps), 1) == at
        for h in (0, 1):
            codes = new.codes[0, h]  # [KH, Hd] int32
            for kh in range(KH):
                old = kv_buf[slot, j, h, kh, rows, :].astype(jnp.int32)
                patched = jnp.where(sub, codes[kh:kh + 1, :],
                                    old).astype(jnp.int8)
                kv_buf[slot, j, h, kh, rows, :] = patched
                new.kv_tile[w, h, kh] = patched
                scales = jnp.where(lane, new.scales[(b * 2 + h) * KH + kh],
                                   s_buf[slot, j, h, kh])
                s_buf[slot, j, h, kh] = scales
                new.s_rows[w, h, kh] = scales
        write_back(w, start)

        @pl.when(k == n_live - 1)
        def _():  # and before the call ends, every write still out
            for back in range(WRITES_AHEAD):
                @pl.when(k >= back)
                def _():
                    write_back(lax.rem(k - back, WRITES_AHEAD), wait)

    def body(i, carry):
        slot = state[_TAKE_SLOT]

        def page(j):
            """A live page of the block and its mask, all kv heads
            batched: shapes stay <= 3-D with the head axis leading — no
            Mosaic relayouts, and each dot is KH x (G x ps x Hd)."""
            def keep(shape):
                pos = ((i * ppcb + j) * ps
                       + lax.broadcasted_iota(jnp.int32, shape, 2))
                if tree is not None:
                    return _tree_keep(
                        pos, length,
                        lax.broadcasted_iota(jnp.int32, shape, 1) // g_base,
                        q_rep, tree)
                limit = length
                if q_rep > 1:
                    limit = length + lax.broadcasted_iota(
                        jnp.int32, shape, 1) // g_base
                kept = pos < limit
                if starts_ref is not None:
                    kept &= pos >= starts_ref[b]
                return kept

            return (kv_buf[slot, j, 0].astype(jnp.float32),  # [KH, ps, Hd]
                    kv_buf[slot, j, 1].astype(jnp.float32),
                    s_buf[slot, j, 0], s_buf[slot, j, 1],    # [KH, 1, ps]
                    keep)

        def block(count, last=False):
            def run(carry):
                copies(b, i, slot, count, wait)
                if last:
                    write_new_row(slot, count - 1)
                for at in range(0, count, fold):
                    carry = _fold_block(q, lambda j, at=at: page(at + j),
                                        min(fold, count - at), carry)
                return carry
            return run

        if new is None:
            carry = by_live_count(n, i, block, carry)
        else:  # a whole block that is the row's last: one body more
            live = jnp.minimum(ppcb, n - i * ppcb)
            carry = lax.switch(
                jnp.where(n == (i + 1) * ppcb, ppcb, live - 1),
                [block(c, last=c < ppcb) for c in range(1, ppcb + 1)]
                + [block(ppcb, last=True)], carry)
        state[_TAKE_SLOT] = after(slot)
        ask()  # into the buffer this block was taken from (rule 3)
        return carry

    init = (jnp.full((KH, G, 1), NEG_INF, jnp.float32),
            jnp.zeros((KH, G, 1), jnp.float32),
            jnp.zeros((KH, G, Hd), jnp.float32))
    m, l, acc = lax.fori_loop(0, pl.cdiv(n, ppcb), body, init)
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)


def page_counts(lengths, page_size: int, max_pages: int,
                block: int | None = None, mask=None,
                fold: int | None = None) -> tuple[int, int, int, int]:
    """On the host, for a batch's `lengths` (numpy, any shape: a call a
    row of its last axis) and its `mask` of live rows (broadcast against
    them; None: every row): the
    pages the kernel copies and multiplies (each live row's n, an idle
    row's none), what whole blocks over EVERY row would cover, which
    is what it walked before it stopped at n and at the live rows (an
    idle row walked a block), the softmax updates it makes for the
    live rows' pages at `fold` pages an update (`fold_pages` of the
    caller's tile; None: a whole block), each block's live pages apart,
    and the grid steps of those calls: one a LIVE row (rule 4 of
    `_int8_kernel`), and one for a call with nobody live. The engine's
    `decode_attn_pages_live` / `decode_attn_pages_walked` /
    `decode_attn_updates` / `decode_attn_grid_steps`."""
    block = min(block or PAGES_PER_BLOCK, max_pages)
    fold = min(fold or block, block)
    n = np.clip(-(-np.asarray(lengths, np.int64) // page_size), 1, max_pages)
    walked = np.minimum(-(-n // block) * block, max_pages)
    live = np.ones(n.shape, bool)
    if mask is not None:
        live = live & np.asarray(mask, bool)
        n = n * live
    updates = n // block * -(-block // fold) + -(-(n % block) // fold)
    grid_steps = np.maximum(live.sum(axis=-1), 1)
    return (int(n.sum()), int(walked.sum()), int(updates.sum()),
            int(grid_steps.sum()))


_STATIC = ("scale", "pages_per_compute_block", "q_rep", "tree", "interpret",
           "split_kv")


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_attention_int8_window(q, kv_pages, kv_scales, page_table, lengths,
                                layer, starts, **kw):
    """paged_attention_int8 for a WINDOW row of a kv_cache.WindowPool:
    `page_table` [B, maxw] is the sequence's window table (the pages
    that reach into the window, oldest first), `lengths` and `starts`
    [B] count from that table's first page: the row attends tokens
    [starts, lengths). A program of its own under a name of its own, so
    that a trace tells the window rows' calls from the global rows'."""
    return _paged_attention_int8(q, kv_pages, kv_scales, page_table, lengths,
                                 layer, starts=starts, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_attention_int8(q, kv_pages, kv_scales, page_table, lengths, layer,
                         **kw):
    return _paged_attention_int8(q, kv_pages, kv_scales, page_table, lengths,
                                 layer, **kw)


def _paged_attention_int8(
    q: jax.Array,          # [B, H, Hd], or [B, R, H, Hd] when q_rep=R>1
    kv_pages: jax.Array,   # FULL pool [2, L, KH, P, ps, Hd] int8
    kv_scales: jax.Array,  # FULL scales [2, L, KH, P, ps] f32
    page_table: jax.Array,  # [B, maxp] int32
    lengths: jax.Array,     # [B] int32, incl. current token (R>1: the
                            # FIRST query's; query j attends lengths+j)
    layer,                  # int32 scalar: which layer to attend over
    *,
    scale: float | None = None,
    pages_per_compute_block: int | None = None,
    q_rep: int = 1,
    tree=None,
    interpret: bool = False,
    split_kv: bool | None = None,
    live: Optional[LiveRows] = None,
    starts: Optional[jax.Array] = None,  # [B] int32: a window row's
    new=None,  # (codes [2, KH, B, Hd] int8, scales [2, KH, B]): the
               # step's new row a slot, NOT in the pool yet
):
    """What `paged_attention_int8` (starts None) and
    `paged_attention_int8_window` run.

    `new` (a decode step's one new row a slot, as kv_append_int8 takes
    it; q_rep 1, no tree, no window): token lengths - 1 of every LIVE row
    is not in the pool yet and the call writes it there, in place, on its
    way (`_int8_kernel`, rule 6): -> (out, kv_pages, kv_scales), the pool
    byte for byte what kv_append_int8 and then this call without `new`
    leave, and `out` bit for bit. The caller donates the pool.

    `live` (live_rows of the step's `active` mask): the kernel walks
    those rows alone, and an idle row's output is zeros, whatever its
    length and table row say. None: every row is live.

    q_rep > 1 is the speculative-verify form: R consecutive query
    positions per sequence ride the kernel's G axis, so the KV pages
    stream from HBM ONCE per sequence instead of once per position
    (folding positions into the batch costs R x the KV traffic AND
    R x the DMA issues — the measured kernel floor).

    tree = (k, n_branches) STATIC (tree verify; requires
    q_rep == 1 + n_branches*k): the positions are the packed
    _tree_layout lattice and query row j attends the committed prefix
    plus its ancestor-or-self chain (_tree_keep) instead of the linear
    pos < length+j span. KV traffic is unchanged: the tree only edits
    the in-kernel mask."""
    if tree is not None:
        assert q_rep == 1 + tree[0] * tree[1], (q_rep, tree)
    if q_rep > 1:
        B, R, H, Hd = q.shape
        assert R == q_rep, (q.shape, q_rep)
    else:
        B, H, Hd = q.shape
    two, L, KH, P, ps, _ = kv_pages.shape
    assert two == 2, kv_pages.shape
    maxp = page_table.shape[1]
    G = (H // KH) * q_rep
    s = scale if scale is not None else Hd ** -0.5

    if q_rep > 1:
        # j-major rows: row = j * (H//KH) + g, matching the kernel's
        # qoff = row // g_base masking.
        qk = (q.astype(jnp.float32) * s).reshape(
            B, q_rep, KH, H // KH, Hd).transpose(0, 2, 1, 3, 4).reshape(
            B, KH, G, Hd)
    else:
        qk = (q.astype(jnp.float32) * s).reshape(B, KH, G, Hd)
    ppcb = min(pages_per_compute_block or PAGES_PER_BLOCK, maxp)
    # Scale pages as 2-D [1, ps] tiles (metadata-only reshape of the
    # CONTIGUOUS full array): the kernel DMAs and consumes them without
    # any vector relayout.
    s2 = kv_scales.reshape(2, L, KH, P, 1, ps)

    if split_kv is None:  # from the pool's shape alone
        split_kv = L * KH * P * ps * Hd >= SPLIT_KV_BYTES
    ahead = blocks_ahead(KH, ps, Hd, ppcb, kv_scales.dtype.itemsize)
    assert starts is None or (q_rep == 1 and tree is None), (q_rep, tree)
    assert new is None or (starts is None and q_rep == 1 and tree is None), (
        "one new row a slot, of a plain decode step")
    kernel = functools.partial(
        _int8_append_kernel if new is not None
        else _int8_kernel if starts is None else _int8_window_kernel,
        ppcb=ppcb, fold=fold_pages(KH, G, ppcb), maxp=maxp, page_size=ps,
        ahead=ahead, q_rep=q_rep, tree=tree, split_kv=split_kv)

    def qmap(k, Ln, T, LY, order, *_):
        return (order[k], 0, 0, 0)

    rows = every_row(B) if live is None else live
    # the grid is as long as the walk; with nobody live it serves row
    # order[0] alone (an idle one: its page, the sink, is read and the
    # select below discards what comes of it)
    n_walk = jnp.maximum(rows.n_live, 1)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    extra = ()  # the scalar prefetches after n_walk
    in_specs = [pl.BlockSpec((1, KH, G, Hd), qmap)]
    out_specs = [pl.BlockSpec((1, KH, G, Hd), qmap)]
    out_shape = [jax.ShapeDtypeStruct((B, KH, G, Hd), jnp.float32)]
    scratch = [
        pltpu.VMEM((ahead + 1, ppcb, 2, KH, ps, Hd), jnp.int8),
        pltpu.VMEM((ahead + 1, ppcb, 2, KH, 1, ps), kv_scales.dtype),
        pltpu.SemaphoreType.DMA((ahead + 1,)),
        pltpu.SMEM((4,), jnp.int32),
    ]
    aliases = {}
    if starts is not None:
        extra = (starts.astype(jnp.int32),)
    if new is not None:
        codes, scales = new
        assert codes.shape == (2, KH, B, Hd) and scales.shape == (2, KH, B), (
            codes.shape, scales.shape, kv_pages.shape)
        # by slot, as kv_append_int8 hands them to its kernel; and the
        # live rows' count as it is, beside the walk's
        n_walk = jnp.concatenate([n_walk, rows.n_live])
        extra = (scales.transpose(2, 0, 1).reshape(-1),)
        in_specs.append(pl.BlockSpec((1, 2, KH, Hd), qmap))
        out_specs += [any_spec, any_spec]
        out_shape += [jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
                      jax.ShapeDtypeStruct(s2.shape, s2.dtype)]
        scratch += [
            pltpu.VMEM((WRITES_AHEAD, 2, KH, TILE_ROWS, Hd), jnp.int8),
            pltpu.VMEM((WRITES_AHEAD, 2, KH, 1, ps), kv_scales.dtype),
            pltpu.SemaphoreType.DMA((WRITES_AHEAD,)),
        ]
        # operands count the scalar prefetches (6), q and the codes
        aliases = {8: 1, 9: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + len(extra),
        grid=(n_walk[0],),
        in_specs=in_specs + [any_spec, any_spec],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    # The blocks are asked for in one chain over the live rows, and each
    # of those takes at least one: a live row of length 0 takes one page,
    # masked but for its first token. Clamp rather than assert.
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    out, *pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # Sequential grid: what was asked for and taken threads through
        # SMEM from one grid step to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=None if starts is None else "paged_attention_int8_window",
    )(lengths, page_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), rows.order, n_walk, *extra,
      qk, *(() if new is None else (
          codes.transpose(2, 0, 1, 3).astype(jnp.int32),)), kv_pages, s2)
    if live is not None:
        # a row the grid never served holds whatever the buffer held: a
        # select, so that no stale NaN reaches a router or a sampler
        out = jnp.where(live.mask[:, None, None, None], out, 0.0)
    if q_rep > 1:
        return out.reshape(B, KH, q_rep, H // KH, Hd).transpose(
            0, 2, 1, 3, 4).reshape(B, q_rep, H, Hd).astype(q.dtype)
    out = out.reshape(B, H, Hd).astype(q.dtype)
    if new is None:
        return out
    return out, pool[0], pool[1].reshape(kv_scales.shape)
