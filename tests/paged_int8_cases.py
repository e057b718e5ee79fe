"""The cases, pools and references of the int8 paged-attention kernel's
page tests (serving/paged_attention_int8.py, interpreted on the CPU as
tests/test_tree_kernel.py runs it, against the XLA gather reference),
shared by the four files that run them:

- test_paged_attention_int8_pages.py, ..._pages_q_rep4.py and
  ..._pages_tree.py: a page past a row's last and an idle row under the
  mask, a file a FORM (one, four query rows, the tree), so that a form's
  traces are shared inside a file and the forms run side by side; the
  tree's file also holds a batch of 64, `live_rows` and `page_counts`
- test_paged_attention_int8_window_fold_new_row.py: a window row's
  start, an update a block of pages, the step's new row

A TEST'S COST IS ITS TRACE (PR 45): 4.5 s a kernel the interpreter
traces, whatever the rows hold. A new case shares its shapes (rows,
table width, pages a block, form) with an old one where it can, and then
costs nothing; one that needs a new shape costs a trace. `--dist
loadfile` gives a FILE to one worker, so a file of these is near the
run's critical path: keep each under ~400 s of test time in the driver's
junit file (ROADMAP.md D14), and when one grows past it, move a form or
a section to a file of its own, as PR 47 did, rather than wait for exit
code 124.

Every table entry past a row's n = clip(cdiv(length + q_rep - 1, ps), 1,
maxp) points at a POISON page (codes 127, scales NaN) and the reference
is taken over the live entries alone, so a dead page that is copied or
multiplied fails by NaN. The interpreter starts a scratch buffer at NaN
too: a page multiplied without having been copied fails the same way.

With a mask (`live_rows` of a step's `active`) the kernel walks the live
rows alone: an idle row's WHOLE table row, its first entry too, points at
the poison page, its output is zeros, and the live rows read as before.
"""

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_tree_attention_int8_reference_fused)

PS, HD, KH, H, LAYERS, LAYER = 8, 16, 2, 4, 2, 1
TREE = (2, 2)  # k, branches: 5 packed nodes
FORMS = {"q_rep1": (1, None), "q_rep4": (4, None),
         "tree": (1 + TREE[0] * TREE[1], TREE)}
# name: (table width, pages a block or None for the kernel's own, the
# rows' lengths as a function of the query rows r). `full` is a row whose
# LAST query position sits on the table's last token.
LENGTHS = {
    "one": (4, None, lambda r: [1, 1, 1]),
    "page": (4, None, lambda r: [PS, PS, PS]),
    "page_plus_one": (4, None, lambda r: [PS + 1, PS + 1, PS + 1]),
    "ragged": (4, None, lambda r: [1, 5, PS, PS + 1, 17, 4 * PS - r + 1]),
    "full": (4, None, lambda r: [4 * PS - r + 1] * 3),
    "idle_between_live": (4, None, lambda r: [13, 0, 22]),
    "several_blocks": (20, 5, lambda r: [3, 6 * PS - r + 1, 20 * PS - r + 1]),
    "block_not_a_divisor": (20, 8, lambda r: [20 * PS - r + 1, PS + 2,
                                              11 * PS]),
    # one softmax update a block (PR 45): a last block of 1, 2, 3 and 4
    # pages behind two whole ones
    "last_block_of_1_2_3_4": (12, None, lambda r: [
        n * PS - r + 1 for n in (9, 10, 11, 12)]),
    # a first block that is partly FUTURE to the earlier query rows: the
    # later rows' tokens, a page that only the last row reaches
    "first_block_partly_future": (4, None, lambda r: [
        1, 2, PS - 1, PS, 2 * PS - 1, 3 * PS]),
}
# name: (table width, pages a block, lengths, the rows that are live)
MASKED = {
    "idle_scattered_among_live": (
        4, None, lambda r: [13, 1, 4 * PS - r + 1, 1, 1, PS + 1, 22],
        [True, False, True, False, False, True, True]),
    "all_idle": (4, None, lambda r: [1, 9, 1], [False] * 3),
    "last_row_idle": (4, None, lambda r: [PS, 2 * PS + 3, 1],
                      [True, True, False]),
    "first_rows_idle_several_blocks": (
        20, 5, lambda r: [1, 1, 6 * PS - r + 1, 1, 20 * PS - r + 1],
        [False, False, True, False, True]),
    "one_live_row": (4, None, lambda r: [1, 1, 3 * PS, 1],
                     [False, False, True, False]),
}
# The walk over the live rows, a grid step each (PR 53: a block is asked
# for into the buffer the block just multiplied was taken from, and
# `blocks_ahead` + 1 of them are on their way from a call's first grid
# step: five at these tiny pages): twelve rows of 1 to 8 pages (one or two
# blocks of 4) of which none, one, two, five, eleven and all are live,
# never a prefix of the batch. The look-ahead is deeper than what one and
# two live rows have, and the longer walks go round the five buffers.
# name: the live rows
_WALK_PAGES = (3, 8, 1, 5, 2, 8, 4, 6, 1, 7, 5, 2)
_WALKS = {"walk_of_0_of_12": (), "walk_of_1_of_12": (7,),
          "walk_of_2_of_12": (3, 9), "walk_of_5_of_12": (1, 4, 5, 9, 11),
          "walk_of_11_of_12": tuple(b for b in range(12) if b != 6),
          "walk_of_12_of_12": tuple(range(12))}
WALKS = {name: (8, None,
                lambda r: [n * PS - r + 1 if n > 1 else 1
                           for n in _WALK_PAGES],
                [b in rows for b in range(12)])
         for name, rows in _WALKS.items()}


def _pool(pages, seed):
    """A fused pool of LAYERS layers whose last page is the poison."""
    rng = np.random.default_rng(seed)
    shape = (2, LAYERS, KH, pages, PS, HD)
    kv = rng.integers(-127, 128, shape, dtype=np.int8)
    s = rng.random(shape[:-1], dtype=np.float32) * 0.05 + 0.01
    kv[:, :, :, 0] = 0          # the sink
    kv[:, :, :, -1] = 127
    s[:, :, :, -1] = np.nan
    return jnp.asarray(kv), jnp.asarray(s)


def _reference(q, kv, s, table, lengths, q_rep, tree):
    kv, s = kv[:, LAYER], s[:, LAYER]
    if tree is not None:
        _, anc = engine_model._tree_layout(*tree)
        return paged_tree_attention_int8_reference_fused(
            q.transpose(0, 2, 1, 3), kv, s, table, lengths,
            anc).transpose(0, 2, 1, 3)
    if q_rep == 1:
        return pa8.paged_attention_int8_reference_fused(q, kv, s, table,
                                                        lengths)
    return jnp.stack([pa8.paged_attention_int8_reference_fused(
        q[:, j], kv, s, table, lengths + j) for j in range(q_rep)], axis=1)


def a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv):
    q_rep, tree = FORMS[form]
    maxp, block, lengths_of = LENGTHS[case]
    lengths = np.asarray(lengths_of(q_rep), np.int32)
    B = len(lengths)
    pages = B * maxp + 2
    kv, s = _pool(pages, seed=len(case) + q_rep)
    n = np.clip(-(-(lengths + q_rep - 1) // PS), 1, maxp)
    live = np.arange(maxp)[None, :] < n[:, None]
    own = 1 + np.arange(B * maxp).reshape(B, maxp)
    poisoned = jnp.asarray(np.where(live, own, pages - 1), jnp.int32)
    clean = jnp.asarray(np.where(live, own, 0), jnp.int32)
    shape = (B, H, HD) if q_rep == 1 else (B, q_rep, H, HD)
    q = jax.random.normal(jax.random.PRNGKey(q_rep), shape, jnp.float32)

    got = np.asarray(pa8.paged_attention_int8(
        q, kv, s, poisoned, jnp.asarray(lengths), LAYER, q_rep=q_rep,
        tree=tree, pages_per_compute_block=block, split_kv=split_kv,
        interpret=True))
    assert np.isfinite(got).all(), "a dead page was copied or multiplied"
    served = lengths > 0  # an idle row's output is nobody's
    want = np.asarray(_reference(q, kv, s, clean, jnp.asarray(lengths),
                                 q_rep, tree))
    np.testing.assert_allclose(got[served], want[served], atol=2e-5,
                               rtol=2e-5)
    # every row live, said with a mask: the same walk, bit for bit
    masked = np.asarray(pa8.paged_attention_int8(
        q, kv, s, poisoned, jnp.asarray(lengths), LAYER, q_rep=q_rep,
        tree=tree, pages_per_compute_block=block, split_kv=split_kv,
        interpret=True, live=pa8.live_rows(jnp.ones((B,), bool))))
    np.testing.assert_array_equal(masked, got)


def an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv):
    """As the engine sends an idle slot (length 1, a table row of page 0)
    but with its table row on the poison page: nothing of it may be
    copied, and a row the grid never served must not show stale VMEM."""
    q_rep, tree = FORMS[form]
    maxp, block, lengths_of, mask = (MASKED.get(case) or WALKS[case])
    lengths = np.asarray(lengths_of(q_rep), np.int32)
    mask = np.asarray(mask)
    B = len(lengths)
    pages = B * maxp + 2
    kv, s = _pool(pages, seed=len(case) + q_rep)
    n = np.clip(-(-(lengths + q_rep - 1) // PS), 1, maxp)
    live = (np.arange(maxp)[None, :] < n[:, None]) & mask[:, None]
    own = 1 + np.arange(B * maxp).reshape(B, maxp)
    poisoned = jnp.asarray(np.where(live, own, pages - 1), jnp.int32)
    clean = jnp.asarray(np.where(live, own, 0), jnp.int32)
    shape = (B, H, HD) if q_rep == 1 else (B, q_rep, H, HD)
    q = jax.random.normal(jax.random.PRNGKey(q_rep), shape, jnp.float32)

    got = np.asarray(pa8.paged_attention_int8(
        q, kv, s, poisoned, jnp.asarray(lengths), LAYER, q_rep=q_rep,
        tree=tree, pages_per_compute_block=block, split_kv=split_kv,
        interpret=True, live=pa8.live_rows(jnp.asarray(mask))))
    assert np.isfinite(got).all(), "an idle row's page was copied"
    assert not got[~mask].any(), "an idle row's output is zeros"
    if mask.any():
        want = np.asarray(_reference(q, kv, s, clean, jnp.asarray(lengths),
                                     q_rep, tree))
        np.testing.assert_allclose(got[mask], want[mask], atol=2e-5,
                                   rtol=2e-5)
        # ... and bit for bit what the same rows read with nobody idle
        alone = np.asarray(pa8.paged_attention_int8(
            q[mask], kv, s, poisoned[mask], jnp.asarray(lengths[mask]),
            LAYER, q_rep=q_rep, tree=tree, pages_per_compute_block=block,
            split_kv=split_kv, interpret=True))
        np.testing.assert_array_equal(got[mask], alone)


# -- a WINDOW row's start (kv_cache.WindowPool; PR 44) ----------------------
# name: (lengths, starts, the rows that are live or None), each counted
# from the row's table's first page as the step program counts them. The
# start lies at zero, inside the first page, on a page boundary, inside a
# later page (the table then holds a page wholly behind the window: two
# blocks in flight), and on the row's last token.
WINDOWED = {
    "at_zero": ([13, 3 * PS, 1], [0, 0, 0], None),
    "inside_the_first_page": ([2 * PS + 3, 4 * PS, PS], [3, PS - 1, 5],
                              None),
    "on_a_page_boundary": ([3 * PS, 2 * PS + 1, 4 * PS], [PS, 2 * PS, PS],
                           None),
    "a_page_wholly_behind": ([4 * PS, 3 * PS + 2, 9], [PS + 2, 2 * PS + 1, 0],
                             None),
    "the_last_token_alone": ([17, 4 * PS, 1], [16, 4 * PS - 1, 0], None),
    "an_idle_row_between": ([2 * PS + 3, 1, 4 * PS], [3, 0, PS + 1],
                            [True, False, True]),
    "several_blocks": ([20 * PS, 11 * PS + 5, 6 * PS], [2 * PS + 5, PS, 7],
                       None),
    # rows of several blocks of 4, one update a block (PR 45): the start
    # in the first page, on its boundary, past the whole first page (the
    # block's leading page gives no weight) and past the whole first
    # BLOCK (an update in which nothing is kept before one in which
    # something is); last blocks of 1, 2, 3 and 4 pages
    "blocks_start_in_the_first_page": (
        [9 * PS, 10 * PS - 3, 11 * PS + 1 - PS, 12 * PS], [3, PS - 1, 1, 5],
        None),
    "blocks_start_on_a_page_boundary": (
        [9 * PS - 1, 10 * PS, 11 * PS, 12 * PS], [PS, PS, 2 * PS, 3 * PS],
        None),
    "blocks_first_page_wholly_behind": (
        [9 * PS, 10 * PS, 11 * PS - 4, 12 * PS],
        [PS + 1, 2 * PS - 1, PS + 3, 3 * PS + 2], None),
    "blocks_first_block_wholly_behind": (
        [9 * PS, 10 * PS - 1, 11 * PS, 12 * PS],
        [4 * PS, 4 * PS + 3, 5 * PS - 1, 8 * PS + 1], None),
    # five live rows of three blocks each among seven idle ones (PR 53):
    # fifteen blocks through the walk's five buffers
    "blocks_walked_for_5_of_12_rows": (
        [1, 9 * PS, 1, 1, 10 * PS - 3, 12 * PS, 1, 1, 1, 11 * PS + 1, 1,
         9 * PS + 1],
        [0, 3, 0, 0, PS, 4 * PS + 3, 0, 0, 0, 2 * PS - 1, 0, 8 * PS],
        [b in (1, 4, 5, 9, 11) for b in range(12)]),
}


# -- one softmax update a BLOCK of pages (PR 45) -----------------------------
# `fold_pages` gives the pages of an update from the score tile's shape;
# whatever width it gives, 1 (a chain a page) to the block's, the kernel
# reads the same to float32 rounding, under every mask it has. `folding`
# is the probes' way to another width than the rule's.
FOLDED = {"q_rep1": (1, None, False), "q_rep4": (4, None, False),
          "tree": (1 + TREE[0] * TREE[1], TREE, False),
          "window": (1, None, True)}


# -- the step's new row, written by the attention call (PR 46) --------------
# `paged_attention_int8(..., new=(codes, scales))` against the two calls a
# step ran until then (kv_append_int8, then the kernel): the pools compared
# BYTE FOR BYTE everywhere, the outputs bit for bit. At the tiles' real
# size (the write is a 32-row tile of a 128-row page), pools of random
# bytes, every live row on pages of its own and an idle one on page 0.

APS, AHD, AKH, AH = 128, 128, 2, 4
# name: (table width, pages a block, lengths, live rows or None: no mask,
# split descriptors). Eight rows each and few table widths, so that the
# interpreted programs are traced once a form and not once a case.
APPENDED = {
    "offset_0": (4, None, [1, APS + 1, 2 * APS + 1, 3 * APS + 1] * 2, None,
                 False),
    "offset_31": (4, None, [32, APS + 32, 2 * APS + 32, 3 * APS + 32] * 2,
                  None, False),
    "offset_32": (4, None, [33, APS + 33, 2 * APS + 33, 3 * APS + 33] * 2,
                  None, False),
    "offset_last_of_a_page": (4, None, [APS, 2 * APS, 3 * APS, 4 * APS] * 2,
                              None, False),
    "length_1": (4, None, [1] * 8, None, False),
    "more_rows_than_writes_ahead": (
        4, None, [7, 40, 129, 200, 256, 257, 300, 512], None, False),
    # the row's last page is the only page of its last block (5 and 9 of
    # blocks of 4), and a whole block that IS the last (4, 8)
    "last_page_alone_in_its_block": (
        9, None, [4 * APS + 1, 9 * APS, 4 * APS, 8 * APS - 3, 5 * APS, 1,
                  8 * APS + 1, 3 * APS], None, False),
    "blocks_of_2_and_every_count": (
        5, 2, [APS, 2 * APS, 2 * APS + 7, 4 * APS, 5 * APS - 1, 1, 3 * APS,
               4 * APS + 1], None, False),
    "idle_between_live": (
        4, None, [13, 1, 300, 1, 1, APS + 1, 22, 4 * APS],
        [True, False, True, False, False, True, True, True], False),
    "first_and_last_rows_idle": (
        4, None, [1, 2 * APS, 77, 1, 1, 3 * APS + 5, 9, 1],
        [False, True, True, False, False, True, True, False], False),
    "all_idle": (4, None, [1, 9, 1, 300, 1, 1, 1, 1], [False] * 8, False),
    "every_row_live_said_with_a_mask": (
        4, None, [5, APS + 64, 3 * APS, 1, 2, 4 * APS, 33, 2 * APS + 1],
        [True] * 8, False),
    "split_descriptors": (
        9, None, [1, 32, APS, 4 * APS + 1, 8 * APS, 9 * APS, 77, 5 * APS],
        None, True),
    "split_descriptors_idle_between_live": (
        4, None, [13, 1, 300, 1, 4 * APS, 1, 1, APS + 32],
        [True, False, True, False, True, False, False, True], True),
    # five live rows of two and three blocks among idle ones (PR 53):
    # thirteen blocks through the five buffers pages of 68 KB get, each
    # asked for after the block before it in its buffer was patched and
    # multiplied, and five writes through WRITES_AHEAD tiles
    "idle_between_live_several_blocks": (
        9, None, [1, 9 * APS, 5 * APS + 1, 1, 8 * APS + 33, 1, 9 * APS - 1,
                  6 * APS], [False, True, True, False, True, False, True,
                             True], False),
}


def _step_with_a_new_row(lengths, maxp, mask, kv_heads=AKH, seed=0):
    """(q, pool kv, s, table, lengths, live, new row's codes, scales, the
    rows' (page, offset)) of one decode step: the new row is token
    lengths - 1 of every live slot, and an idle slot's table row is the
    sink's."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pages = B * maxp + 1
    shape = (2, LAYERS, kv_heads, pages, APS, AHD)
    kv = jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))
    s = jnp.asarray(rng.random(shape[:-1], dtype=np.float32) * 0.05 + 0.01)
    table = 1 + np.arange(B * maxp).reshape(B, maxp)
    if mask is not None:
        table = np.where(np.asarray(mask)[:, None], table, 0)
    lengths = np.asarray(lengths, np.int32)
    page_idx = table[np.arange(B), (lengths - 1) // APS]
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (B, kv_heads * (AH // AKH), AHD), jnp.float32)
    codes = jnp.asarray(rng.integers(-127, 128, (2, kv_heads, B, AHD),
                                     dtype=np.int8))
    scales = jnp.asarray(rng.random((2, kv_heads, B), dtype=np.float32))
    live = None if mask is None else pa8.live_rows(jnp.asarray(mask))
    return (q, kv, s, jnp.asarray(table, jnp.int32), jnp.asarray(lengths),
            live, codes, scales, jnp.asarray(page_idx, jnp.int32),
            jnp.asarray((lengths - 1) % APS, jnp.int32))
