"""The int8 paged-attention kernel reads only the pages a row HAS
(serving/paged_attention_int8.py): interpreted on the CPU as
tests/test_tree_kernel.py runs it, against the XLA gather reference.

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
import pytest

from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_tree_attention_int8_reference_fused)
from scripts.measure_paged_attention import folding

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


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("case", list(LENGTHS))
def test_a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
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


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("case", list(MASKED))
def test_an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv):
    """As the engine sends an idle slot (length 1, a table row of page 0)
    but with its table row on the poison page: nothing of it may be
    copied, and a row the grid never served must not show stale VMEM."""
    q_rep, tree = FORMS[form]
    maxp, block, lengths_of, mask = MASKED[case]
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


# a decode batch of 64 slots as the cells send it: name -> live slots
LIVE_OF_64 = {"3_of_64": 3, "60_of_64": 60, "1_of_64": 1, "all_64": 64}


@pytest.mark.parametrize("case", list(LIVE_OF_64))
def test_a_batch_of_64_attends_for_its_live_slots_alone(case):
    """3, 60, 1 and all of 64 slots live, scattered among the idle ones
    and never a prefix of the batch; an idle slot as the engine sends it
    (length 1) but with its table row on the poison page. The live rows
    read what the reference reads, the idle ones zeros, and no mask at
    all is everyone live, bit for bit."""
    n_live, B, maxp = LIVE_OF_64[case], 64, 4
    rng = np.random.default_rng(n_live)
    mask = np.zeros((B,), bool)
    mask[1 + rng.permutation(B - 1)[:n_live]] = True
    if n_live == B:
        mask[:] = True
    assert mask.sum() == n_live and (n_live == B or not mask[0])
    lengths = np.where(mask, rng.integers(1, maxp * PS + 1, B), 1).astype(
        np.int32)
    pages = B * maxp + 2
    kv, s = _pool(pages, seed=n_live)
    n = np.clip(-(-lengths // PS), 1, maxp)
    live = (np.arange(maxp)[None, :] < n[:, None]) & mask[:, None]
    own = 1 + np.arange(B * maxp).reshape(B, maxp)
    poisoned = jnp.asarray(np.where(live, own, pages - 1), jnp.int32)
    clean = jnp.asarray(np.where(live, own, 0), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(n_live), (B, H, HD),
                          jnp.float32)

    got = np.asarray(pa8.paged_attention_int8(
        q, kv, s, poisoned, jnp.asarray(lengths), LAYER, interpret=True,
        live=pa8.live_rows(jnp.asarray(mask))))
    assert np.isfinite(got).all(), "an idle row's page was copied"
    assert not got[~mask].any(), "an idle row's output is zeros"
    want = np.asarray(_reference(q, kv, s, clean, jnp.asarray(lengths), 1,
                                 None))
    np.testing.assert_allclose(got[mask], want[mask], atol=2e-5, rtol=2e-5)
    if mask.all():  # `live=None` is everyone live
        np.testing.assert_array_equal(got, np.asarray(pa8.paged_attention_int8(
            q, kv, s, poisoned, jnp.asarray(lengths), LAYER, interpret=True)))


def test_live_rows_lists_the_live_rows_first_and_in_order():
    mask = jnp.asarray([False, True, True, False, True, False])
    rows = pa8.live_rows(mask)
    assert np.asarray(rows.order).tolist() == [1, 2, 4, 0, 3, 5]
    assert np.asarray(rows.n_live).tolist() == [3]
    assert rows.order.dtype == jnp.int32 and rows.n_live.dtype == jnp.int32
    every = pa8.every_row(4)
    assert np.asarray(every.order).tolist() == [0, 1, 2, 3]
    assert np.asarray(every.n_live).tolist() == [4] and every.mask.all()
    none = pa8.live_rows(jnp.zeros((3,), bool))
    assert np.asarray(none.n_live).tolist() == [0]


def test_page_counts_of_a_known_batch():
    """What the engine's three counters add a step: the rows' pages, what
    whole blocks over the same rows cover, and the softmax updates the
    kernel folds the rows' pages into."""
    lengths = np.array([1, 128, 129, 0, 640, 2560, 4000], np.int32)
    live, walked, updates = pa8.page_counts(lengths, page_size=128,
                                            max_pages=20, block=5)
    assert (live, walked) == (1 + 1 + 2 + 1 + 5 + 20 + 20,
                              5 + 5 + 5 + 5 + 5 + 20 + 20)
    assert updates == 1 + 1 + 1 + 1 + 1 + 4 + 4  # a block an update
    # a block that does not divide the table's width stops at the width
    assert pa8.page_counts(lengths[-1:], 128, 20, block=8) == (20, 20, 3)
    assert pa8.page_counts(lengths[:0], 128, 20, block=8) == (0, 0, 0)
    # with the step's mask an idle row has no page to copy; what whole
    # blocks over every row covered is the walk it is compared with
    mask = np.array([True, False, True, False, True, False, True])
    assert pa8.page_counts(lengths, 128, 20, block=5, mask=mask) == (
        1 + 2 + 5 + 20, walked, 1 + 1 + 1 + 4)
    # a block of K steps: [K, B] lengths against the [B] mask
    assert pa8.page_counts(np.stack([lengths, lengths + 1]), 128, 20,
                           block=5, mask=mask) == (
        (1 + 2 + 5 + 20) + (1 + 2 + 6 + 20), 2 * walked + 5,
        (1 + 1 + 1 + 4) + (1 + 1 + 2 + 4))


# rows of 1, 4, 6, 7, 11 and 20 pages; name: (pages a block, pages an
# update, the updates each row makes)
UPDATES = {
    "a_page_an_update": (4, 1, [1, 4, 6, 7, 11, 20]),
    "two_pages_an_update": (4, 2, [1, 2, 3, 4, 6, 10]),
    "three_of_a_block_of_four": (4, 3, [1, 2, 3, 3, 5, 10]),
    "a_block_an_update": (4, 4, [1, 1, 2, 2, 3, 5]),
    "no_width_given_is_a_block": (4, None, [1, 1, 2, 2, 3, 5]),
    "a_width_past_the_block_is_the_block": (4, 8, [1, 1, 2, 2, 3, 5]),
    "four_of_a_block_of_five": (5, 4, [1, 1, 3, 3, 5, 8]),
    "the_tables_width_bounds_the_block": (32, 16, [1, 1, 1, 1, 1, 2]),
}


@pytest.mark.parametrize("case", list(UPDATES))
def test_page_counts_updates_follow_the_kernels_fold(case):
    """`page_counts`' third count is the kernel's own rule: a block's
    live pages go in updates of `fold`, and no update spans two blocks."""
    block, fold, want = UPDATES[case]
    lengths = np.array([1, 4, 6, 7, 11, 20]) * 128 - 3
    for row, n in zip(lengths, want):
        assert pa8.page_counts(row, 128, 20, block=block, fold=fold)[2] == n
    mask = np.array([True, True, False, True, False, True])
    assert pa8.page_counts(lengths, 128, 20, block=block, fold=fold,
                           mask=mask)[2] == sum(
        n for n, m in zip(want, mask) if m)


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
}


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("case", list(WINDOWED))
def test_a_window_rows_start_masks_to_the_token(case, split_kv):
    """paged_attention_int8_window against the gather reference under a
    DENSE mask `starts <= s < lengths`: exact to the token wherever the
    start lies; pages past the row's last are still never touched."""
    lengths, starts, mask = WINDOWED[case]
    lengths, starts = np.asarray(lengths, np.int32), np.asarray(starts,
                                                                np.int32)
    B = len(lengths)
    maxp, block = ((20, 5) if case == "several_blocks"
                   else (12, None) if case.startswith("blocks_")
                   else (4, None))
    pages = B * maxp + 2
    kv, s = _pool(pages, seed=len(case))
    n = np.clip(-(-lengths // PS), 1, maxp)
    live = np.arange(maxp)[None, :] < n[:, None]
    if mask is not None:
        live &= np.asarray(mask)[:, None]
    own = 1 + np.arange(B * maxp).reshape(B, maxp)
    poisoned = jnp.asarray(np.where(live, own, pages - 1), jnp.int32)
    clean = jnp.asarray(np.where(live, own, 0), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(7), (B, H, HD), jnp.float32)
    rows = None if mask is None else pa8.live_rows(jnp.asarray(mask))
    got = np.asarray(pa8.paged_attention_int8_window(
        q, kv, s, poisoned, jnp.asarray(lengths), LAYER, jnp.asarray(starts),
        pages_per_compute_block=block, split_kv=split_kv, interpret=True,
        live=rows))
    assert np.isfinite(got).all(), "a dead page was copied or multiplied"
    want = np.asarray(pa8.paged_attention_int8_reference_fused(
        q, kv[:, LAYER], s[:, LAYER], clean, jnp.asarray(lengths),
        starts=jnp.asarray(starts)))
    served = np.ones(B, bool) if mask is None else np.asarray(mask)
    np.testing.assert_allclose(got[served], want[served], atol=2e-5,
                               rtol=2e-5)
    assert not got[~served].any()
    # the dense mask by hand, one row: softmax over tokens [start, length)
    b = int(np.flatnonzero(served)[-1])
    flat = np.asarray(clean)[b]
    k = (np.asarray(kv[0, LAYER, 0][flat], np.float32)
         * np.asarray(s[0, LAYER, 0][flat])[..., None]).reshape(-1, HD)
    v = (np.asarray(kv[1, LAYER, 0][flat], np.float32)
         * np.asarray(s[1, LAYER, 0][flat])[..., None]).reshape(-1, HD)
    sc = (np.asarray(q[b, 0]) @ k.T) * HD ** -0.5
    keep = (np.arange(len(sc)) >= starts[b]) & (np.arange(len(sc))
                                                < lengths[b])
    p = np.where(keep, np.exp(sc - sc[keep].max()), 0.0)
    np.testing.assert_allclose(got[b, 0], (p / p.sum()) @ v, atol=2e-5,
                               rtol=2e-5)
    # a start of zero is the kernel every other row runs, bit for bit
    if not starts.any():
        plain = np.asarray(pa8.paged_attention_int8(
            q, kv, s, poisoned, jnp.asarray(lengths), LAYER,
            pages_per_compute_block=block, split_kv=split_kv,
            interpret=True, live=rows))
        np.testing.assert_array_equal(plain, got)
    else:  # ... and a start past zero is another answer than none
        plain = np.asarray(pa8.paged_attention_int8_reference_fused(
            q, kv[:, LAYER], s[:, LAYER], clean, jnp.asarray(lengths)))
        assert not np.allclose(plain[served], got[served], atol=1e-3)


# -- one softmax update a BLOCK of pages (PR 45) -----------------------------
# `fold_pages` gives the pages of an update from the score tile's shape;
# whatever width it gives, 1 (a chain a page) to the block's, the kernel
# reads the same to float32 rounding, under every mask it has. `folding`
# is the probes' way to another width than the rule's.
FOLDED = {"q_rep1": (1, None, False), "q_rep4": (4, None, False),
          "tree": (1 + TREE[0] * TREE[1], TREE, False),
          "window": (1, None, True)}


@pytest.mark.parametrize("width", [1, 2, 3])
@pytest.mark.parametrize("form", list(FOLDED))
def test_every_width_the_rule_can_return_reads_the_same(width, form):
    """Rows of 1 to 12 pages (last blocks of every count, a first block
    partly future to the early query rows, a window's start in the first
    page, past it and past the first block) at each width under the
    block's 4, which every other test of this file runs: each is the
    reference to float32 rounding, so all agree."""
    q_rep, tree, window = FOLDED[form]
    maxp = 12
    lengths = np.asarray([1, PS - 1, 2 * PS + 3, 5 * PS, 6 * PS + 1,
                          7 * PS - q_rep, 11 * PS - q_rep - 2,
                          12 * PS - q_rep + 1], np.int32)
    starts = np.asarray([0, 3, PS, PS + 2, 4 * PS + 1, 5, 8 * PS,
                         2 * PS - 1], np.int32)
    B = len(lengths)
    pages = B * maxp + 2
    kv, s = _pool(pages, seed=q_rep)
    n = np.clip(-(-(lengths + q_rep - 1) // PS), 1, maxp)
    live = np.arange(maxp)[None, :] < n[:, None]
    own = 1 + np.arange(B * maxp).reshape(B, maxp)
    poisoned = jnp.asarray(np.where(live, own, pages - 1), jnp.int32)
    clean = jnp.asarray(np.where(live, own, 0), jnp.int32)
    shape = (B, H, HD) if q_rep == 1 else (B, q_rep, H, HD)
    q = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32)
    with folding(pa8, width):
        if window:
            got = pa8.paged_attention_int8_window(
                q, kv, s, poisoned, jnp.asarray(lengths), LAYER,
                jnp.asarray(starts), interpret=True)
        else:
            got = pa8.paged_attention_int8(
                q, kv, s, poisoned, jnp.asarray(lengths), LAYER, q_rep=q_rep,
                tree=tree, interpret=True)
    if window:
        want = pa8.paged_attention_int8_reference_fused(
            q, kv[:, LAYER], s[:, LAYER], clean, jnp.asarray(lengths),
            starts=jnp.asarray(starts))
    else:
        want = _reference(q, kv, s, clean, jnp.asarray(lengths), q_rep, tree)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("width", [1, 3, 4])
def test_the_hosts_update_count_is_the_folds_the_kernel_runs(
        width, monkeypatch):
    """`page_counts`' updates (the engine's decode_attn_updates) against
    the interpreted kernel on the same lengths and mask: every fold that
    runs says how many pages it took."""
    ran = []
    fold = pa8._fold_block

    def counted(q, page, count, carry):
        jax.debug.callback(lambda: ran.append(count))
        return fold(q, page, count, carry)

    monkeypatch.setattr(pa8, "_fold_block", counted)
    maxp = 12
    lengths = np.asarray([1, 3 * PS, 4 * PS + 1, 7 * PS, 1, 12 * PS,
                          10 * PS - 1], np.int32)
    B = len(lengths)
    kv, s = _pool(B * maxp + 2, seed=width)
    table = jnp.asarray(1 + np.arange(B * maxp).reshape(B, maxp), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, HD), jnp.float32)
    with folding(pa8, width):  # nothing traced in here outlives it
        for mask in ([True] * B, [True, False, True, True, False, True, True]):
            ran.clear()
            jax.block_until_ready(pa8.paged_attention_int8(
                q, kv, s, table, jnp.asarray(lengths), LAYER, interpret=True,
                live=pa8.live_rows(jnp.asarray(mask))))
            jax.effects_barrier()
            pages, _, updates = pa8.page_counts(
                lengths, PS, maxp, mask=np.asarray(mask), fold=width)
            assert (sum(ran), len(ran)) == (pages, updates)
            assert max(ran) <= width


def test_the_rule_is_a_width_the_block_can_hold():
    """`fold_pages` for every tile a cell has (KV heads, query heads a KV
    head) and the speculative forms' larger groups: 1 .. the block's."""
    for kv_heads, group in [(4, 7), (4, 8), (8, 4), (2, 4), (16, 1),
                            (8, 16), (8, 20), (2, 2)]:
        for ppcb in (1, 2, 4, 5, 8):
            assert 1 <= pa8.fold_pages(kv_heads, group, ppcb) <= ppcb


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


@pytest.mark.parametrize("case", list(APPENDED))
def test_the_call_that_writes_the_new_row_leaves_the_two_calls_bytes(case):
    from generativeaiexamples_tpu.serving.kv_append_int8 import kv_append_int8

    maxp, block, lengths, mask, split_kv = APPENDED[case]
    (q, kv, s, table, lens, live, codes, scales, page_idx,
     offset) = _step_with_a_new_row(lengths, maxp, mask, seed=len(case))
    kw = dict(pages_per_compute_block=block, split_kv=split_kv,
              interpret=True, live=live)
    kv_2, s_2 = kv_append_int8(kv, s, LAYER, page_idx, offset, codes, scales,
                               live, interpret=True, split_kv=split_kv)
    want = pa8.paged_attention_int8(q, kv_2, s_2, table, lens, LAYER, **kw)
    got, kv_1, s_1 = pa8.paged_attention_int8(
        q, kv, s, table, lens, LAYER, new=(codes, scales), **kw)
    np.testing.assert_array_equal(np.asarray(kv_1), np.asarray(kv_2))
    np.testing.assert_array_equal(np.asarray(s_1), np.asarray(s_2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the sink page and every other layer's rows are what they were
    for after, before in ((kv_1, kv), (s_1, s)):
        after, before = np.asarray(after), np.asarray(before)
        np.testing.assert_array_equal(after[:, :, :, 0], before[:, :, :, 0])
        np.testing.assert_array_equal(after[:, 1 - LAYER], before[:, 1 - LAYER])
    served = np.ones(len(lengths), bool) if mask is None else np.asarray(mask)
    touched = np.argwhere(np.asarray(kv_1) != np.asarray(kv))
    assert set(touched[:, 3]) <= set(np.asarray(page_idx)[served].tolist())
    assert served.any() == bool(len(touched))
    # the new row is IN what the live rows attended: without it they
    # read otherwise
    if served.any():
        stale = pa8.paged_attention_int8(q, kv, s, table, lens, LAYER, **kw)
        assert not np.array_equal(np.asarray(stale)[served],
                                  np.asarray(got)[served])


def test_without_the_new_row_the_call_is_the_one_it_was():
    """No `starts`, no `new`: ONE array comes back, the reference's over
    a pool that holds the row already, and the call aliases nothing (the
    pool comes back from the other variant alone)."""
    (q, kv, s, table, lens, _, _, _, _, _) = _step_with_a_new_row(
        [5, APS + 1, 3 * APS], 4, None)
    out = pa8.paged_attention_int8(q, kv, s, table, lens, LAYER,
                                   interpret=True)
    assert isinstance(out, jax.Array) and out.shape == q.shape
    want = pa8.paged_attention_int8_reference_fused(
        q, kv[:, LAYER], s[:, LAYER], table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    def traced(**kw):  # not interpreted: the call is one equation
        return str(jax.make_jaxpr(lambda *a: pa8.paged_attention_int8(
            *a, LAYER, **kw))(q, kv, s, table, lens))

    assert "input_output_aliases=()" in traced()
    assert "input_output_aliases=((8, 1), (9, 2))" in traced(
        new=(jnp.zeros((2, AKH, 3, AHD), jnp.int8), jnp.zeros((2, AKH, 3))))
    # a verify's and a window row's calls take no new row
    with pytest.raises(AssertionError, match="one new row a slot"):
        pa8.paged_attention_int8(
            jnp.zeros((3, 2, AH, AHD)), kv, s, table, lens, LAYER, q_rep=2,
            interpret=True, new=(jnp.zeros((2, AKH, 3, AHD), jnp.int8),
                                 jnp.zeros((2, AKH, 3))))


@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
def test_the_new_row_over_four_virtual_devices(masked):
    """Under a tensor-parallel mesh, through the dispatch's shard_map on
    the kv heads (one a device): the pool and the output of the pool's
    own append under the same mesh and then the dispatch without the
    row."""
    from jax.sharding import Mesh

    from generativeaiexamples_tpu.serving.kv_cache import QuantPagePool
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_dispatch)
    from test_kv_append_kernel import interpreted

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("tensor",))
    mask = [True, False, True, True] if masked else None
    (q, kv, s, table, lens, live, codes, scales, page_idx,
     offset) = _step_with_a_new_row([13, 1, 2 * APS, APS + 32], 4, mask,
                                    kv_heads=4)

    def attend(kv, s, new=None):
        return paged_attention_dispatch(
            q, kv, None, table, lens, k_scales=s, layer=LAYER,
            use_pallas=True, mesh=mesh, live=live, new=new)

    with interpreted():
        two = QuantPagePool(kv, s, APS)._append_kernel(
            LAYER, page_idx, offset, mesh, codes, scales, live)
        want = attend(two.kv, two.s)
        got, kv_1, s_1 = attend(kv, s, (codes, scales))
    np.testing.assert_array_equal(np.asarray(kv_1), np.asarray(two.kv))
    np.testing.assert_array_equal(np.asarray(s_1), np.asarray(two.s))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(kv_1), np.asarray(kv))
