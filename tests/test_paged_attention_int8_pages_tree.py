"""The int8 paged-attention kernel's page and idle-row tests
(tests/paged_int8_cases.py's bodies; tests/test_paged_attention_int8_pages.py
says what they hold) under the TREE form; a decode batch of 64 slots as the
cells send it; `live_rows` and `page_counts` by hand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8
from paged_int8_cases import (
    H, HD, LAYER, LENGTHS, MASKED, PS, _pool, _reference,
    a_page_past_a_rows_last_is_neither_copied_nor_multiplied,
    an_idle_row_is_never_asked_for_and_reads_zeros)


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", ["tree"])
@pytest.mark.parametrize("case", list(LENGTHS))
def test_a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv):
    a_page_past_a_rows_last_is_neither_copied_nor_multiplied(
        case, form, split_kv)


@pytest.mark.parametrize("split_kv", [False, True])
@pytest.mark.parametrize("form", ["tree"])
@pytest.mark.parametrize("case", list(MASKED))
def test_an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv):
    an_idle_row_is_never_asked_for_and_reads_zeros(case, form, split_kv)


@pytest.mark.parametrize("case", ["walk_of_5_of_12"])
def test_the_walk_serves_the_live_rows_alone(case):
    """tests/test_paged_attention_int8_pages.py's, in this file's form at
    one count: five live rows' eight blocks through five buffers."""
    an_idle_row_is_never_asked_for_and_reads_zeros(case, "tree", False)


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
    """What the engine's four counters add a step: the rows' pages, what
    whole blocks over the same rows cover, the softmax updates the
    kernel folds the rows' pages into, and the calls' grid steps: one a
    live row, and one for a call (a row of the lengths' last axis) with
    nobody live."""
    lengths = np.array([1, 128, 129, 0, 640, 2560, 4000], np.int32)
    live, walked, updates, grid_steps = pa8.page_counts(
        lengths, page_size=128, max_pages=20, block=5)
    assert (live, walked) == (1 + 1 + 2 + 1 + 5 + 20 + 20,
                              5 + 5 + 5 + 5 + 5 + 20 + 20)
    assert updates == 1 + 1 + 1 + 1 + 1 + 4 + 4  # a block an update
    assert grid_steps == 7  # no mask: every row is live
    # a block that does not divide the table's width stops at the width
    assert pa8.page_counts(lengths[-1:], 128, 20, block=8) == (20, 20, 3, 1)
    assert pa8.page_counts(lengths[:0], 128, 20, block=8) == (0, 0, 0, 1)
    # with the step's mask an idle row has no page to copy; what whole
    # blocks over every row covered is the walk it is compared with
    mask = np.array([True, False, True, False, True, False, True])
    assert pa8.page_counts(lengths, 128, 20, block=5, mask=mask) == (
        1 + 2 + 5 + 20, walked, 1 + 1 + 1 + 4, 4)
    # ... and with nobody live the call still makes one grid step
    assert pa8.page_counts(lengths, 128, 20, block=5,
                           mask=np.zeros(7, bool)) == (0, walked, 0, 1)
    # a block of K steps: [K, B] lengths against the [B] mask
    assert pa8.page_counts(np.stack([lengths, lengths + 1]), 128, 20,
                           block=5, mask=mask) == (
        (1 + 2 + 5 + 20) + (1 + 2 + 6 + 20), 2 * walked + 5,
        (1 + 1 + 1 + 4) + (1 + 1 + 2 + 4), 4 + 4)
    # eight steps of three cache rows each: twenty-four calls of 7 rows,
    # of 4 live ones, and of none
    steps = np.ones((8, 3, 7), np.int32)
    assert pa8.page_counts(steps, 128, 20)[3] == 24 * 7
    assert pa8.page_counts(steps, 128, 20, mask=mask)[3] == 24 * 4
    assert pa8.page_counts(steps, 128, 20, mask=~mask | mask)[3] == 24 * 7
    assert pa8.page_counts(steps, 128, 20, mask=mask & ~mask)[3] == 24


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
