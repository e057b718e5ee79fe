"""The int8 paged-attention kernel's three later forms
(serving/paged_attention_int8.py): a WINDOW row's start (PR 44), one
softmax update a BLOCK of pages (PR 45), and the step's new row written
by the attention call (PR 46). Tables, pools and `_step_with_a_new_row`
are tests/paged_int8_cases.py's (read its head before adding a case).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8
from paged_int8_cases import (
    AH, AHD, AKH, APPENDED, APS, FOLDED, H, HD, LAYER, PS, WINDOWED, _pool,
    _reference, _step_with_a_new_row)
from scripts.measure_paged_attention import folding


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
            pages, _, updates, _ = pa8.page_counts(
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


# a page's bytes at the cells' shapes (KV heads of 128 x 128 int8 codes and
# float32 scales, four pages a block) -> the blocks in flight
DEPTHS = {"68_KB_a_chip_of_tp4": (2, 128, 128, 4, 4),
          "135_KB_smallthinker": (4, 128, 128, 4, 4),
          "270_KB_mistral_7b": (8, 128, 128, 4, 3),
          "541_KB_ouro": (16, 128, 128, 4, 2),
          "34_KB_one_kv_head": (1, 128, 128, 4, pa8.MAX_BLOCKS_AHEAD),
          "a_table_of_one_page": (8, 128, 128, 1, pa8.MAX_BLOCKS_AHEAD),
          "the_tests_tiny_pages": (2, 8, 16, 4, pa8.MAX_BLOCKS_AHEAD),
          "1_MB_32_kv_heads": (32, 128, 128, 4, pa8.BLOCKS_AHEAD)}


@pytest.mark.parametrize("case", list(DEPTHS))
def test_the_look_ahead_keeps_three_megabytes_in_flight(case):
    """`blocks_ahead`, a pure function of a block's bytes: what its
    docstring states for the cells' four page sizes, and its two bounds."""
    kv_heads, ps, hd, ppcb, want = DEPTHS[case]
    got = pa8.blocks_ahead(kv_heads, ps, hd, ppcb)
    assert got == want
    block = ppcb * 2 * kv_heads * ps * (hd + 4)
    assert pa8.BLOCKS_AHEAD <= got <= pa8.MAX_BLOCKS_AHEAD
    # the buffers (one more): never over what is in flight and two blocks,
    # or the three blocks a large page always had
    assert (got + 1) * block <= max(pa8.BYTES_IN_FLIGHT + 2 * block,
                                    (pa8.BLOCKS_AHEAD + 1) * block)
    if pa8.BLOCKS_AHEAD < got < pa8.MAX_BLOCKS_AHEAD:
        assert (got - 1) * block < pa8.BYTES_IN_FLIGHT <= got * block


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
