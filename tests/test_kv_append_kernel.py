"""The int8 pool's append as a Pallas kernel (serving/kv_append_int8.py)
against XLA's scatters, the reference form that every program lowered off
the chip keeps (kv_cache.QuantPagePool.append): the kernel interpreted on
the CPU, the pools compared BYTE FOR BYTE. Pools start from random
contents, so a tile written back to another place, a neighbour touched or
a stale read of a tile fails the comparison. What interpret mode cannot
see (tiling, VMEM, aliasing without a copy) is tests/test_chip_compile.py's.

The interpreter is Pallas's plain one (`interpret=True`: the kernel's body
as JAX operations inside the calling computation). The TPU interpret mode
(`pltpu.force_tpu_interpret_mode`) runs a kernel through host callbacks
that dispatch JAX operations of their own, and deadlocks when another
thread dispatches to the device meanwhile (the test's next line, or an
engine's scheduler): under `-n 6` it hung this file in every whole run.
"""

import contextlib
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from generativeaiexamples_tpu.serving import kv_cache
from generativeaiexamples_tpu.serving.kv_append_int8 import kv_append_int8
from generativeaiexamples_tpu.serving.kv_cache import (
    PagePool, QuantPagePool, kernel_append, kernel_live_rows, token_slots)

R, PS, HD = 3, 128, 128
EDGES = (0, 31, 32, 127)  # first and last row of a tile, of a page


def _pool(kv_heads, pages, seed=0, rows=R):
    rng = np.random.default_rng(seed)
    kv = rng.integers(-127, 128, (2, rows, kv_heads, pages, PS, HD),
                      dtype=np.int8)
    s = rng.random((2, rows, kv_heads, pages, PS), dtype=np.float32)
    return QuantPagePool(jnp.asarray(kv), jnp.asarray(s), PS)


def _new_rows(kv_heads, slots, seed=1):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(x, jnp.bfloat16) for x in
                 rng.standard_normal((2, kv_heads, slots, HD)) * 3)


def _same(got, want):
    np.testing.assert_array_equal(np.asarray(got.kv), np.asarray(want.kv))
    np.testing.assert_array_equal(np.asarray(got.s), np.asarray(want.s))


@contextlib.contextmanager
def interpreted():
    """Every `pl.pallas_call` made inside, by whatever module and in
    whatever thread, is interpreted: the served path has no `interpret`
    argument to hand down (on the chip nothing is interpreted)."""
    call = pl.pallas_call

    @functools.wraps(call)
    def interpreted_call(*args, **kwargs):
        return call(*args, **{**kwargs, "interpret": True})

    with mock.patch.object(pl, "pallas_call", interpreted_call):
        yield


def _both(pool, row, page_idx, offset, k_new, v_new, active=None):
    """(the kernel's pool, the scatters' pool) for one append; `active`:
    the step's mask, which the kernel's form alone reads."""
    KH = pool.geometry.kv_heads
    page_idx, offset = jnp.asarray(page_idx), jnp.asarray(offset)
    if active is not None:
        active = jnp.asarray(active)
    with interpreted():
        live = kernel_live_rows(pool, active, True)
        got = pool.append(row, token_slots(KH, page_idx, offset, True,
                                           live=live), k_new, v_new)
    want = pool.append(row, token_slots(KH, page_idx, offset, False),
                       k_new, v_new)
    return got, want


@pytest.mark.parametrize("kv_heads,slots", [(8, 64), (16, 32), (2, 64)])
def test_kernel_writes_the_bytes_the_scatters_write(kv_heads, slots):
    """The three shapes the cells run, every slot on a page of its own,
    the offsets all over a page and the tile edges among them."""
    rng = np.random.default_rng(kv_heads)
    pool = _pool(kv_heads, slots + 1)
    page_idx = 1 + rng.permutation(slots)
    offset = rng.integers(0, PS, slots)
    offset[:4] = EDGES
    got, want = _both(pool, 1, page_idx, offset, *_new_rows(kv_heads, slots))
    _same(got, want)
    assert not np.array_equal(np.asarray(got.kv), np.asarray(pool.kv))


@pytest.mark.parametrize("offset", EDGES)
def test_kernel_at_the_edges_of_a_tile_and_a_page(offset):
    pool = _pool(2, 4)
    got, want = _both(pool, 0, [2, 1, 3], [offset] * 3, *_new_rows(2, 3))
    _same(got, want)
    touched = np.argwhere(np.asarray(got.kv) != np.asarray(pool.kv))
    assert set(touched[:, 4]) == {offset} and set(touched[:, 1]) == {0}


def test_a_second_append_reads_the_tile_the_first_one_wrote():
    """Two steps into the same tile: the second call's read-modify-write
    keeps the first call's row."""
    pool = _pool(2, 3)
    k1, v1 = _new_rows(2, 2, seed=1)
    k2, v2 = _new_rows(2, 2, seed=2)
    got, want = _both(pool, 2, [1, 2], [5, 63], k1, v1)
    got2, _ = _both(got, 2, [1, 2], [6, 64], k2, v2)
    _, want2 = _both(want, 2, [1, 2], [6, 64], k2, v2)
    _same(got2, want2)
    np.testing.assert_array_equal(np.asarray(got2.kv)[:, 2, :, 1, 5],
                                  np.asarray(got.kv)[:, 2, :, 1, 5])


def test_a_traced_row_inside_a_fori_loop():
    """The looped walk's: the row is the loop's counter, and the pool
    its carry."""
    KH, B = 2, 3
    pool = _pool(KH, 4, rows=4)
    page_idx, offset = jnp.asarray([3, 1, 2]), jnp.asarray([127, 0, 40])
    k_new, v_new = _new_rows(KH, B)

    def walk(pool, use_pallas):
        slots = token_slots(KH, page_idx, offset, use_pallas)
        return jax.lax.fori_loop(
            1, 4, lambda row, p: p.append(
                row, slots, k_new * row.astype(jnp.bfloat16), v_new), pool)

    with interpreted():
        got = jax.jit(walk, static_argnums=1)(pool, True)
    want = jax.jit(walk, static_argnums=1)(pool, False)
    _same(got, want)
    np.testing.assert_array_equal(np.asarray(got.kv)[:, 0],
                                  np.asarray(pool.kv)[:, 0])


@pytest.mark.parametrize("split_kv", [False, True])
def test_split_descriptors_write_the_same(split_kv):
    """One descriptor for a tile's K and V, or one each (a pool whose
    half reaches SPLIT_KV_BYTES: chosen from the shape, forced here)."""
    KH, B = 2, 3
    pool = _pool(KH, 4)
    page_idx, offset = jnp.asarray([1, 3, 2]), jnp.asarray([33, 95, 0])
    k_new, v_new = _new_rows(KH, B)
    want = pool.append(1, token_slots(KH, page_idx, offset, False),
                       k_new, v_new)
    (kq, ks), (vq, vs) = pool._quantize(k_new), pool._quantize(v_new)
    kv, s = kv_append_int8(pool.kv, pool.s, 1, page_idx, offset,
                           jnp.stack([kq, vq]), jnp.stack([ks, vs]),
                           interpret=True, split_kv=split_kv)
    _same(QuantPagePool(kv, s, PS), want)


def test_inactive_slots_share_the_sink_page_and_harm_no_one():
    """As the engine sends them where a program hands the kernel no mask
    (the fused rider lane, the spec-state lane): inactive slots at (page
    0, offset 0). Their tiles race among themselves; every live slot's
    bytes are the scatters', and the sink holds one of the inactive
    slots' rows."""
    KH, B = 2, 6
    pool = _pool(KH, 4)
    page_idx, offset = [2, 0, 0, 3, 0, 1], [17, 0, 0, 127, 0, 32]
    k_new, v_new = _new_rows(KH, B)
    got, want = _both(pool, 1, page_idx, offset, k_new, v_new)
    g_kv, w_kv = np.array(got.kv), np.array(want.kv)
    g_s, w_s = np.array(got.s), np.array(want.s)
    kq = np.asarray(pool._quantize(k_new)[0])  # [KH, B, Hd]
    assert any(np.array_equal(g_kv[0, 1, :, 0, 0], kq[:, b])
               for b in (1, 2, 4))
    g_kv[:, 1, :, 0, 0] = w_kv[:, 1, :, 0, 0]
    g_s[:, 1, :, 0, 0] = w_s[:, 1, :, 0, 0]
    np.testing.assert_array_equal(g_kv, w_kv)
    np.testing.assert_array_equal(g_s, w_s)


def test_inactive_slots_write_nothing():
    """As the engine sends them: inactive slots at (page 0, offset 0),
    `active` False. The sink page holds its pattern before and after;
    every live slot's bytes are the scatters'."""
    KH, B = 2, 6
    pool = _pool(KH, 4)
    page_idx, offset = [2, 0, 0, 3, 0, 1], [17, 0, 0, 127, 0, 32]
    active = [True, False, False, True, False, True]
    k_new, v_new = _new_rows(KH, B)
    got, want = _both(pool, 1, page_idx, offset, k_new, v_new, active)
    g_kv, w_kv = np.array(got.kv), np.array(want.kv)
    g_s, w_s = np.array(got.s), np.array(want.s)
    np.testing.assert_array_equal(g_kv[:, :, :, 0],
                                  np.asarray(pool.kv)[:, :, :, 0])
    np.testing.assert_array_equal(g_s[:, :, :, 0],
                                  np.asarray(pool.s)[:, :, :, 0])
    # the scatters did leave an idle slot's row there
    assert not np.array_equal(w_kv[:, 1, :, 0, 0],
                              np.asarray(pool.kv)[:, 1, :, 0, 0])
    np.testing.assert_array_equal(g_kv[:, :, :, 1:], w_kv[:, :, :, 1:])
    np.testing.assert_array_equal(g_s[:, :, :, 1:], w_s[:, :, :, 1:])


@pytest.mark.parametrize("active", [
    [False] * 5, [False, False, False, False, True],
    [True, False, True, True, False], [True] * 5])
def test_the_kernel_writes_the_live_slots_rows_and_no_other(active):
    """Nobody live, the last slot alone, a mix, everyone: an idle slot's
    tile is untouched even where its page is a real one (a slot the
    reaper starved keeps its table row), and with everyone live the mask
    changes nothing."""
    KH, B = 2, 5
    pool = _pool(KH, 7)
    page_idx, offset = [1, 2, 3, 4, 5], [0, 31, 32, 127, 64]
    k_new, v_new = _new_rows(KH, B)
    got, _ = _both(pool, 2, page_idx, offset, k_new, v_new, active)
    live = [b for b in range(B) if active[b]]
    if live:
        _, want = _both(pool, 2, [page_idx[b] for b in live],
                        [offset[b] for b in live], k_new[:, live],
                        v_new[:, live])
    else:
        want = pool
    _same(got, want)
    if all(active):
        _same(got, _both(pool, 2, page_idx, offset, k_new, v_new)[0])


# a decode batch of 64 slots as the cells send it: name -> live slots
LIVE_OF_64 = {"3_of_64": 3, "60_of_64": 60, "1_of_64": 1, "all_64": 64}


def live_of_64(case):
    """The case's mask [64]: its live slots scattered among the idle
    ones, never a prefix of the batch (slot 0 is idle wherever one is)."""
    n = LIVE_OF_64[case]
    rng = np.random.default_rng(n)
    active = np.zeros((64,), bool)
    active[1 + rng.permutation(63)[:n]] = True
    if n == 64:
        active[:] = True
    assert active.sum() == n and (n == 64 or not active[0])
    return active


@pytest.mark.parametrize("case", list(LIVE_OF_64))
def test_a_batch_of_64_writes_its_live_slots_tiles_and_leaves_the_rest(case):
    """3, 60, 1 and all of 64 slots live, scattered: the pool is byte for
    byte what the scatters leave when they are given the live slots alone
    (so every other page, the sink among them, is UNTOUCHED), and with no
    mask the kernel writes what it writes with everyone live."""
    KH, B = 2, 64
    active = live_of_64(case)
    pool = _pool(KH, B + 1)
    rng = np.random.default_rng(B)
    # as the engine sends an idle slot: page 0, offset 0
    page_idx = np.where(active, 1 + rng.permutation(B), 0)
    offset = np.where(active, rng.integers(0, PS, B), 0)
    k_new, v_new = _new_rows(KH, B)
    got, _ = _both(pool, 1, page_idx, offset, k_new, v_new, active)
    live = np.flatnonzero(active)
    _, want = _both(pool, 1, page_idx[live], offset[live], k_new[:, live],
                    v_new[:, live])
    _same(got, want)
    np.testing.assert_array_equal(np.asarray(got.kv)[:, :, :, 0],
                                  np.asarray(pool.kv)[:, :, :, 0])
    assert not np.array_equal(np.asarray(got.kv), np.asarray(pool.kv))
    if active.all():  # `active=None` is everyone live
        _same(got, _both(pool, 1, page_idx, offset, k_new, v_new)[0])


def test_slots_of_rank_two_keep_the_scatters(monkeypatch):
    """A verify's r rows a slot share a tile: they never reach the
    kernel, whatever use_pallas says; nor does a bf16 pool, nor a page
    the kernel's DMAs cannot tile."""
    def no_kernel(*a, **k):
        raise AssertionError("the kernel was reached")

    monkeypatch.setattr(QuantPagePool, "_append_kernel", no_kernel)
    KH, B, r = 2, 2, 3
    pool = _pool(KH, 4)
    page_idx = jnp.asarray([[1, 1, 1], [2, 2, 3]])
    offset = jnp.asarray([[4, 5, 6], [126, 127, 0]])
    rng = np.random.default_rng(3)
    k_new, v_new = (jnp.asarray(x, jnp.bfloat16) for x in
                    rng.standard_normal((2, KH, B, r, HD)))
    got = pool.append(0, token_slots(KH, page_idx, offset, True),
                      k_new, v_new)
    _same(got, pool.append(0, token_slots(KH, page_idx, offset, False),
                           k_new, v_new))
    assert not kernel_append(pool, True, rank=2)
    assert kernel_append(pool, True) and not kernel_append(pool, False)
    assert not kernel_append(pool, None)  # no TPU here
    bf16 = PagePool(jnp.zeros((R, KH, 4, PS, HD), jnp.bfloat16),
                    jnp.zeros((R, KH, 4, PS, HD), jnp.bfloat16), PS)
    assert not kernel_append(bf16, True)
    said = []
    monkeypatch.setattr(kv_cache, "log_kernel_declined",
                        lambda *a: said.append(a))
    small = QuantPagePool(jnp.zeros((2, R, KH, 4, 16, HD), jnp.int8),
                          jnp.zeros((2, R, KH, 4, 16), jnp.float32), 16)
    assert not kernel_append(small, True)
    assert said and said[0][0] == "kv_append_int8"


# -- through the step program and the engine -------------------------------

def _tiny(looped=False):
    import dataclasses

    from generativeaiexamples_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=96, dim=256, n_layers=2, n_heads=2, n_kv_heads=2,
        head_dim=128, mlp_dim=128, max_seq_len=256, dtype=jnp.float32)
    if looped:
        cfg = dataclasses.replace(cfg, n_passes=2, post_norms=True)
    return cfg


@pytest.mark.parametrize("looped", [False, True])
def test_decode_multi_step_with_the_kernel_gives_the_scatters_tokens_and_pool(
        looped, monkeypatch):
    """One tiny int8 `decode_multi_step` with kernels on (interpreted),
    its new row written by the append's kernel or, in the looped walk, by
    the attention call itself (PR 46), against the same program made to
    keep the scatters: the same tokens, and the same pool byte for byte outside
    the sink page. (Against the program the CPU serves the tokens are
    the same too; its attention is the XLA reference, whose rounding
    moves a later layer's scales by an ulp.) The batch is mostly idle,
    as the open cells' is: six slots of eight are nobody's, their table
    rows zeros; the kernels walk the two live ones, the sink page keeps
    its bytes, and a live slot's tokens are those of the program that
    computes every slot."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import engine_model as em

    cfg = _tiny(looped)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, maxp, K = 8, 2, 3
    tables = np.zeros((B, maxp), np.int32)
    tables[1], tables[6] = (1, 2), (5, 6)
    lengths = np.ones((B,), np.int32)
    lengths[1], lengths[6] = 127, 32
    active = np.zeros((B,), bool)
    active[[1, 6]] = True
    first = np.zeros((B,), np.int32)
    first[1], first[6] = 3, 27

    def run(use_pallas):
        rng = np.random.default_rng(0)
        shape = (2, cfg.cache_rows, cfg.n_kv_heads, 7, PS, HD)
        pool = QuantPagePool(
            jnp.asarray(rng.integers(-127, 128, shape, np.int8)),
            jnp.asarray(rng.random(shape[:-1], np.float32) * 0.02), PS)
        step = jax.jit(em.decode_multi_step.__wrapped__, static_argnames=(
            "cfg", "n_steps", "use_pallas", "sampling_flags"))  # traced anew
        with interpreted():
            return pool, step(
                params, cfg, pool, jnp.asarray(first),
                jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(active), jnp.zeros((B,), jnp.float32),
                jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                jax.random.PRNGKey(1), K, use_pallas,
                sampling_flags=(True, False, False))

    pool_0, (block_k, last_k, pool_k) = run(True)
    pool_0 = jax.tree.map(np.asarray, pool_0)  # before it is donated
    # a decode step writes nothing to the sink page
    np.testing.assert_array_equal(np.asarray(pool_k.kv)[:, :, :, 0],
                                  pool_0.kv[:, :, :, 0])
    np.testing.assert_array_equal(np.asarray(pool_k.s)[:, :, :, 0],
                                  pool_0.s[:, :, :, 0])
    # every slot computed, by the scatters and the unmasked attention
    # kernel: the parent's program
    assert em.fuses_append(cfg, pool_k, True) == looped
    monkeypatch.setattr(kv_cache, "kernel_append", lambda *a, **k: False)
    monkeypatch.setattr(em, "kernel_append", lambda *a, **k: False)
    _, (block_s, last_s, pool_s) = run(True)
    np.testing.assert_array_equal(np.asarray(block_k)[active],
                                  np.asarray(block_s)[active])
    np.testing.assert_array_equal(np.asarray(last_k)[active],
                                  np.asarray(last_s)[active])
    # an idle slot's token stands still in both
    np.testing.assert_array_equal(np.asarray(block_k)[~active],
                                  np.asarray(block_s)[~active])
    np.testing.assert_array_equal(np.asarray(pool_k.kv)[:, :, :, 1:],
                                  np.asarray(pool_s.kv)[:, :, :, 1:])
    np.testing.assert_array_equal(np.asarray(pool_k.s)[:, :, :, 1:],
                                  np.asarray(pool_s.s)[:, :, :, 1:])
    _, (block_cpu, _, _) = run(False)  # the program the CPU serves
    np.testing.assert_array_equal(np.asarray(block_k), np.asarray(block_cpu))


def _drive_inline(eng, reqs, max_iters=400):
    """The scheduler's loop body on this thread (tests/test_flight.py)."""
    for r in reqs:
        eng.submit(r)
    for _ in range(max_iters):
        eng._admit_waiting()
        eng._advance_long_prefills()
        eng._emit_ready_first_tokens()
        while (len(eng._inflight) < eng.pipeline_depth
               and any(s is not None for s in eng.slots)):
            if not eng._dispatch_decode():
                break
        if eng._inflight:
            eng._land_next_block()
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._inflight and not eng._pending_first):
            break
    eng._emit_ready_first_tokens()


def test_an_engine_with_idle_slots_streams_the_tokens_the_parent_streamed(
        monkeypatch):
    """An int8 engine of four slots driven inline with kernels on
    (interpreted): three requests of unequal lengths, so that a block
    holds three, two, one live slots and the fourth is never anyone's.
    Every stream is byte for byte that of the engine whose programs are
    given no live list (the parent's walk: every slot computed, an idle
    one on the sink page), and `decode_attn_rows_skipped` counts (B - live
    slots) x K a block: every row a step less the busy ones."""
    import queue

    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = _tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B = 4
    ecfg = EngineConfig(max_batch_size=B, max_seq_len=256, page_size=PS,
                        kv_dtype="int8", prefill_buckets=(128,),
                        decode_steps_per_dispatch=2,
                        pace_emission_max_streams=0)

    def streams():
        jax.clear_caches()  # the step programs are traced anew
        reqs = [GenRequest(prompt_ids=ids, max_new_tokens=n)
                for ids, n in (([5, 6, 7, 8], 9), (list(range(9, 40)), 4),
                               ([3] * 126, 7))]
        with interpreted():
            eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                            use_pallas=True)
            assert eng.metrics.snapshot()["decode_attn_rows_skipped"] == 0
            _drive_inline(eng, reqs)
        out = []
        for r in reqs:
            out.append([])
            while True:
                try:
                    ev = r.stream.get_nowait()
                except queue.Empty:
                    break
                if ev["token_id"] >= 0:
                    out[-1].append(ev["token_id"])
        return out, eng.metrics

    from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8

    made, live_rows = [], pa8.live_rows
    monkeypatch.setattr(pa8, "live_rows",
                        lambda mask: made.append(1) or live_rows(mask))
    got, metrics = streams()
    assert made, "the decode programs were traced with the live list"
    assert [len(t) for t in got] == [9, 4, 7]
    snap, steps = metrics.snapshot(), metrics.decode_steps
    assert snap["decode_steps_kernel_append"] == steps > 0
    assert snap["decode_steps_fused_append"] == 0  # a one-pass model
    assert snap["decode_attn_rows_skipped"] == (
        steps * B - metrics.busy_slots_acc) > steps  # over a row a step idle
    monkeypatch.setattr(em, "kernel_live_rows", lambda *a, **k: None)
    del made[:]
    want, _ = streams()
    assert got == want and not made
    jax.clear_caches()


PLANS = {"plain": dict(decode_k=2),
         "rider": dict(decode_k=2, rider_width=8, rider_s_total=32),
         "spec_state": dict(decode_k=2, spec_state=True),
         "verify": dict(decode_k=2, spec_k=3)}


@pytest.mark.parametrize("name", list(PLANS))
def test_the_engines_counter_and_the_programs_agree_on_who_gets_the_mask(
        name, monkeypatch):
    """`engine_model.masks_pool_kernels(plan)`, which the engine's
    `decode_attn_rows_skipped` reads, against what the program that
    `_plan_step` lowers the plan to does: whether its decode body asks
    `kernel_live_rows` for a list with a mask in hand."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import engine_model as em

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    plan = em.StepPlan(**PLANS[name])
    B, maxp = 4, 4
    asked = []
    monkeypatch.setattr(
        em, "kernel_live_rows",
        lambda pool, active, use_pallas: asked.append(active is not None))
    kw = dict(pool=PagePool.zeros(cfg, 9, 16, dtype=jnp.int8),
              last_tokens=jnp.zeros((B,), jnp.int32),
              page_tables=jnp.zeros((B, maxp), jnp.int32),
              active=jnp.ones((B,), bool), use_pallas=False)
    if plan.spec_k or plan.spec_state:
        kw.update(history=jnp.zeros((B, 32), jnp.int32),
                  dev_lengths=jnp.ones((B,), jnp.int32))
    if not plan.spec_k:
        kw.update(lengths=jnp.ones((B,), jnp.int32),
                  temperature=jnp.zeros((B,), jnp.float32),
                  top_p=jnp.ones((B,), jnp.float32),
                  top_k=jnp.zeros((B,), jnp.int32),
                  rng=jax.random.PRNGKey(1))
    if plan.rider_width:
        kw.update(cache=llama.KVCache.zeros(cfg, 1, max_len=32),
                  chunk_tokens=jnp.zeros((1, 8), jnp.int32),
                  chunk_valid=jnp.int32(5))
    jax.clear_caches()  # the program is traced anew, with the probe in it
    em._plan_step(params, cfg, plan, **kw)
    jax.clear_caches()
    assert any(asked) == em.masks_pool_kernels(plan) == (name == "plain")
    # a body that has no mask still asks, and gets every slot
    assert asked or name == "verify"


def test_a_pool_without_the_two_kernels_gets_no_live_list():
    """The list is made where the int8 pool's kernels run and nowhere
    else: not off the chip, not for a bf16 pool, not for a latent pool
    (A.X-K1's), not without a mask."""
    from generativeaiexamples_tpu.models.latent_moe import LatentMoeConfig
    from generativeaiexamples_tpu.serving.kv_cache import LatentPagePool

    active = jnp.asarray([True, False, True])
    quant = _pool(2, 3)
    rows = kernel_live_rows(quant, active, True)
    assert np.asarray(rows.order).tolist() == [0, 2, 1]
    assert np.asarray(rows.n_live).tolist() == [2]
    assert kernel_live_rows(quant, active, False) is None
    assert kernel_live_rows(quant, None, True) is None
    bf16 = PagePool(jnp.zeros((R, 2, 3, PS, HD), jnp.bfloat16),
                    jnp.zeros((R, 2, 3, PS, HD), jnp.bfloat16), PS)
    assert kernel_live_rows(bf16, active, True) is None
    latent = LatentPagePool.zeros(LatentMoeConfig.tiny(), 3, PS,
                                  jnp.bfloat16)
    assert kernel_live_rows(latent, active, True) is None
    assert token_slots(1, active, active).live is None


@pytest.mark.parametrize("looped", [False, True], ids=["", "looped"])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_engine_counts_the_steps_whose_append_is_the_kernel(use_pallas,
                                                            looped):
    """`decode_steps_kernel_append` equals `decode_steps` for a plain
    int8 engine with kernels on, and `decode_steps_fused_append` does for
    a LOOPED model's (its attention call writes the row, PR 46: no step
    is left to the other counter, which counts the steps that launch the
    append); both are 0, never absent, with kernels off, and so are the
    pages its attention kernel reads and would have walked
    and the idle rows both kernels leave out; they are in snapshot(), in
    /metrics and among the fleet's sums."""
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import fleet
    from generativeaiexamples_tpu.serving.engine import (
        EngineMetrics, LLMEngine)
    from generativeaiexamples_tpu.serving.flight import prometheus_text
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = _tiny(looped)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=PS,
                        kv_dtype="int8", prefill_buckets=(128,),
                        decode_steps_per_dispatch=2,
                        pace_emission_max_streams=0)
    with interpreted() if use_pallas else contextlib.nullcontext():
        eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                        use_pallas=use_pallas).start()
        try:
            served = [ev["token_id"] for ev in eng.generate_stream(
                [5, 6, 7, 8], max_new_tokens=6) if ev["token_id"] >= 0]
            snap = eng.metrics.snapshot()
        finally:
            eng.stop()
    assert len(served) == 6
    assert snap["decode_steps"] > 0
    by_form = {"decode_steps_fused_append": looped,
               "decode_steps_kernel_append": not looped}
    for name, engaged in by_form.items():
        assert snap[name] == (
            snap["decode_steps"] if use_pallas and engaged else 0), name
    # the same steps attend through paged_attention_int8: of a step's
    # two rows the live one (under a page long) HAS a page and the idle
    # one none, where whole blocks over a table of two would cover both
    # pages of both; and both kernels leave out one row of the two
    steps = snap["decode_steps"] if use_pallas else 0
    assert snap["decode_attn_pages_live"] == steps
    assert snap["decode_attn_pages_walked"] == 4 * steps
    assert snap["decode_attn_rows_skipped"] == steps
    # ... and a row of one page is one softmax update, whatever the fold
    assert snap["decode_attn_updates"] == steps
    # ... and a step's call is a grid step a live row: one of the two
    assert snap["decode_attn_grid_steps"] == steps
    for name in ("decode_steps_kernel_append", "decode_steps_fused_append",
                 "decode_attn_pages_live",
                 "decode_attn_pages_walked", "decode_attn_rows_skipped",
                 "decode_attn_updates", "decode_attn_grid_steps"):
        assert name in fleet.counter_keys()
        assert name in prometheus_text(snap)
        assert EngineMetrics().snapshot()[name] == 0


# name: (a looped model, the plan)
FUSING = {"looped": (True, PLANS["plain"]), "plain": (False, PLANS["plain"]),
          "rider": (False, PLANS["rider"]),
          "spec_state": (False, PLANS["spec_state"]),
          "verify": (False, PLANS["verify"])}


@pytest.mark.parametrize("name", list(FUSING))
def test_a_looped_walks_step_hands_its_new_row_to_the_attention_call(
        name, monkeypatch):
    """PR 46: with kernels on, a LOOPED model's decode program never calls
    the pool's append: the attention call takes the step's one new row a
    slot (`QuantPagePool.attend_appending`), once a step and cache row. A
    one-pass model's programs of one row a slot (plain, the fused rider's
    decode half, the spec-state lane) keep the append's kernel in a launch
    of its own; a verify's r rows a slot (rank 2) keep the scatters.
    `engine_model.fuses_append` says which, and no drawn block's
    configuration fuses."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.window_attn_moe import (
        WindowAttnMoeConfig)
    from generativeaiexamples_tpu.serving import engine_model as em

    looped, plan = FUSING[name]
    cfg, plan = _tiny(looped), em.StepPlan(**plan)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B = 2
    pool = _pool(cfg.n_kv_heads, 5, rows=cfg.cache_rows)
    assert em.fuses_append(cfg, pool, True) == looped
    assert not em.fuses_append(cfg, pool, False)  # kernels off
    # a drawn block keeps its own call site, and the separate append
    assert kernel_append(pool, True) and not em.fuses_append(
        WindowAttnMoeConfig.tiny(), pool, True)
    calls = {"append": 0, "attend_appending": 0, "_append_kernel": 0}
    for method in calls:
        def counted(self, *a, _method=method,
                    _fn=getattr(QuantPagePool, method), **k):
            calls[_method] += 1
            return _fn(self, *a, **k)
        monkeypatch.setattr(QuantPagePool, method, counted)
    kw = dict(pool=pool, last_tokens=jnp.asarray([3, 7], jnp.int32),
              page_tables=jnp.asarray([[1, 2], [3, 4]], jnp.int32),
              active=jnp.ones((B,), bool), use_pallas=True)
    lengths = jnp.asarray([5, 100], jnp.int32)
    if plan.spec_k or plan.spec_state:
        kw.update(history=jnp.zeros((B, 256), jnp.int32), dev_lengths=lengths)
    if not plan.spec_k:
        kw.update(lengths=lengths, temperature=jnp.zeros((B,), jnp.float32),
                  top_p=jnp.ones((B,), jnp.float32),
                  top_k=jnp.zeros((B,), jnp.int32),
                  rng=jax.random.PRNGKey(1))
    if plan.rider_width:
        kw.update(cache=llama.KVCache.zeros(cfg, 1, max_len=32),
                  chunk_tokens=jnp.zeros((1, 8), jnp.int32),
                  chunk_valid=jnp.int32(5))
    jax.clear_caches()  # the program is traced anew, the counters in it
    with interpreted():
        jax.block_until_ready(em._plan_step(params, cfg, plan, **kw))
    jax.clear_caches()
    # a looped model's passes are a loop around its blocks: traced once
    rows = plan.decode_k * cfg.n_layers
    if name == "verify":  # rank 2: the scatters
        assert calls["attend_appending"] == calls["_append_kernel"] == 0
        assert calls["append"] > 0
    elif looped:
        assert calls == {"append": 0, "attend_appending": rows,
                         "_append_kernel": 0}
    else:
        assert calls == {"append": rows, "attend_appending": 0,
                         "_append_kernel": rows}
