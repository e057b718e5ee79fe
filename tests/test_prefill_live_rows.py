"""A prefill program of one prompt computes, attends over and writes only
its LIVE rows (engine_model.prefill_row_counts): the prompt rounded up to
one of its bucket's heights, picked inside the program from `lengths`; a
group of several runs its whole bucket, its dead flash blocks skipped.

Against the contiguous reference (llama.forward into a KVCache, the pool's
own encode_pages): first tokens, the live pages' codes and scales, and the
pages behind DEAD table entries, which hold a poison value before and
after. The flash kernel's dead-block skip against mha_reference with NaN
in the K/V it may not read. The two counters. Tiny sizes on the CPU; where
a kernel runs it is interpreted (Pallas's plain interpreter).
"""

import contextlib
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.serving import engine_model as em
from generativeaiexamples_tpu.serving.kv_cache import PagePool

STEP = em.PREFILL_ROW_STEP
PS, BUCKET = 64, 4 * STEP
HEIGHTS = em.prefill_row_counts(BUCKET, PS)  # of a program of one prompt
H0, H1 = HEIGHTS[:2]
PLAIN = dataclasses.replace(llama.LlamaConfig.tiny(), max_seq_len=BUCKET)
LOOPED = dataclasses.replace(PLAIN, n_layers=3, n_kv_heads=4, n_passes=2,
                             post_norms=True)
CFGS = {"plain": PLAIN, "looped": LOOPED}
POISON, CANARY = 77, 1  # the fill of every page; the page dead entries name


@contextlib.contextmanager
def interpreted():
    """tests/test_kv_append_kernel.py::interpreted: every pallas_call
    made inside takes Pallas's plain interpreter."""
    call = pl.pallas_call

    @functools.wraps(call)
    def interpreted_call(*args, **kwargs):
        return call(*args, **{**kwargs, "interpret": True})

    with mock.patch.object(pl, "pallas_call", interpreted_call):
        yield


@functools.lru_cache(maxsize=None)
def _params(name):
    cfg = CFGS[name]
    p = llama.init_params(cfg, jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(103)
    for i, n in enumerate(k for k in p["layers"] if k.startswith("ln")):
        w = p["layers"][n]
        p["layers"][n] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), w.shape, w.dtype)
    return p


def _ids(n, seed):
    return (np.arange(1, n + 1) * (7 + 2 * seed) + 3 + seed) % 250 + 1


@functools.lru_cache(maxsize=None)
def _reference(name, n, seed):
    """(greedy first token, K, V [R, KH, n, Hd]) of prompt `seed` alone,
    unpadded, through the contiguous forward."""
    cfg = CFGS[name]
    cache = llama.KVCache.zeros(cfg, 1, max_len=n)
    logits, cache = llama.forward(_params(name), cfg,
                                  jnp.asarray(_ids(n, seed))[None],
                                  kv_cache=cache, use_pallas=False)
    return int(jnp.argmax(logits[0, -1])), cache.k[:, 0], cache.v[:, 0]


def _poisoned(cfg, n_pages, kv_dtype):
    pool = PagePool.zeros(cfg, n_pages, PS, dtype=jnp.dtype(kv_dtype))
    return jax.tree.map(lambda a: jnp.full_like(a, POISON), pool)


def _prefill(name, lengths, kv_dtype, use_pallas=False):
    """The group through prefill_batch_step as the engine lays it out:
    N a power of two, padding rows of length 1 on page 0; a table entry
    past a prompt's pages names the sink page 0 up to the program's live
    pages and the CANARY page past them (the engine leaves 0 there too:
    the program may not read which)."""
    cfg = CFGS[name]
    N = 1
    while N < len(lengths):
        N *= 2
    width = BUCKET // PS
    live_pages = em.prefill_row_counts(BUCKET, PS, N)[int(
        em.prefill_live_index(np.ones((N,), np.int32) * max(lengths), BUCKET,
                              PS))] // PS
    tokens = np.zeros((N, BUCKET), np.int32)
    lens = np.ones((N,), np.int32)
    tables = np.zeros((N, width), np.int32)
    tables[:, live_pages:] = CANARY
    page = CANARY + 1
    for b, n in enumerate(lengths):
        tokens[b, :n] = _ids(n, b)
        lens[b] = n
        held = -(-n // PS)
        tables[b, :held] = np.arange(page, page + held)
        page += held
    pool = _poisoned(cfg, page, kv_dtype)
    zeros = jnp.zeros((N,), jnp.float32)
    with interpreted() if use_pallas else contextlib.nullcontext():
        first, pool = em.prefill_batch_step(
            _params(name), cfg, pool, jnp.asarray(tokens), jnp.asarray(lens),
            jnp.asarray(tables), zeros, zeros, jnp.zeros((N,), jnp.int32),
            jax.random.PRNGKey(0), use_pallas=use_pallas)
    return np.asarray(first), pool, tables, live_pages


GROUPS = [
    (1,), (H0 - 1,), (H0,), (H0 + 1,), (H1,), (H1 + 1,), (BUCKET,),
    (1, H0 + 1), (H0, H0 - 1), (BUCKET - 7, 3),
    (5, H0, 100), (H0 + 1, H1 + 1, PS, BUCKET),
]


@pytest.mark.parametrize("name,kv_dtype,lengths", [
    *[("plain", "int8", g) for g in GROUPS],
    *[("looped", "int8", g) for g in GROUPS[2:4] + GROUPS[7:9] + GROUPS[-1:]],
    *[("plain", "float32", g) for g in (GROUPS[3], GROUPS[8], GROUPS[10])],
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else v)
def test_first_tokens_and_live_pages_are_the_contiguous_references(
        name, kv_dtype, lengths):
    cfg = CFGS[name]
    first, pool, tables, live_pages = _prefill(name, lengths, kv_dtype)
    assert HEIGHTS == (2 * STEP, 3 * STEP, BUCKET)
    assert live_pages * PS == (BUCKET if len(lengths) > 1 else next(
        h for h in HEIGHTS if h >= lengths[0]))
    empty = _poisoned(cfg, 1, kv_dtype)
    for b, n in enumerate(lengths):
        tok, k_ref, v_ref = _reference(name, n, b)
        assert first[b] == tok, (b, n)
        # the prompt's tokens, page by page where the table put them
        pages = tables[b, :-(-n // PS)]
        want = empty.encode_pages(k_ref, v_ref)  # of [R, KH, n, Hd]
        stored = (pool.k, pool.v) if kv_dtype != "int8" else (
            pool.kv[0], pool.s[0], pool.kv[1], pool.s[1])
        for got, ref in zip(stored, want):
            R, KH = got.shape[:2]
            got = np.asarray(got[:, :, pages]).reshape(
                R, KH, len(pages) * PS, *got.shape[4:])[:, :, :n]
            ref = np.asarray(ref)
            if ref.dtype == np.int8:  # a code may round the other way
                assert np.abs(got.astype(int) - ref.astype(int)).max() <= 1
                assert (got != ref).mean() < 1e-3
            else:
                np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    # the page behind the dead table entries, and the one none names,
    # hold what they held
    for leaf in jax.tree.leaves(pool):
        axis = 3 if kv_dtype == "int8" else 2
        untouched = np.asarray(jnp.take(leaf, jnp.asarray([CANARY]), axis))
        assert (untouched == POISON).all()


@pytest.mark.parametrize("name,lengths", [
    ("plain", (H0 + 1,)), ("plain", (3, H1, H0 - 1)), ("looped", (H1,)),
    ("looped", (H0, H1 + 1))],
    ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else v)
def test_with_the_flash_kernel_interpreted_the_program_gives_the_same(
        name, lengths):
    first, pool, tables, _ = _prefill(name, lengths, "int8")
    first_k, pool_k, _, _ = _prefill(name, lengths, "int8", use_pallas=True)
    np.testing.assert_array_equal(first_k[:len(lengths)],
                                  first[:len(lengths)])
    for b, n in enumerate(lengths):
        pages = tables[b, :-(-n // PS)]
        got, ref = (np.asarray(p.kv[:, :, :, pages]).astype(int)
                    for p in (pool_k, pool))
        assert np.abs(got - ref).max() <= 1
        np.testing.assert_allclose(np.asarray(pool_k.s[:, :, :, pages]),
                                   np.asarray(pool.s[:, :, :, pages]),
                                   rtol=1e-4)
    assert (np.asarray(pool_k.kv[:, :, :, CANARY]) == POISON).all()


@pytest.mark.parametrize("bucket,page_size,counts", [
    (128, 128, (128,)), (128, 16, (128,)), (16, 8, (16,)), (256, 128, (256,)),
    (384, 128, (256, 384)), (512, 128, (256, 512)),
    (1024, 128, (512, 768, 1024)),
    (1024, 96, (576, 864, 1024)),  # whole pages
    (2048, 128, (1024, 1280, 1536, 1792, 2048)),
    # never finer than an eighth of the bucket: five heights at most
    (4096, 128, (2048, 2560, 3072, 3584, 4096)),
    (3000, 128, (1536, 1920, 2304, 2688, 3000)),
])
def test_a_buckets_row_counts_and_the_index_the_lengths_pick(
        bucket, page_size, counts):
    assert em.prefill_row_counts(bucket, page_size) == counts
    picked = jax.jit(em.prefill_live_index, static_argnums=(1, 2))
    for i, rows in enumerate(counts):
        below = counts[i - 1] if i else 0
        for longest in {below + 1, rows}:
            lengths = np.asarray([longest], np.int32)
            assert int(em.prefill_live_index(lengths, bucket,
                                             page_size)) == i
            # the program's traced operand picks the same one
            assert int(picked(jnp.asarray(lengths), bucket, page_size)) == i
    # a group of several has ONE height, its bucket
    for group in (2, 4):
        assert em.prefill_row_counts(bucket, page_size, group) == (bucket,)
        lengths = np.asarray([1] * (group - 1) + [counts[0]], np.int32)
        assert int(em.prefill_live_index(lengths, bucket, page_size)) == 0
        assert int(picked(jnp.asarray(lengths), bucket, page_size)) == 0


def _qkv(B, H, KH, S, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, H, S, D), jnp.float32),
            jax.random.normal(ks[1], (B, KH, S, D), jnp.float32),
            jax.random.normal(ks[2], (B, KH, S, D), jnp.float32))


@pytest.mark.parametrize("causal,lengths,offsets", [
    (True, (1, 63, 64, 65, 256), None),
    (True, (129, 200, 7, 128, 192), None),
    (False, (1, 64, 65, 255, 130), None),
    (True, (70, 256, 130, 193, 64), (6, 64, 2, 65, 0)),
], ids=["causal-edges", "causal-ragged", "full-ragged", "offset-ragged"])
def test_flash_kernel_skips_what_lies_past_the_lengths(causal, lengths,
                                                      offsets):
    """Blocks of 64 over 256 rows: a k block that starts at or past a
    row's length holds NaN and may not be read; a q block that does gives
    zeros; every live row is mha_reference's."""
    B, S, blk = len(lengths), 256, 64
    q, k, v = _qkv(B, 4, 2, S, 32)
    ln = jnp.asarray(lengths, jnp.int32)
    off = None if offsets is None else jnp.asarray(offsets, jnp.int32)
    q_rows = S if offsets is None else 2 * blk
    q = q[:, :, :q_rows]
    ref = attn_ops.mha_reference(q, k, v, causal=causal, lengths=ln,
                                 q_offset=off)
    dead_from = -(-np.asarray(lengths) // blk) * blk  # first whole dead block
    rows = np.arange(S)[None, None, :, None]
    nan = rows >= dead_from[:, None, None, None]
    k_bad, v_bad = (jnp.where(nan, jnp.nan, t) for t in (k, v))
    got = attn_ops.flash_attention(q, k_bad, v_bad, causal=causal, lengths=ln,
                                   q_offset=off, block_q=blk, block_k=blk,
                                   interpret=True)
    got, ref = np.asarray(got), np.asarray(ref)
    assert np.isfinite(got).all()
    start = np.zeros(B, int) if offsets is None else np.asarray(offsets)
    for b in range(B):
        live = max(0, min(q_rows, lengths[b] - start[b]))
        np.testing.assert_allclose(got[b, :, :live], ref[b, :, :live],
                                   rtol=2e-5, atol=2e-5)
        dead = -(-live // blk) * blk  # the first wholly dead q block
        assert (got[b, :, dead:] == 0).all()


@pytest.mark.parametrize("buckets,prompts,live,bucket_rows", [
    ((128,), (4, 100), 2 * 128, 2 * 128),
    ((STEP, BUCKET), (4, STEP + 1, H1 + 1), STEP + H0 + BUCKET,
     STEP + BUCKET + BUCKET),
], ids=["one-height", "three-heights"])
def test_engine_counts_the_rows_its_prefills_compute(buckets, prompts, live,
                                                     bucket_rows):
    """`prefill_rows_live` / `prefill_rows_bucket`: 0 at start and never
    absent, the known sums for known groups (one request at a time: every
    group is one row), in snapshot(), /metrics and the fleet's sums."""
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving import fleet
    from generativeaiexamples_tpu.serving.engine import (
        EngineMetrics, LLMEngine)
    from generativeaiexamples_tpu.serving.flight import prometheus_text
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    ecfg = EngineConfig(max_batch_size=2, max_seq_len=buckets[-1] + PS,
                        page_size=PS, kv_dtype="int8",
                        prefill_buckets=buckets, decode_steps_per_dispatch=2,
                        pace_emission_max_streams=0)
    cfg = dataclasses.replace(PLAIN, max_seq_len=buckets[-1] + PS)
    eng = LLMEngine(_params("plain"), cfg, ByteTokenizer(), ecfg,
                    use_pallas=False)
    assert eng.metrics.snapshot()["prefill_rows_live"] == 0
    eng.start()
    try:
        for b, n in enumerate(prompts):
            served = [ev["token_id"] for ev in eng.generate_stream(
                [int(t) for t in _ids(n, b)], max_new_tokens=2)
                if ev["token_id"] >= 0]
            assert served[0] == _reference("plain", n, b)[0]
        snap = eng.metrics.snapshot()
    finally:
        eng.stop()
    assert snap["prefill_rows_live"] == live
    assert snap["prefill_rows_bucket"] == bucket_rows
    for name in ("prefill_rows_live", "prefill_rows_bucket"):
        assert name in fleet.counter_keys()
        assert name in prometheus_text(snap)
        assert EngineMetrics().snapshot()[name] == 0
