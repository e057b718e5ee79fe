"""Serving stack: paged attention numerics, paged forward vs contiguous,
engine end-to-end with continuous batching, sampling ops."""

import os
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import (
    PageAllocator, PagePool, SequencePages)
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_attention, paged_attention_reference)
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


class TestPagedAttention:
    def _setup(self, B=2, H=4, KH=2, Hd=16, ps=8, maxp=4, P=16):
        q = _rand((B, H, Hd), 1)
        k_pages = _rand((KH, P, ps, Hd), 2)
        v_pages = _rand((KH, P, ps, Hd), 3)
        table = jnp.asarray(
            np.random.default_rng(0).choice(np.arange(1, P), (B, maxp),
                                            replace=False).astype(np.int32))
        lengths = jnp.array([ps * maxp, ps * 2 + 3], jnp.int32)
        return q, k_pages, v_pages, table, lengths

    def test_reference_matches_dense(self):
        """Gathered-page attention == dense attention over the same keys."""
        from generativeaiexamples_tpu.ops.attention import mha_reference

        q, kp, vp, table, lengths = self._setup()
        got = paged_attention_reference(q, kp, vp, table, lengths)
        B, H, Hd = q.shape
        KH, _, ps, _ = kp.shape
        maxp = table.shape[1]
        k = kp[:, table].transpose(1, 0, 2, 3, 4).reshape(B, KH, maxp * ps, Hd)
        v = vp[:, table].transpose(1, 0, 2, 3, 4).reshape(B, KH, maxp * ps, Hd)
        want = mha_reference(q[:, :, None], k, v, causal=False,
                             lengths=lengths)[:, :, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_pallas_kernel_interpret_matches_reference(self):
        q, kp, vp, table, lengths = self._setup()
        want = paged_attention_reference(q, kp, vp, table, lengths)
        got = paged_attention(q, kp, vp, table, lengths, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


class TestPagedForward:
    def test_prefill_decode_matches_contiguous(self):
        """Paged engine steps must reproduce models.llama exactly."""
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        toks = np.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (1, 11), 0, TINY.vocab_size))
        full, _ = llama.forward(params, TINY, jnp.asarray(toks))

        ps, maxp, n_pages = 4, 8, 32
        pool = PagePool.zeros(TINY, n_pages, ps, dtype=jnp.float32)
        alloc = PageAllocator(n_pages)
        seq = SequencePages(alloc, ps, maxp)
        L = 7  # prefill the first 7 tokens, bucket 8
        seq.ensure(L)
        bucket = 8
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = toks[0, :L]
        row = np.zeros((bucket // ps,), np.int32)
        row[: len(seq.pages)] = seq.pages
        logits, pool = engine_model.prefill_step(
            params, TINY, pool, jnp.asarray(padded), jnp.int32(L),
            jnp.asarray(row), False)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[0, L - 1]),
                                   atol=1e-4)
        # decode the rest, one token at a time
        for t in range(L, toks.shape[1]):
            seq.ensure(t + 1)
            table = seq.table_row()[None, :]
            logits, pool = engine_model.decode_step(
                params, TINY, pool, jnp.asarray(toks[:, t]),
                jnp.asarray(table), jnp.asarray([t + 1], np.int32), False)
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       np.asarray(full[0, t]), atol=1e-4,
                                       err_msg=f"pos {t}")


@pytest.fixture(scope="module")
def tiny_engine():
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                        prefill_buckets=(16, 32))
    eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                    use_pallas=False).start()
    yield eng
    eng.stop()


class TestEngine:
    def test_engine_matches_offline_greedy(self, tiny_engine):
        prompt = [10, 11, 12, 13, 14]
        events = list(tiny_engine.generate_stream(prompt, max_new_tokens=6))
        got = [e["token_id"] for e in events if e["token_id"] >= 0]
        want = np.asarray(llama.greedy_generate(
            tiny_engine.params, TINY, jnp.asarray([prompt]), 6))[0, len(prompt):]
        np.testing.assert_array_equal(got, want)

    def test_concurrent_requests_all_complete(self, tiny_engine):
        results = {}

        def run(i):
            text_ids = [e["token_id"] for e in tiny_engine.generate_stream(
                [i, i + 1, i + 2], max_new_tokens=5) if e["token_id"] >= 0]
            results[i] = text_ids

        threads = [threading.Thread(target=run, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert len(results) == 8
        assert all(len(v) == 5 for v in results.values())
        # determinism: same prompt -> same greedy tokens regardless of batching
        want = np.asarray(llama.greedy_generate(
            tiny_engine.params, TINY, jnp.asarray([[3, 4, 5]]), 5))[0, 3:]
        np.testing.assert_array_equal(results[3], want)

    def test_metrics_populated(self, tiny_engine):
        snap = tiny_engine.metrics.snapshot()
        assert snap["tokens_generated"] > 0
        assert snap["ttft_p50_ms"] is not None

    def test_metrics_window_reset_scopes_the_rate_gauge(self):
        """reset_window() drops prior emission events so the sliding
        gauge covers only the next phase (the r4 8% meter disagreement
        was an idle gap stretching the window span)."""
        from generativeaiexamples_tpu.serving.engine import EngineMetrics

        m = EngineMetrics()
        m.record_tokens(1000)
        assert m.tokens_per_sec() > 0
        m.reset_window()
        assert m.tokens_per_sec() == 0.0
        m.record_tokens(50)
        assert m.tokens_per_sec() > 0

    def test_long_prompt_rejected_at_submit(self, tiny_engine):
        from generativeaiexamples_tpu.serving.engine import PromptTooLongError

        prompt = list(range(5)) * 20  # 100 > max bucket 32
        with pytest.raises(PromptTooLongError):
            list(tiny_engine.generate_stream(prompt, max_new_tokens=3))
        # explicit opt-in truncation still works (context-budget mode)
        events = list(tiny_engine.generate_stream(
            prompt, max_new_tokens=3, truncate_prompt=True))
        assert events[-1]["finished"]


class TestSampling:
    def test_greedy_at_zero_temperature(self):
        from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

        logits = jnp.asarray([[1.0, 3.0, 2.0], [0.5, 0.1, 4.0]])
        sp = SamplingParams.make(2, temperature=0.0)
        toks = sample(logits, sp, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(toks), [1, 2])

    def test_top_k_restricts_support(self):
        from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

        logits = jnp.asarray([[0.0, 5.0, 4.9, -1.0]])
        sp = SamplingParams.make(1, temperature=1.0, top_k=2)
        seen = {int(sample(logits, sp, jax.random.PRNGKey(s))[0])
                for s in range(50)}
        assert seen <= {1, 2}

    def test_top_p_keeps_head(self):
        from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

        logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]])
        sp = SamplingParams.make(1, temperature=1.0, top_p=0.5)
        seen = {int(sample(logits, sp, jax.random.PRNGKey(s))[0])
                for s in range(20)}
        assert seen == {0}

    def test_quantized_mm_close(self):
        from generativeaiexamples_tpu.ops.quant import mm, quantize_tensor

        w = _rand((64, 32), 5)
        x = _rand((4, 64), 6)
        got = mm(x, quantize_tensor(w))
        # int8 rounding accumulates ~ sqrt(K)*amax/254 over K=64 contraction
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   atol=0.2)

    def test_quantized_llama_forward_close(self):
        from generativeaiexamples_tpu.ops.quant import quantize_llama_params

        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        qparams = quantize_llama_params(params)
        toks = jnp.asarray([[1, 2, 3, 4, 5]])
        full, _ = llama.forward(params, TINY, toks)
        quant, _ = llama.forward(qparams, TINY, toks)
        # int8 weight-only: logits close enough to preserve argmax mostly
        assert jnp.mean(jnp.abs(full - quant)) < 0.15


class TestPagedDispatch:
    def test_dispatch_paths_agree(self):
        """Write-then-attend contract: the dispatcher's kernel paths and
        the gather reference agree on a pool that already contains the
        current token at lengths-1."""
        from generativeaiexamples_tpu.serving.paged_attention import (
            paged_attention_dispatch, paged_attention_reference)

        B, H, KH, Hd, ps, maxp, P = 2, 4, 2, 16, 8, 4, 16
        q = _rand((B, H, Hd), 10)
        kp = _rand((KH, P, ps, Hd), 11)
        vp = _rand((KH, P, ps, Hd), 12)
        table = jnp.asarray(
            np.arange(1, 1 + B * maxp).reshape(B, maxp).astype(np.int32))
        lengths = jnp.array([ps * 2 + 4, 7], jnp.int32)  # incl. current token

        want = paged_attention_reference(q, kp, vp, table, lengths)
        got_ref = paged_attention_dispatch(q, kp, vp, table, lengths,
                                           use_pallas=False)
        np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                                   atol=2e-5)
        got_pl = paged_attention_dispatch(q, kp, vp, table, lengths,
                                          use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want),
                                   atol=2e-5)


class TestPoolPressure:
    def test_slot_continues_within_allocated_pages_when_pool_dry(self):
        """With zero free pages a slot whose current page still has room
        must keep decoding (not be cut with finish_reason 'length')."""
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        # 1 slot, page_size 8, exactly enough pages for one sequence of
        # 4 pages (n_pages=5 incl. sink) -> allocator runs dry as soon as
        # the sequence holds all 4.
        ecfg = EngineConfig(max_batch_size=1, max_seq_len=32, page_size=8,
                            prefill_buckets=(8,), decode_steps_per_dispatch=8)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, n_pages=5,
                        use_pallas=False).start()
        try:
            # Misaligned prompt (6 tokens, not a page multiple): the pool
            # hits n_free==0 mid-page, where the old engine finished the
            # slot with 'length' despite in-page capacity remaining. The
            # shrink-retry path must instead complete all 26 tokens
            # (6 + 26 == 32 == max_seq_len exactly).
            events = list(eng.generate_stream(list(range(6)),
                                              max_new_tokens=26))
            toks = [e["token_id"] for e in events if e["token_id"] >= 0]
            assert len(toks) == 26, events[-1]
            assert events[-1]["finish_reason"] in ("length", "stop")
        finally:
            eng.stop()


class TestSchedulerLatency:
    """r4 TTFT paths: no overshoot blocks, first tokens emitted off the
    async prefill copy, admissions landing DURING a block readback."""

    def _engine(self, **kw):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=8, **kw)
        return LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                         use_pallas=False)

    def test_no_overshoot_blocks_past_max_new_tokens(self):
        """max_new_tokens=2 needs exactly ONE decode step after the
        prefill token; the dispatcher must not launch K=8 blocks whose
        tokens nobody will consume (each held the next arrival hostage
        for a full block readback)."""
        eng = self._engine().start()
        try:
            events = list(eng.generate_stream([1, 2, 3], max_new_tokens=2))
            toks = [e["token_id"] for e in events if e["token_id"] >= 0]
            assert len(toks) == 2
            assert eng.metrics.decode_steps == 1
            # ... and exactly one TTFT sample was recorded (the early
            # async path and the block path must not double-count).
            assert eng.metrics.hists["ttft_ms"].count == 1
        finally:
            eng.stop()

    def test_admission_and_first_token_during_blocked_fetch(self):
        """While the reader thread is stuck inside a block readback
        (gated here), a newly submitted request must still be admitted
        AND receive its first token via the async prefill copy."""
        gate = threading.Event()

        class SlowBlock:
            def __init__(self, inner):
                self.inner = inner

            def __array__(self, dtype=None):
                assert gate.wait(timeout=30), "test gate never opened"
                a = np.asarray(self.inner)
                return a.astype(dtype) if dtype is not None else a

        eng = self._engine().start()
        orig = eng._dispatch_decode

        def slow_dispatch():
            out = orig()
            if out and eng._inflight:
                fl = eng._inflight[-1]
                if not isinstance(fl.block, SlowBlock):
                    fl.block = SlowBlock(fl.block)
            return out

        eng._dispatch_decode = slow_dispatch
        try:
            req_a = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=8)
            eng.submit(req_a)
            # Wait until the scheduler is inside the gated fetch.
            deadline = time.time() + 10
            while not eng._fetch_req.qsize() and time.time() < deadline:
                time.sleep(0.005)
            req_b = GenRequest(prompt_ids=[4, 5, 6], max_new_tokens=4)
            eng.submit(req_b)
            # With the readback still gated: B gets a slot (admission
            # overlapped the fetch) and its first token (early path).
            first = req_b.stream.get(timeout=10)
            assert first["token_id"] >= 0
            assert any(s is not None and s.req is req_b for s in eng.slots)
            assert not gate.is_set()
        finally:
            gate.set()
            # Stream A must reach a terminal event once the gate opens.
            while True:
                ev = req_a.stream.get(timeout=30)
                if ev["finished"]:
                    break
            eng.stop()

    def test_mixed_max_new_tokens_batch_completes_exactly(self):
        """Short and long requests share blocks; the scheduled cap must
        not under-deliver the long one or over-deliver the short one."""
        eng = self._engine().start()
        try:
            results = {}

            def run(i, n):
                results[i] = [e["token_id"] for e in eng.generate_stream(
                    [i, i + 1], max_new_tokens=n) if e["token_id"] >= 0]

            threads = [threading.Thread(target=run, args=(i, n))
                       for i, n in enumerate([2, 9, 3, 17])]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert {i: len(v) for i, v in results.items()} == \
                {0: 2, 1: 9, 2: 3, 3: 17}
        finally:
            eng.stop()


class TestSpeculativeDecode:
    """Greedy self-speculative decoding (engine.speculative_k): tokens
    must be EXACTLY the greedy continuation regardless of draft
    acceptance, across batching and request lengths."""

    def _engine(self, spec_k=2):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        # pace_emission_max_streams=0: these tests assert EXACT token
        # equality vs offline greedy, and until PR 33 the pacer could
        # hand a stream its tokens out of order under host contention
        # (a fast block's burst put past a slower block's pending one:
        # ROADMAP D7; r5 had read it as flipped ties). Pacing has its
        # own test class.
        ecfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=4,
                            speculative_k=spec_k,
                            pace_emission_max_streams=0)
        return LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                         use_pallas=False)

    def test_matches_offline_greedy(self):
        eng = self._engine().start()
        try:
            prompt = [10, 11, 12, 13, 14]
            got = [e["token_id"] for e in
                   eng.generate_stream(prompt, max_new_tokens=9)
                   if e["token_id"] >= 0]
            want = np.asarray(llama.greedy_generate(
                eng.params, TINY, jnp.asarray([prompt]), 9))[0, len(prompt):]
            np.testing.assert_array_equal(got, want)
        finally:
            eng.stop()

    def test_concurrent_mixed_lengths_match_greedy(self):
        eng = self._engine().start()
        try:
            results = {}

            def run(i, n):
                results[i] = [e["token_id"] for e in eng.generate_stream(
                    [i, i + 1, i + 2], max_new_tokens=n)
                    if e["token_id"] >= 0]

            lens = [7, 3, 12, 5]
            threads = [threading.Thread(target=run, args=(i, n))
                       for i, n in enumerate(lens)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert {i: len(v) for i, v in results.items()} == \
                {i: n for i, n in enumerate(lens)}
            for i, n in enumerate(lens):
                want = np.asarray(llama.greedy_generate(
                    eng.params, TINY, jnp.asarray([[i, i + 1, i + 2]]),
                    n))[0, 3:]
                np.testing.assert_array_equal(results[i], want,
                                              err_msg=f"slot {i}")
        finally:
            eng.stop()

    def test_sampled_request_falls_back_not_rejected(self):
        """Sampled requests on a speculative engine are served through
        the per-request plain-plan fallback (spec-state decode), not
        rejected: the stream completes with the requested token count,
        the fallback counter moves, and a greedy request issued
        afterwards still matches offline greedy exactly (verify plans
        resume once no sampled slot is live)."""
        eng = self._engine().start()
        try:
            got = [e["token_id"] for e in eng.generate_stream(
                [1, 2], max_new_tokens=7, temperature=0.7, top_p=0.9)
                if e["token_id"] >= 0]
            assert len(got) == 7
            assert eng.metrics.spec_fallback_steps > 0
            snap = eng.metrics.snapshot()
            assert "spec_fallback_steps" in snap
            prompt = [10, 11, 12, 13, 14]
            greedy = [e["token_id"] for e in
                      eng.generate_stream(prompt, max_new_tokens=9)
                      if e["token_id"] >= 0]
            want = np.asarray(llama.greedy_generate(
                eng.params, TINY, jnp.asarray([prompt]), 9))[0, len(prompt):]
            np.testing.assert_array_equal(greedy, want)
        finally:
            eng.stop()

    def test_stress_random_lengths_cancels_and_pool_reuse(self):
        """Churn the speculative scheduler: random request lengths,
        mid-stream cancellations, tight page pool. Every request must
        terminate, token counts must be exact for uncancelled ones, and
        every page must return to the allocator (the page-accounting
        bug class the pipelined-sibling reconciliation fix addressed)."""
        import random

        rng = random.Random(0)
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=3, max_seq_len=64, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=4, speculative_k=2)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        free0 = eng.allocator.n_free
        try:
            reqs = []
            for i in range(12):
                n = rng.choice([1, 2, 5, 9, 17, 30])
                r = GenRequest(prompt_ids=[i % 7 + 1, 2, 3],
                               max_new_tokens=n)
                eng.submit(r)
                if rng.random() < 0.25:
                    r.cancelled = True
                reqs.append((r, n))
            for r, n in reqs:
                toks = 0
                while True:
                    ev = r.stream.get(timeout=60)
                    if ev["token_id"] >= 0:
                        toks += 1
                    if ev["finished"]:
                        break
                if not r.cancelled:
                    assert toks == n, (toks, n)
            # Drain in-flight blocks (parked releases) then check pages.
            deadline = time.time() + 20
            while eng.allocator.n_free != free0 and time.time() < deadline:
                time.sleep(0.05)
            assert eng.allocator.n_free == free0, \
                (eng.allocator.n_free, free0)
        finally:
            eng.stop()

    def test_repetitive_sequence_accepts_drafts(self):
        """A prompt whose greedy continuation enters a cycle must see
        n-gram drafts accepted (tokens-per-step > 1) — the mechanism's
        win condition. TINY greedy outputs loop quickly, so run long
        enough to enter the cycle and compare step counts."""
        eng = self._engine().start()
        try:
            prompt = [7, 8, 9]
            got = [e["token_id"] for e in
                   eng.generate_stream(prompt, max_new_tokens=40)
                   if e["token_id"] >= 0]
            want = np.asarray(llama.greedy_generate(
                eng.params, TINY, jnp.asarray([prompt]), 40))[0, 3:]
            np.testing.assert_array_equal(got, want)
            steps = eng.metrics.decode_steps
            # 40 tokens: 1 from prefill + 39 from verify steps. With
            # zero acceptance that needs 39 steps; a looping greedy
            # continuation must do measurably better.
            assert steps < 39, (steps, got)
        finally:
            eng.stop()


class TestStarvationRecovery:
    """ADVICE r4 (medium): a slot starved against the worst-case
    speculative reservation must NOT be finished with 'length' when the
    landing refund (kv_worst -= spec_worst) restores page capacity —
    and a stale no_capacity flag must never outlive the shortage."""

    def _engine(self, spec_k=2):
        from generativeaiexamples_tpu.serving import engine as engine_mod
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=32, page_size=8,
                            prefill_buckets=(8,),
                            decode_steps_per_dispatch=4,
                            speculative_k=spec_k)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False)
        return eng, engine_mod

    def test_reap_survives_slot_after_spec_refund(self):
        eng, em = self._engine()
        req = GenRequest(prompt_ids=[1, 2, 3, 4], max_new_tokens=24)
        seq = SequencePages(eng.allocator, eng.pool.page_size, eng.max_pages)
        seq.ensure(4)
        slot = em._Slot(req, seq, None)
        eng.slots[0] = slot
        # In-flight spec block reserving worst=12 (K=4 steps x r=3);
        # capacity 32 - (18 + 12) = 2 < r -> starve defers the finish.
        slot.kv_len = 18
        slot.kv_worst = 12
        fl = em._InFlight((None, None), [(0, slot, 18)], 4, spec_worst=12)
        eng._inflight.append(fl)
        eng._starve(0)
        assert slot.no_capacity
        assert eng.slots[0] is slot
        # The block lands: 2 of 12 worst-case tokens committed, the
        # rest refunded (mirrors _process_spec_block bookkeeping).
        eng._inflight.clear()
        slot.kv_len += 2
        slot.kv_worst -= 12
        eng._reap_starved()
        # Capacity is back (32 - 20 = 12 >= r=3): slot must survive
        # with the flag cleared, not be cut with reason 'length'.
        assert eng.slots[0] is slot
        assert not slot.no_capacity
        assert req.stream.empty()

    def test_reap_finishes_slot_when_capacity_truly_exhausted(self):
        eng, em = self._engine()
        req = GenRequest(prompt_ids=[1, 2], max_new_tokens=64)
        seq = SequencePages(eng.allocator, eng.pool.page_size, eng.max_pages)
        seq.ensure(30)
        slot = em._Slot(req, seq, None)
        slot.kv_len = 30  # 32 - 30 = 2 < r=3, nothing in flight
        eng.slots[0] = slot
        slot.no_capacity = True
        eng._reap_starved()
        assert eng.slots[0] is None
        ev = req.stream.get_nowait()
        assert ev["finished"] and ev["finish_reason"] == "length"

    def test_dispatch_clears_stale_flag_nonspec(self):
        """Non-spec path: pool-exhaustion starve recovers once another
        slot frees pages; a successful dispatch must clear the flag so
        a later drain window can't kill the live slot."""
        eng, em = self._engine(spec_k=0)
        req = GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=16)
        seq = SequencePages(eng.allocator, eng.pool.page_size, eng.max_pages)
        seq.ensure(3)
        slot = em._Slot(req, seq, None)
        eng.slots[0] = slot
        slot.no_capacity = True  # stale starve from an earlier shortage
        assert eng._dispatch_decode()
        assert not slot.no_capacity
        eng._inflight.clear()
        eng._reap_starved()
        assert eng.slots[0] is slot


class TestEmissionPacing:
    """VERDICT r4 #2: K-step blocks deliver ~K-token bursts; the pacer
    re-spaces them over the observed block interval for interactive
    stream counts, never delaying terminal events or first tokens."""

    def _engine(self, **kw):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=8, **kw)
        return LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                         use_pallas=False)

    def test_paced_burst_is_spaced_and_ordered(self):
        """White-box: a committed burst must reach the consumer in
        order with real spacing between events (lower-bound only —
        upper bounds flake on a loaded 1-core host)."""
        eng = self._engine().start()
        try:
            req = GenRequest(prompt_ids=[1, 2], max_new_tokens=99)
            from generativeaiexamples_tpu.serving import engine as em
            seq = SequencePages(eng.allocator, eng.pool.page_size,
                                eng.max_pages)
            slot = em._Slot(req, seq, None)
            evs = [{"text": str(j), "token_id": j, "finished": False,
                    "finish_reason": None} for j in range(4)]
            slot.pace_buf = list(evs)
            slot.pace_last_land = time.perf_counter() - 0.2  # 50 ms/tok
            eng._pace_commit(slot, time.perf_counter())
            got = []
            times = []
            for _ in range(4):
                got.append(req.stream.get(timeout=5))
                times.append(time.perf_counter())
            assert [e["token_id"] for e in got] == [0, 1, 2, 3]
            gaps = [b - a for a, b in zip(times, times[1:])]
            assert sum(1 for g in gaps if g >= 0.02) >= 2, gaps
        finally:
            eng.stop()

    def test_fast_block_behind_a_slow_one_keeps_the_order(self):
        """A block that landed slowly is still with the pacer when the
        next lands at once (under 4 ms a token: not paced): its tokens
        go out first, not behind the fast block's (the byte-identity
        pins that failed now and then under load, ROADMAP D7)."""
        eng = self._engine().start()
        try:
            req = GenRequest(prompt_ids=[1, 2], max_new_tokens=99)
            from generativeaiexamples_tpu.serving import engine as em
            seq = SequencePages(eng.allocator, eng.pool.page_size,
                                eng.max_pages)
            slot = em._Slot(req, seq, None)

            def evs(ids):
                return [{"text": str(j), "token_id": j, "finished": False,
                         "finish_reason": None} for j in ids]

            now = time.perf_counter()
            slot.pace_buf = evs(range(4))
            slot.pace_last_land = now - 8.0  # slow: 100 ms a token
            eng._pace_commit(slot, now)
            slot.pace_buf = evs(range(4, 8))
            eng._pace_commit(slot, now + 0.004)  # 1 ms a token
            got = [req.stream.get(timeout=5)["token_id"] for _ in range(8)]
            assert got == list(range(8))
            assert not eng._pace_entries
        finally:
            eng.stop()

    def test_terminal_event_flushes_pending_tokens_in_order(self):
        eng = self._engine().start()
        try:
            req = GenRequest(prompt_ids=[1, 2], max_new_tokens=99)
            from generativeaiexamples_tpu.serving import engine as em
            seq = SequencePages(eng.allocator, eng.pool.page_size,
                                eng.max_pages)
            slot = em._Slot(req, seq, None)
            slot.pace_buf = [{"text": "a", "token_id": 7,
                              "finished": False, "finish_reason": None}]
            slot.pace_last_land = time.perf_counter() - 4.0  # slow pace
            eng._pace_commit(slot, time.perf_counter())
            eng.slots[0] = slot
            eng._finish(0, "cancelled")
            # The paced token arrives BEFORE the terminal, immediately.
            t0 = time.perf_counter()
            first = req.stream.get(timeout=2)
            term = req.stream.get(timeout=2)
            assert first["token_id"] == 7
            assert term["finished"] and term["finish_reason"] == "cancelled"
            assert time.perf_counter() - t0 < 1.0
        finally:
            eng.stop()

    def test_streams_above_threshold_not_paced(self):
        """Bulk regime: with pace_emission_max_streams below the live
        stream count, no pacer entries are ever created."""
        eng = self._engine(pace_emission_max_streams=1).start()
        try:
            entries_seen = []
            results = {}

            def run(i):
                results[i] = [e["token_id"] for e in eng.generate_stream(
                    [i + 1, 2, 3], max_new_tokens=12) if e["token_id"] >= 0]
                with eng._pace_lock:
                    entries_seen.append(dict(eng._pace_entries))

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert all(len(v) == 12 for v in results.values())
            assert all(not e for e in entries_seen)
        finally:
            eng.stop()

    def test_stop_flushes_paced_tokens(self):
        eng = self._engine().start()
        req = GenRequest(prompt_ids=[1, 2], max_new_tokens=99)
        from generativeaiexamples_tpu.serving import engine as em
        seq = SequencePages(eng.allocator, eng.pool.page_size,
                            eng.max_pages)
        slot = em._Slot(req, seq, None)
        slot.pace_buf = [{"text": "z", "token_id": 9,
                          "finished": False, "finish_reason": None}]
        slot.pace_last_land = time.perf_counter() - 8.0
        eng._pace_commit(slot, time.perf_counter())
        eng.stop()
        assert req.stream.get(timeout=2)["token_id"] == 9


class TestPrefillPriorityLane:
    """VERDICT r4 #7: while a chunked prefill is live alongside decode
    streams, decode blocks shrink to prefill_decode_k_cap and up to
    prefill_chunks_per_block chunks dispatch per landed block."""

    def test_decode_k_capped_and_chunks_doubled_during_long_prefill(
            self, monkeypatch):
        from generativeaiexamples_tpu.serving import engine_model as em

        calls = []
        real_chunk = em.prefill_chunk_step
        real_decode = em.decode_multi_step

        def chunk_spy(*a, **k):
            calls.append(("chunk", None))
            return real_chunk(*a, **k)

        def decode_spy(params, cfg, pool, last, tables, lengths, mask,
                       temps, top_ps, top_ks, key, K, *a, **k):
            calls.append(("decode", K))
            return real_decode(params, cfg, pool, last, tables, lengths,
                               mask, temps, top_ps, top_ks, key, K, *a, **k)

        monkeypatch.setattr(em, "prefill_chunk_step", chunk_spy)
        monkeypatch.setattr(em, "decode_multi_step", decode_spy)

        params = llama.init_params(TINY, jax.random.PRNGKey(3))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=8)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        try:
            a_done = threading.Event()
            a_tokens = []

            def stream_a():
                for ev in eng.generate_stream([5, 6, 7],
                                              max_new_tokens=150):
                    if ev["token_id"] >= 0:
                        a_tokens.append(ev["token_id"])
                a_done.set()

            t = threading.Thread(target=stream_a, daemon=True)
            t.start()
            while len(a_tokens) < 4 and not a_done.is_set():
                time.sleep(0.005)
            long_prompt = [(i * 7) % TINY.vocab_size for i in range(160)]
            got = [e["token_id"] for e in
                   eng.generate_stream(long_prompt, max_new_tokens=4)
                   if e["token_id"] >= 0]
            assert len(got) == 4
            t.join(timeout=60)
            assert a_done.is_set()
        finally:
            eng.stop()
        # While the 10 chunks were in progress, decode blocks between
        # chunk dispatches must use the capped K (2, a warmed variant).
        idx = [i for i, (kind, _) in enumerate(calls) if kind == "chunk"]
        between = [K for i, (kind, K) in enumerate(calls)
                   if kind == "decode" and idx[0] < i < idx[-1]]
        assert between and all(K <= 2 for K in between), calls
        # Chunk dispatches group up to prefill_chunks_per_block per
        # landed block: at least one adjacent chunk pair must exist.
        assert any(b - a == 1 for a, b in zip(idx, idx[1:])), idx


class TestPagedKernelChoice:
    def test_stdlib_gated_off_for_small_head_dim(self, monkeypatch):
        """llama3.2-1b (head_dim 64) must route to the in-repo kernel —
        the stdlib kernel's BlockSpecs require head_dim % 128 == 0."""
        from generativeaiexamples_tpu.serving import paged_attention as pa

        calls = {}

        def fake_stdlib(*a, **k):
            calls["stdlib"] = True
            raise AssertionError("stdlib kernel must not be chosen")

        def fake_own(*a, **k):
            calls["own"] = True
            return jnp.zeros(a[0].shape, a[0].dtype)

        monkeypatch.setattr(pa, "_stdlib_paged_attention", fake_stdlib)
        monkeypatch.setattr(pa, "paged_attention", fake_own)
        q = jnp.zeros((2, 4, 64), jnp.float32)   # Hd=64
        kp = jnp.zeros((2, 8, 8, 64), jnp.float32)
        table = jnp.zeros((2, 4), jnp.int32)
        lengths = jnp.ones((2,), jnp.int32)
        pa._paged_tpu(q, kp, kp, table, lengths, scale=None,
                      interpret=False, pages_per_compute_block=None)
        assert calls == {"own": True}

        # Hd=128 picks the stdlib kernel
        q = jnp.zeros((2, 4, 128), jnp.float32)
        kp = jnp.zeros((2, 8, 8, 128), jnp.float32)
        monkeypatch.setattr(pa, "_stdlib_paged_attention",
                            lambda *a, **k: jnp.zeros(q.shape, q.dtype))
        out = pa._paged_tpu(q, kp, kp, table, lengths, scale=None,
                            interpret=False, pages_per_compute_block=None)
        assert out.shape == q.shape


class TestChunkedPrefill:
    def test_long_prompt_matches_offline_greedy(self):
        """A prompt LARGER than the biggest prefill bucket goes through
        chunked prefill and must produce exactly the offline greedy
        continuation (VERDICT r1 §5.7: long-context first-class)."""
        params = llama.init_params(TINY, jax.random.PRNGKey(3))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=96, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=2)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        try:
            prompt = [(i * 7) % TINY.vocab_size for i in range(50)]  # > 16
            got = [e["token_id"]
                   for e in eng.generate_stream(prompt, max_new_tokens=8)
                   if e["token_id"] >= 0]
            want = np.asarray(llama.greedy_generate(
                params, TINY, jnp.asarray([prompt]), 8))[0, len(prompt):]
            np.testing.assert_array_equal(got, want)

            # short prompts still take the batched-bucket path alongside
            short = [5, 6, 7]
            got2 = [e["token_id"]
                    for e in eng.generate_stream(short, max_new_tokens=4)
                    if e["token_id"] >= 0]
            want2 = np.asarray(llama.greedy_generate(
                params, TINY, jnp.asarray([short]), 4))[0, len(short):]
            np.testing.assert_array_equal(got2, want2)
        finally:
            eng.stop()

    def test_chunks_interleave_with_decode_dispatches(self, monkeypatch):
        """A long prompt admitted mid-stream must NOT monopolize the
        device queue: chunk dispatches interleave with decode dispatches
        (one chunk per scheduler iteration), so concurrent streams keep
        their token cadence (VERDICT r2 weak #3). Asserts on the actual
        dispatch ORDER — deterministic, no wall-clock flake."""
        from generativeaiexamples_tpu.serving import engine_model as em

        order = []
        real_chunk = em.prefill_chunk_step
        real_chunk_sample = em.prefill_chunk_sample_step
        real_decode = em.decode_multi_step

        def chunk_spy(*a, **k):
            order.append("chunk")
            return real_chunk(*a, **k)

        def chunk_sample_spy(*a, **k):
            # The prompt-completing chunk rides the fused-sampling
            # tail (engine.fused_sampling default-on) — still one
            # chunk dispatch for interleave accounting.
            order.append("chunk")
            return real_chunk_sample(*a, **k)

        def decode_spy(*a, **k):
            order.append("decode")
            return real_decode(*a, **k)

        monkeypatch.setattr(em, "prefill_chunk_step", chunk_spy)
        monkeypatch.setattr(em, "prefill_chunk_sample_step",
                            chunk_sample_spy)
        monkeypatch.setattr(em, "decode_multi_step", decode_spy)

        params = llama.init_params(TINY, jax.random.PRNGKey(3))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=2)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        try:
            # Stream A: a short prompt generating continuously.
            a_tokens = []
            a_done = threading.Event()

            def stream_a():
                for ev in eng.generate_stream([5, 6, 7],
                                              max_new_tokens=120):
                    if ev["token_id"] >= 0:
                        a_tokens.append(ev["token_id"])
                a_done.set()

            t = threading.Thread(target=stream_a, daemon=True)
            t.start()
            while len(a_tokens) < 4 and not a_done.is_set():
                time.sleep(0.005)
            # Mid-stream: a 150-token prompt = 10 chunks of 16.
            long_prompt = [(i * 7) % TINY.vocab_size for i in range(150)]
            got = [e["token_id"]
                   for e in eng.generate_stream(long_prompt, max_new_tokens=4)
                   if e["token_id"] >= 0]
            t.join(timeout=60)
            assert a_done.is_set(), "stream A never finished"
        finally:
            eng.stop()

        # Correctness through the incremental path is preserved.
        want = np.asarray(llama.greedy_generate(
            params, TINY, jnp.asarray([long_prompt]), 4))[0, len(long_prompt):]
        np.testing.assert_array_equal(got, want)

        # The 10 chunks must not run back-to-back: while stream A was
        # live, every consecutive chunk run is broken up by decode
        # dispatches. Allow a tail run (stream A may finish first), but
        # the longest chunk run while decodes continued afterwards must
        # stay ~1.
        n_chunks = order.count("chunk")
        assert n_chunks == 10, order
        runs = []
        cur = 0
        for op in order:
            if op == "chunk":
                cur += 1
            else:
                if cur:
                    runs.append(cur)
                cur = 0
        if cur:
            runs.append(cur)
        interleaved_runs = runs[:-1] if order and order[-1] == "chunk" \
            else runs
        assert interleaved_runs and max(interleaved_runs) <= 2, (runs, order)

    def test_concurrent_long_prompts_defer_and_complete(self):
        """Scratch-cache memory is bounded: only one chunked prefill
        runs at a time (the second defers, then admits), and both
        produce exact greedy output."""
        params = llama.init_params(TINY, jax.random.PRNGKey(3))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=96, page_size=8,
                            prefill_buckets=(16,),
                            decode_steps_per_dispatch=2)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        try:
            prompts = [[(i * 7) % TINY.vocab_size for i in range(50)],
                       [(i * 11 + 1) % TINY.vocab_size for i in range(40)]]
            outs = [None, None]

            def run(j):
                outs[j] = [e["token_id"] for e in
                           eng.generate_stream(prompts[j], max_new_tokens=6)
                           if e["token_id"] >= 0]

            ts = [threading.Thread(target=run, args=(j,), daemon=True)
                  for j in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            for j in range(2):
                want = np.asarray(llama.greedy_generate(
                    params, TINY, jnp.asarray([prompts[j]]), 6))[0,
                                                                 len(prompts[j]):]
                np.testing.assert_array_equal(outs[j], want, err_msg=f"req {j}")
        finally:
            eng.stop()

    def test_no_compiles_after_long_prompt_warmup(self):
        """VERDICT r4 #1: the 2k-prefill TTFT was 3.5x unstable across
        same-commit runs because parts of the chunked-prefill FINISH
        path (sample_token / set_last_token — jit variants distinct
        from the batched-prefill graph) compiled on the scheduler
        thread mid-request, visible only when the persistent compile
        cache was cold. After warmup(long_prompts=True), serving long
        prompts — including one at full page capacity — must trigger
        ZERO new XLA compiles.

        Runs in a SUBPROCESS: jit caches are process-global, so the
        other tests in this file would pre-warm the exact variants this
        guards; a positive-control compile validates the log-capture
        instrumentation against jax message/logger renames."""
        import subprocess
        import sys
        import textwrap

        script = textwrap.dedent("""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import logging
            import jax
            jax.config.update("jax_platforms", "cpu")
            import jax.numpy as jnp
            from generativeaiexamples_tpu.models import llama
            from generativeaiexamples_tpu.serving.engine import LLMEngine
            from generativeaiexamples_tpu.config.schema import EngineConfig
            from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

            TINY = llama.LlamaConfig.tiny()
            params = llama.init_params(TINY, jax.random.PRNGKey(3))
            ecfg = EngineConfig(max_batch_size=2, max_seq_len=96,
                                page_size=8, prefill_buckets=(16,),
                                decode_steps_per_dispatch=2)
            eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                            use_pallas=False)
            eng.warmup(long_prompts=True)
            records = []
            handler = logging.Handler()
            handler.emit = lambda r: records.append(r.getMessage())
            jax.config.update("jax_log_compiles", True)
            logging.getLogger("jax").addHandler(handler)
            # Positive control: a deliberately novel graph must be seen
            # by the instrumentation, or the assertion below is vacuous.
            jax.jit(lambda x: x * 3 + 7)(jnp.arange(5))
            canary = [m for m in records if m.startswith("Compiling ")]
            assert canary, "instrumentation lost: no compile record"
            records.clear()
            eng.start()
            # 50 -> S_total 64; 87 -> S_total 96 == full page capacity.
            for plen in (50, 87):
                prompt = [(i * 7) % TINY.vocab_size for i in range(plen)]
                got = [e["token_id"] for e in
                       eng.generate_stream(prompt, max_new_tokens=4)
                       if e["token_id"] >= 0]
                assert len(got) == 4
            eng.stop()
            compiles = [m for m in records if m.startswith("Compiling ")]
            assert not compiles, compiles
            print("OK")
        """)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # single emulated device is enough
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=600,
                              env=env)
        assert proc.returncode == 0 and "OK" in proc.stdout, (
            proc.stdout, proc.stderr[-4000:])

    def test_overlong_prompt_rejected_at_page_capacity(self):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=32, page_size=8,
                            prefill_buckets=(16,))
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False)
        import pytest

        from generativeaiexamples_tpu.serving.engine import (
            GenRequest, PromptTooLongError)

        with pytest.raises(PromptTooLongError):
            eng.submit(GenRequest(prompt_ids=list(range(40))))  # > 31


class TestPrefillGroupCap:
    def test_burst_admission_split_into_capped_groups(self, monkeypatch):
        """max_prefill_group bounds each batched prefill dispatch (the
        transient-memory cap for large max_batch_size bursts)."""
        from generativeaiexamples_tpu.serving import engine_model as em

        sizes = []
        real = em.prefill_batch_step

        def spy(params, cfg, pool, tokens, *a, **k):
            sizes.append(tokens.shape[0])
            return real(params, cfg, pool, tokens, *a, **k)

        monkeypatch.setattr(em, "prefill_batch_step", spy)
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=8, max_seq_len=64, page_size=8,
                            prefill_buckets=(16,), max_prefill_group=2,
                            decode_steps_per_dispatch=2)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        try:
            threads = []
            outs = []

            def run():
                outs.append(len([e for e in eng.generate_stream(
                    [3, 4, 5], max_new_tokens=4) if e["token_id"] >= 0]))

            for _ in range(6):
                t = threading.Thread(target=run)
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=60)
        finally:
            eng.stop()
        assert outs == [4] * 6
        # Groups padded to powers of two but never beyond the cap.
        assert sizes and max(sizes) <= 2, sizes
