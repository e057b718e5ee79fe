"""Fused prefill+decode dispatch (engine.fused_prefill): the decode
batch's next block and one chunk of an in-progress long prefill in ONE
jitted device step, so long prompts advance without standalone
batch-of-1 chunk dispatches serializing ahead of decode blocks.

Byte-identicality tests drive the scheduler INLINE (no threads): the
dispatch schedule is then a pure function of engine state, so fused-on
and fused-off runs see identical schedules and their token streams can
be compared exactly. (Threaded runs are schedule-timing-dependent on
the CPU backend — which compiled variant carries a given step varies
with admission timing, and near-tie argmaxes on random weights can
flip; that is pre-existing engine behavior, not a fusing property.)
"""

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.PRNGKey(3))


def _engine(**kw):
    base = dict(max_batch_size=2, max_seq_len=256, page_size=8,
                prefill_buckets=(16,), decode_steps_per_dispatch=8,
                pace_emission_max_streams=0)
    base.update(kw)
    return LLMEngine(PARAMS, TINY, ByteTokenizer(), EngineConfig(**base),
                     use_pallas=False)


def _step(eng):
    """One deterministic scheduler iteration (mirrors _loop's body,
    single-threaded). Returns the landed _InFlight block or None."""
    eng._admit_waiting()
    eng._advance_long_prefills()
    eng._emit_ready_first_tokens()
    while (len(eng._inflight) < eng.pipeline_depth
           and any(s is not None for s in eng.slots)):
        if not eng._dispatch_decode():
            break
    if not eng._inflight:
        return None
    fl = eng._inflight.popleft()
    eng._process_block_host(fl, eng._fetch_block_host(fl))
    for seq in fl.releases:
        seq.release()
    fl.releases = []
    eng._reap_starved()
    eng._beat += 1
    eng._note_prefill_stalls()
    return fl


def _drain(req):
    """Collect all events already delivered to a request's stream."""
    out = []
    while True:
        try:
            out.append(req.stream.get_nowait())
        except queue.Empty:
            return out


def _run_inline(fused, observe=None):
    """Deterministic workload: one short stream decodes continuously; a
    long prompt (13 chunks of 16) is admitted after two beats. K is
    pinned to 2 so both modes run the same decode program at every step
    (different K variants are distinct XLA programs whose last-bit
    rounding can flip near-tie argmaxes on random weights). Returns
    (short token ids, long token ids, metrics snapshot)."""
    eng = _engine(fused_prefill=fused, decode_steps_per_dispatch=2)
    short = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=64)
    eng.submit(short)
    for _ in range(2):
        _step(eng)
    long_prompt = [(i * 7) % TINY.vocab_size for i in range(200)]
    long_req = GenRequest(prompt_ids=long_prompt, max_new_tokens=4)
    eng.submit(long_req)
    for _ in range(400):
        fl = _step(eng)
        if observe is not None:
            observe(eng, fl)
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._long_prefills and not eng._inflight
                and not eng._pending_first):
            break
    s_toks = [e["token_id"] for e in _drain(short) if e["token_id"] >= 0]
    l_toks = [e["token_id"] for e in _drain(long_req) if e["token_id"] >= 0]
    return s_toks, l_toks, eng.metrics.snapshot()


class TestFusedDispatch:
    def test_fused_on_off_byte_identical_and_counters(self):
        s_off, l_off, m_off = _run_inline(False)
        s_on, l_on, m_on = _run_inline(True)
        # Identical decode programs -> byte-identical token streams.
        assert s_on == s_off and len(s_on) == 64
        assert l_on == l_off and len(l_on) == 4
        # ... and the long stream is the true greedy continuation.
        long_prompt = [(i * 7) % TINY.vocab_size for i in range(200)]
        want = np.asarray(llama.greedy_generate(
            PARAMS, TINY, jnp.asarray([long_prompt]), 4))[0, 200:]
        np.testing.assert_array_equal(l_on, want)
        # Fused-off is byte-identical AND reports zeroed fused counters
        # (present, not absent).
        assert m_off["fused_steps"] == 0
        assert m_off["fused_prefill_tokens"] == 0
        # Fused-on carried the whole 200-token prompt as riders: no
        # standalone chunk dispatch ran while decode traffic was live.
        assert m_on["fused_steps"] == 13  # 12 full chunks + 8-token tail
        assert m_on["fused_prefill_tokens"] == 200
        # prefill_tokens stays honest (real tokens, not rider padding).
        assert m_on["prefill_tokens"] == m_off["prefill_tokens"] == 203

    def test_gap_bound_no_stream_skips_beats(self):
        """While the long prefill is in progress, no live decode stream
        may go more than prefill_chunks_per_block + 1 consecutive beats
        without landing tokens — the generation-stall regression the
        fused rider closes."""
        missed = {"cur": 0, "max": 0}

        def observe(eng, fl):
            if not eng._long_prefills or fl is None:
                return
            live = [s for s in eng.slots
                    if s is not None and not s.prefilling]
            if not live:
                return
            in_block = {id(s) for _, s, *_ in fl.metas}
            if all(id(s) in in_block for s in live):
                missed["cur"] = 0
            else:
                missed["cur"] += 1
                missed["max"] = max(missed["max"], missed["cur"])

        _, _, snap = _run_inline(True, observe=observe)
        bound = EngineConfig().prefill_chunks_per_block + 1
        assert missed["max"] <= bound, missed
        assert snap["fused_steps"] > 0

    def test_idle_engine_uses_fallback_lane(self):
        """With no decode traffic, chunks run through the interleaved
        lane at full dispatch speed — the fused rider needs a decode
        batch to ride on."""
        eng = _engine(fused_prefill=True)
        long_prompt = [(i * 7) % TINY.vocab_size for i in range(100)]
        req = GenRequest(prompt_ids=long_prompt, max_new_tokens=3)
        eng.submit(req)
        for _ in range(200):
            _step(eng)
            if all(s is None for s in eng.slots) and not eng._inflight \
                    and not eng._pending_first:
                break
        toks = [e["token_id"] for e in _drain(req) if e["token_id"] >= 0]
        want = np.asarray(llama.greedy_generate(
            PARAMS, TINY, jnp.asarray([long_prompt]), 3))[0, 100:]
        np.testing.assert_array_equal(toks, want)
        assert eng.metrics.fused_steps == 0  # nothing to fuse into

    def test_speculative_engine_never_fuses(self):
        """The fused step has no speculative variant: a speculative
        engine keeps the interleaved lane even with the knob on."""
        eng = LLMEngine(PARAMS, TINY, ByteTokenizer(),
                        EngineConfig(max_batch_size=2, max_seq_len=256,
                                     page_size=8, prefill_buckets=(16,),
                                     decode_steps_per_dispatch=4,
                                     speculative_k=2, fused_prefill=True,
                                     pace_emission_max_streams=0),
                        use_pallas=False)
        assert eng._fused_width == 0

    def test_fused_threaded_matches_offline_greedy(self):
        """End-to-end through the real scheduler threads: a long prompt
        fused into live decode traffic still produces exactly the
        offline greedy continuation."""
        eng = _engine(fused_prefill=True).start()
        try:
            a_done = threading.Event()

            def stream_a():
                for _ in eng.generate_stream([5, 6, 7],
                                             max_new_tokens=150):
                    pass
                a_done.set()

            t = threading.Thread(target=stream_a, daemon=True)
            t.start()
            while eng.metrics.tokens_out < 4 and not a_done.is_set():
                time.sleep(0.005)
            long_prompt = [(i * 7) % TINY.vocab_size for i in range(150)]
            got = [e["token_id"] for e in
                   eng.generate_stream(long_prompt, max_new_tokens=4)
                   if e["token_id"] >= 0]
            t.join(timeout=60)
            assert a_done.is_set()
            want = np.asarray(llama.greedy_generate(
                PARAMS, TINY, jnp.asarray([long_prompt]), 4))[0, 150:]
            np.testing.assert_array_equal(got, want)
            assert eng.metrics.fused_steps > 0
        finally:
            eng.stop()


class TestTailChunkBucketing:
    def test_tail_chunk_buckets_to_pow2_width(self, monkeypatch):
        """The final partial chunk dispatches at the smallest power-of-
        two width >= the tail instead of padding to the full chunk."""
        widths = []
        real = engine_model.prefill_chunk_step
        real_sample = engine_model.prefill_chunk_sample_step

        def spy(params, cfg, cache, tokens, *a, **k):
            widths.append(tokens.shape[1])
            return real(params, cfg, cache, tokens, *a, **k)

        def sample_spy(params, cfg, cache, tokens, *a, **k):
            # The prompt-completing chunk rides the fused-sampling
            # tail (engine.fused_sampling default-on) — same width
            # accounting.
            widths.append(tokens.shape[1])
            return real_sample(params, cfg, cache, tokens, *a, **k)

        monkeypatch.setattr(engine_model, "prefill_chunk_step", spy)
        monkeypatch.setattr(engine_model, "prefill_chunk_sample_step",
                            sample_spy)
        eng = _engine()
        prompt = [(i * 7) % TINY.vocab_size for i in range(150)]  # tail 6
        req = GenRequest(prompt_ids=prompt, max_new_tokens=2)
        eng.submit(req)
        for _ in range(200):
            _step(eng)
            if all(s is None for s in eng.slots) and not eng._inflight \
                    and not eng._pending_first:
                break
        toks = [e["token_id"] for e in _drain(req) if e["token_id"] >= 0]
        want = np.asarray(llama.greedy_generate(
            PARAMS, TINY, jnp.asarray([prompt]), 2))[0, 150:]
        np.testing.assert_array_equal(toks, want)
        assert widths == [16] * 9 + [8], widths

    def test_staging_buffers_reused_per_width(self):
        """One host staging buffer per width for the engine's lifetime
        (the old path allocated a fresh (1, chunk) array per chunk)."""
        eng = _engine()
        first = eng._chunk_buf(16)
        first[0, :3] = [1, 2, 3]
        again = eng._chunk_buf(16)
        assert again is first  # reused ...
        assert not again.any()  # ... and re-zeroed
        assert eng._chunk_buf(8) is not first
        assert set(eng._chunk_staging) == {8, 16}

    def test_the_device_never_sees_the_staging_buffer_itself(self):
        """jnp.asarray aliases a 64-byte-aligned numpy buffer on the
        CPU, and a chunk's program reads its input after the dispatch
        returned: what goes to the device must not be the buffer that
        _chunk_buf refills for the next chunk."""
        eng = _engine()
        seen = []
        put = eng._put

        def spy(x):
            seen.append(x)
            return put(x)

        eng._put = spy
        req = GenRequest(prompt_ids=[(i * 7) % TINY.vocab_size
                                     for i in range(40)], max_new_tokens=2)
        eng.submit(req)
        for _ in range(6):
            _step(eng)
        staged = list(eng._chunk_staging.values())
        chunks = [x for x in seen if isinstance(x, np.ndarray)
                  and x.ndim == 2 and x.shape[0] == 1
                  and x.dtype == np.int32]
        assert staged and chunks
        for x in chunks:
            assert not any(np.shares_memory(x, buf) for buf in staged)

    def test_pick_chunk_width_respects_warmed_set(self):
        eng = _engine()
        # No warmup: plain power-of-two >= n, capped at the chunk.
        assert eng._pick_chunk_width(6, 16, 64) == 8
        assert eng._pick_chunk_width(16, 16, 64) == 16
        assert eng._pick_chunk_width(1, 16, 64) == 1
        # Warmed: restricted to this scratch shape's compiled widths;
        # the full chunk is the always-warm fallback.
        eng._warm_chunk_widths = {(64, 8), (64, 16), (96, 16)}
        assert eng._pick_chunk_width(6, 16, 64) == 8
        assert eng._pick_chunk_width(6, 16, 96) == 16  # no tail variant
        assert eng._pick_chunk_width(3, 16, 64) == 8  # smallest warmed


class TestFusedWarmup:
    def test_warmup_precompiles_fused_variants(self):
        """warmup(long_prompts=True) on a fused engine records the
        (S_total, K) fused variants, and live dispatch restricts itself
        to them."""
        eng = _engine(fused_prefill=True,
                      decode_steps_per_dispatch=2)
        eng.warmup(long_prompts=True, long_prompt_lengths=(40,))
        # 40 tokens -> S_total 48 (chunk 16); K capped by
        # prefill_decode_k_cap=2 while a prefill is live -> {1, 2}.
        assert (48, 1) in eng._warm_fused
        assert (48, 2) in eng._warm_fused
        assert (48, 16) in eng._warm_chunk_widths
        # The 8-wide tail (40 % 16 = 8) was warmed for the tail bucket.
        assert (48, 8) in eng._warm_chunk_widths
        # An unwarmed scratch shape must NOT fuse (falls back to the
        # interleaved lane instead of compiling mid-traffic).
        from generativeaiexamples_tpu.serving.engine import _LongPrefill

        lp = _LongPrefill(GenRequest(prompt_ids=[1] * 100), 0, None,
                          [1] * 100, 112, None, 16)
        assert not eng._fuse_ready(lp)

    def test_fused_metrics_always_present_in_snapshot(self):
        snap = _engine().metrics.snapshot()
        assert snap["fused_steps"] == 0
        assert snap["fused_prefill_tokens"] == 0
        assert snap["prefill_stall_beats"] == 0
