"""Pallas tree-attention kernels + fused sampling tail (ISSUE 15).

Interpret-mode parity: the bf16 and int8 tree kernels
(serving/paged_attention_tree.py, serving/paged_attention_int8.py
with tree=(k, M)) run under the Pallas interpreter on CPU against the
XLA gather references — ragged lengths, branch counts 2/4/8. Commit
semantics: the whole speculative verify program
(decode_spec_multi_step -> _tree_verify_once) emits bit-identical
targets/counts on the reference route and the forced-kernel route.
Fused sampling: prefill_chunk_sample_step / sample_token_into match
the unfused sample_token pair bitwise (greedy) and draw-for-draw
under a fixed key, and an engine with the knob off streams the same
bytes as the default-on engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.kv_cache import PagePool, QuantPagePool
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_tree_attention_int8_reference_fused,
    paged_tree_attention_reference)
from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    paged_attention_int8, quantize_kv)
from generativeaiexamples_tpu.serving.paged_attention_tree import (
    _canonical_tree, paged_tree_attention, paged_tree_attention_dispatch,
    tree_shape_of)

TINY = llama.LlamaConfig.tiny()


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _geom(k, M, seed=0, B=3, H=4, KH=2, Hd=16, ps=8, maxp=8, P=32):
    """Random q / pools / ragged lengths with tree-slot headroom."""
    r = 1 + M * k
    rng = np.random.default_rng(seed)
    q = _rand((B, H, r, Hd), 1)
    k_pages = _rand((KH, P, ps, Hd), 2)
    v_pages = _rand((KH, P, ps, Hd), 3)
    table = jnp.asarray(rng.choice(np.arange(1, P), (B, maxp),
                                   replace=False), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, maxp * ps - r, (B,)), jnp.int32)
    return q, k_pages, v_pages, table, lengths


class TestTreeLayoutArithmetic:
    def test_canonical_matches_tree_layout(self):
        # The in-kernel arithmetic mask must reproduce _tree_layout
        # exactly for every (k, M) the engine can configure.
        for k in (1, 2, 3, 4):
            for M in (1, 2, 3, 4, 8):
                _, anc = engine_model._tree_layout(k, M)
                assert np.array_equal(np.asarray(anc, bool),
                                      _canonical_tree(k, M)), (k, M)
                assert tree_shape_of(anc, k, M) == (k, M)

    def test_non_canonical_mask_rejected(self):
        _, anc = engine_model._tree_layout(2, 2)
        doctored = np.asarray(anc, bool).copy()
        doctored[2, 1] = not doctored[2, 1]
        assert tree_shape_of(doctored, 2, 2) is None
        assert tree_shape_of(anc, 2, 3) is None  # wrong shape


class TestTreeKernelParity:
    """Interpret-mode kernels == XLA gather references (bf16 + int8),
    ragged lengths, branch counts 2/4/8."""

    @pytest.mark.parametrize("k,M", [(2, 2), (3, 4), (2, 8)])
    def test_bf16_kernel_matches_reference(self, k, M):
        q, kp, vp, table, lengths = _geom(k, M, seed=k * 10 + M)
        _, anc = engine_model._tree_layout(k, M)
        want = paged_tree_attention_reference(q, kp, vp, table, lengths,
                                              anc)
        got = paged_tree_attention(q, kp, vp, table, lengths, (k, M),
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("k,M", [(2, 2), (3, 4), (2, 8)])
    def test_int8_kernel_matches_reference(self, k, M):
        q, kf, vf, table, lengths = _geom(k, M, seed=k * 100 + M)
        r = 1 + M * k
        kq, ks = quantize_kv(kf)
        vq, vs = quantize_kv(vf)
        kv = jnp.stack([kq, vq])[:, None]   # L=1 fused pool
        s = jnp.stack([ks, vs])[:, None]
        _, anc = engine_model._tree_layout(k, M)
        want = paged_tree_attention_int8_reference_fused(
            q, kv[:, 0], s[:, 0], table, lengths, anc)
        got = paged_attention_int8(
            q.transpose(0, 2, 1, 3), kv, s, table, lengths, 0,
            q_rep=r, tree=(k, M), interpret=True).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_doctored_mask_takes_reference_route(self, monkeypatch):
        # A mask the arithmetic kernel cannot express must fall back
        # to the reference EVEN when the kernel route is forced.
        monkeypatch.setenv("ENGINE_TREE_KERNEL_INTERPRET", "1")
        q, kp, vp, table, lengths = _geom(2, 2, seed=7)
        _, anc = engine_model._tree_layout(2, 2)
        doctored = np.asarray(anc, bool).copy()
        doctored[3, 1] = not doctored[3, 1]
        got = paged_tree_attention_dispatch(q, kp, vp, table, lengths,
                                            doctored, 2, 2)
        want = paged_tree_attention_reference(q, kp, vp, table, lengths,
                                              doctored)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_kernel_off_env_takes_reference_route(self, monkeypatch):
        monkeypatch.setenv("ENGINE_TREE_KERNEL", "0")
        monkeypatch.setenv("ENGINE_TREE_KERNEL_INTERPRET", "1")
        q, kp, vp, table, lengths = _geom(2, 2, seed=8)
        _, anc = engine_model._tree_layout(2, 2)
        got = paged_tree_attention_dispatch(q, kp, vp, table, lengths,
                                            anc, 2, 2)
        want = paged_tree_attention_reference(q, kp, vp, table, lengths,
                                              anc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestTreeVerifyCommitSemantics:
    """decode_spec_multi_step (the program _tree_verify_once lives in)
    commits BIT-IDENTICAL target/count streams on the reference route
    vs the forced interpret-mode kernel route — the kernel may change
    speed, never content."""

    K, M = 2, 3

    def _run(self, quantized):
        cfg = TINY
        params = llama.init_params(cfg, jax.random.PRNGKey(5))
        B, ps, maxp = 2, 8, 8
        if quantized:
            pool = QuantPagePool.zeros(cfg, n_pages=B * maxp + 1,
                                       page_size=ps)
        else:
            pool = PagePool.zeros(cfg, n_pages=B * maxp + 1, page_size=ps,
                                  dtype=jnp.float32)
        rng = np.random.default_rng(0)
        Hcap = 64
        history = jnp.asarray(
            rng.integers(2, cfg.vocab_size, (B, Hcap)), jnp.int32)
        last = jnp.asarray(rng.integers(2, cfg.vocab_size, (B,)),
                           jnp.int32)
        lengths = jnp.asarray([11, 19], jnp.int32)
        tables = jnp.asarray(
            np.stack([rng.permutation(np.arange(1, B * maxp + 1))[:maxp]
                      for _ in range(B)]), jnp.int32)
        active = jnp.ones((B,), bool)
        targets, counts, *_ = engine_model.decode_spec_multi_step(
            params, cfg, pool, history, last, lengths, tables, active,
            n_steps=2, k=self.K, n_branches=self.M, use_pallas=False)
        return np.asarray(targets), np.asarray(counts)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_kernel_route_commits_identically(self, quantized,
                                              monkeypatch):
        jax.clear_caches()
        t_ref, c_ref = self._run(quantized)
        monkeypatch.setenv("ENGINE_TREE_KERNEL_INTERPRET", "1")
        jax.clear_caches()
        t_ker, c_ker = self._run(quantized)
        monkeypatch.delenv("ENGINE_TREE_KERNEL_INTERPRET")
        jax.clear_caches()
        np.testing.assert_array_equal(t_ref, t_ker)
        np.testing.assert_array_equal(c_ref, c_ker)


class TestFusedSampling:
    """The fused first-token tail == the unfused pair, bitwise."""

    W = 16

    def _chunk_inputs(self):
        params = llama.init_params(TINY, jax.random.PRNGKey(9))
        toks = jnp.asarray(np.arange(2, 2 + self.W)[None, :], jnp.int32)
        valid = jnp.asarray(self.W, jnp.int32)
        return params, toks, valid

    @pytest.mark.parametrize("temp,flags", [
        (0.0, (True, False, False)),   # greedy: bitwise equality
        (0.7, (False, True, True)),    # sampled: same key -> same draw
    ])
    def test_chunk_sample_step_matches_unfused(self, temp, flags):
        params, toks, valid = self._chunk_inputs()
        key = jax.random.PRNGKey(17)
        cache = llama.KVCache.zeros(TINY, 1, max_len=self.W)
        logits, _ = engine_model.prefill_chunk_step(
            params, TINY, cache, toks, valid, False)
        want = engine_model.sample_token(logits, temp, 0.9, 10, key,
                                         *flags)
        cache = llama.KVCache.zeros(TINY, 1, max_len=self.W)
        lt = jnp.zeros((4,), jnp.int32)
        tok0, lt2, _ = engine_model.prefill_chunk_sample_step(
            params, TINY, cache, toks, valid, lt,
            jnp.asarray(1, jnp.int32), temp, 0.9, 10, key, False,
            sampling_flags=flags)
        assert int(tok0) == int(want)
        np.testing.assert_array_equal(
            np.asarray(lt2), np.asarray([0, int(want), 0, 0]))
        # sample_token_into: the merged one-dispatch finish.
        tok3, lt3 = engine_model.sample_token_into(
            jnp.zeros((4,), jnp.int32), jnp.asarray(3, jnp.int32),
            logits, temp, 0.9, 10, key, *flags)
        assert int(tok3) == int(want) and int(lt3[3]) == int(want)

    def test_rider_sample_plan_lowering(self):
        # StepPlan(rider_sample=True) lowers to the fused tail and
        # returns tok0/last_tokens instead of chunk_logits.
        params, toks, valid = self._chunk_inputs()
        key = jax.random.PRNGKey(23)
        cache = llama.KVCache.zeros(TINY, 1, max_len=self.W)
        res = engine_model.plan_step(
            params, TINY,
            engine_model.StepPlan(rider_width=self.W,
                                  rider_s_total=self.W,
                                  rider_sample=True),
            cache=cache, chunk_tokens=toks, chunk_valid=valid,
            last_tokens=jnp.zeros((4,), jnp.int32),
            slot_idx=jnp.asarray(2, jnp.int32),
            temperature=0.0, top_p=1.0, top_k=0, rng=key,
            sampling_flags=(True, False, False), use_pallas=False)
        assert set(res) >= {"tok0", "last_tokens", "cache"}
        assert "chunk_logits" not in res
        assert int(res["last_tokens"][2]) == int(res["tok0"])

    def test_engine_knob_off_streams_identically(self):
        # fused_sampling=False restores the two-dispatch finish;
        # streams must be byte-identical either way (chunked-prefill
        # prompt so the finish tail actually runs).
        from generativeaiexamples_tpu.config.schema import EngineConfig
        from generativeaiexamples_tpu.serving.engine import LLMEngine
        from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

        params = llama.init_params(TINY, jax.random.PRNGKey(3))
        prompt = [(i * 5) % TINY.vocab_size for i in range(40)]

        def run(fused):
            ecfg = EngineConfig(max_batch_size=2, max_seq_len=256,
                                page_size=8, prefill_buckets=(16,),
                                decode_steps_per_dispatch=2,
                                pace_emission_max_streams=0,
                                fused_sampling=fused)
            eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                            use_pallas=False).start()
            try:
                toks = [ev["token_id"]
                        for ev in eng.generate_stream(prompt,
                                                      max_new_tokens=8)
                        if ev["token_id"] >= 0]
            finally:
                eng.stop()
            return toks, eng.metrics.fused_sample_dispatches

        fused_toks, fused_count = run(True)
        plain_toks, plain_count = run(False)
        assert fused_toks == plain_toks
        assert len(fused_toks) == 8
        assert fused_count >= 1      # the tail actually rode a dispatch
        assert plain_count == 0      # knob off: counter stays 0
