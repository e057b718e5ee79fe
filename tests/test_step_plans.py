"""Composable step plans (engine.step_plans) + tree-verify drafts
(engine.speculative_tree_branches): every device dispatch is lowered
from a declarative StepPlan through engine_model.plan_step, so the
old partially-exclusive lanes compose — one warmed jitted step can
carry decode + spec tree-verify + a prefill rider simultaneously.

Byte-identicality tests drive the scheduler INLINE (no threads): the
dispatch schedule is then a pure function of engine state, so plans-on
and plans-off runs are exactly comparable (same caveats as
tests/test_fused_prefill.py)."""

import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.engine_model import StepPlan
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.PRNGKey(3))


def _engine(**kw):
    base = dict(max_batch_size=2, max_seq_len=256, page_size=8,
                prefill_buckets=(16,), decode_steps_per_dispatch=2,
                pace_emission_max_streams=0)
    base.update(kw)
    return LLMEngine(PARAMS, TINY, ByteTokenizer(), EngineConfig(**base),
                     use_pallas=False)


def _step(eng):
    """One deterministic scheduler iteration (mirrors _loop's body)."""
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
    out = []
    while True:
        try:
            ev = req.stream.get_nowait()
        except queue.Empty:
            return out
        if ev["token_id"] >= 0:
            out.append(ev["token_id"])


LONG_PROMPT = [(i * 7) % TINY.vocab_size for i in range(200)]


def _run_inline_spec(step_plans, tree_branches=0):
    """Deterministic composed workload on a SPECULATIVE engine: one
    short stream decodes continuously; a 200-token long prompt is
    admitted after two beats. With step_plans on, its chunks ride
    INSIDE the verify dispatches (fused_spec_prefill_step); with them
    off, the speculative engine never fuses (the pre-plan lanes).
    Returns (short tokens, long tokens, metrics snapshot)."""
    eng = _engine(speculative_k=2, speculative_tree_branches=tree_branches,
                  fused_prefill=True, step_plans=step_plans)
    short = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=120)
    eng.submit(short)
    for _ in range(2):
        _step(eng)
    long_req = GenRequest(prompt_ids=list(LONG_PROMPT), max_new_tokens=4)
    eng.submit(long_req)
    for _ in range(400):
        _step(eng)
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._long_prefills and not eng._inflight
                and not eng._pending_first):
            break
    return _drain(short), _drain(long_req), eng.metrics.snapshot()


class TestPlanComposition:
    def test_spec_plus_rider_byte_identical_to_separate_lanes(self):
        """spec-verify + prefill-rider in ONE step produces exactly the
        token streams of the lane-separate scheduler (plans off), and
        both match offline greedy — composition changes only where the
        chunk work rides, never what any stream says."""
        s_off, l_off, m_off = _run_inline_spec(False)
        s_on, l_on, m_on = _run_inline_spec(True)
        assert s_on == s_off and len(s_on) == 120
        assert l_on == l_off and len(l_on) == 4
        want = np.asarray(llama.greedy_generate(
            PARAMS, TINY, jnp.asarray([LONG_PROMPT]), 4))[0, 200:]
        np.testing.assert_array_equal(l_on, want)
        # Plans off: the speculative engine keeps the interleaved lane
        # (never fuses), with the fused counters present and zero.
        assert m_off["fused_steps"] == 0
        assert m_off["fused_prefill_tokens"] == 0
        # Plans on: every prompt token rode a composed spec+rider step.
        assert m_on["fused_steps"] == 13  # 12 full chunks + 8-token tail
        assert m_on["fused_prefill_tokens"] == 200

    def test_counters_account_exactly(self):
        s_on, l_on, m_on = _run_inline_spec(True)
        total = len(s_on) + len(l_on)
        assert m_on["tokens_generated"] == total == 124
        # Every decode token except the two prefill-sampled first
        # tokens was committed by a verify step; the acceptance gauge
        # is their exact ratio (present even when zero).
        assert m_on["spec_tokens_per_step"] > 0
        # prefill accounting stays honest across the composed path:
        # 3 short + 200 long prompt tokens, none double-counted.
        assert m_on["prefill_tokens"] == 203
        # No warmup ran in this test, so no plan lattice was compiled.
        assert m_on["plan_variants_compiled"] == 0
        assert m_on["spec_fallback_steps"] == 0

    def test_spec_commit_identity(self):
        """spec_committed == tokens_generated - first tokens: the
        verify loop emits exactly what the block landing reports."""
        eng = _engine(speculative_k=2, fused_prefill=True, step_plans=True)
        req = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=40)
        eng.submit(req)
        for _ in range(200):
            _step(eng)
            if all(s is None for s in eng.slots) and not eng._inflight \
                    and not eng._pending_first:
                break
        toks = _drain(req)
        assert len(toks) == 40
        assert eng.metrics.spec_committed == 40 - 1  # minus first token
        assert eng.metrics.tokens_out == 40


class TestTreeDrafts:
    def test_tree_draft_branch0_equals_linear_chain(self):
        h = jnp.asarray(np.array([[5, 6, 7, 5, 8, 9, 5, 1, 0, 0]],
                                 np.int32))
        ln = jnp.asarray([8], jnp.int32)
        t0 = jnp.asarray([5], jnp.int32)
        lin = np.asarray(engine_model.ngram_draft(h, ln, t0, 2))
        tree = np.asarray(engine_model.ngram_tree_draft(h, ln, t0, 2, 3))
        np.testing.assert_array_equal(tree[:, 0], lin)
        # Older occurrences feed the middle branches.
        np.testing.assert_array_equal(tree[0, 1], [8, 9])
        # Last branch is the bigram (t_{-1}, t0) = (5, 5) match — no
        # such pair in history, so it falls back to repeating t0.
        np.testing.assert_array_equal(tree[0, 2], [5, 5])
        # Fewer occurrences than branches -> fallback repeats t0.
        t0b = jnp.asarray([9], jnp.int32)
        tb = np.asarray(engine_model.ngram_tree_draft(h, ln, t0b, 2, 3))
        np.testing.assert_array_equal(tb[0, 1], [9, 9])

    def test_tree_draft_bigram_branch(self):
        """The last branch follows the longest-suffix (bigram) match:
        where recency says one continuation but the two-token context
        (9, 5) last occurred elsewhere, the bigram branch drafts that
        older continuation."""
        h = jnp.asarray(np.array([[9, 5, 7, 7, 2, 5, 3, 0, 9, 5]],
                                 np.int32))
        ln = jnp.asarray([10], jnp.int32)
        t0 = jnp.asarray([5], jnp.int32)
        tree = np.asarray(engine_model.ngram_tree_draft(h, ln, t0, 2, 2))
        np.testing.assert_array_equal(tree[0, 0], [3, 0])  # most recent 5
        np.testing.assert_array_equal(tree[0, 1], [7, 7])  # after (9, 5)
        # When the best bigram site IS branch 0's site, the bigram
        # branch dedups to the next-most-recent bigram occurrence.
        h2 = jnp.asarray(np.array([[9, 5, 1, 1, 3, 9, 5, 2, 9, 5]],
                                  np.int32))
        t2 = np.asarray(engine_model.ngram_tree_draft(h2, ln, t0, 2, 2))
        np.testing.assert_array_equal(t2[0, 0], [2, 9])
        np.testing.assert_array_equal(t2[0, 1], [1, 1])

    def test_tree_layout_ancestors(self):
        depth, anc = engine_model._tree_layout(2, 2)
        assert list(depth) == [0, 1, 2, 1, 2]
        assert anc[2, 1] and anc[2, 0] and not anc[2, 3]
        assert anc[4, 3] and not anc[4, 1]

    def test_tree_verify_matches_offline_greedy(self):
        """Tree drafts commit EXACTLY the greedy continuation — same
        contract as the linear chain, across concurrent streams."""
        eng = _engine(speculative_k=2, speculative_tree_branches=3,
                      max_batch_size=4, decode_steps_per_dispatch=4).start()
        try:
            results = {}

            def run(i, n):
                results[i] = [e["token_id"] for e in eng.generate_stream(
                    [i, i + 1, i + 2], max_new_tokens=n)
                    if e["token_id"] >= 0]

            lens = [7, 3, 12, 40]
            threads = [threading.Thread(target=run, args=(i, n))
                       for i, n in enumerate(lens)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            for i, n in enumerate(lens):
                want = np.asarray(llama.greedy_generate(
                    eng.params, TINY, jnp.asarray([[i, i + 1, i + 2]]),
                    n))[0, 3:]
                np.testing.assert_array_equal(results[i], want,
                                              err_msg=f"slot {i}")
        finally:
            eng.stop()

    def test_tree_acceptance_at_least_linear(self):
        """On a repetitive (n-gram friendly) workload the tree lattice
        must accept at least as much per step as the single chain —
        extra branches only ADD acceptance opportunities."""
        def run(tree):
            eng = _engine(speculative_k=2, speculative_tree_branches=tree,
                          decode_steps_per_dispatch=4).start()
            try:
                list(eng.generate_stream([7, 8, 9], max_new_tokens=48))
                snap = eng.metrics.snapshot()
                return snap["spec_tokens_per_step"]
            finally:
                eng.stop()

        linear = run(0)
        tree = run(3)
        assert tree >= linear > 1.0, (tree, linear)

    def test_tree_int8_pool_matches_linear_int8(self):
        """The quantized tree path (int8 codes + narrow scales moved
        verbatim by the relocation commit, gather-then-dequantize
        attention) commits exactly what the linear int8 verify path
        commits: both read the same quantized pool state, so targets —
        and therefore streams — are identical."""
        def run(tree):
            eng = _engine(speculative_k=2, speculative_tree_branches=tree,
                          kv_dtype="int8", page_size=8,
                          decode_steps_per_dispatch=4)
            req = GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=24)
            eng.submit(req)
            for _ in range(100):
                _step(eng)
                if all(s is None for s in eng.slots) and not eng._inflight \
                        and not eng._pending_first:
                    break
            return _drain(req)

        lin = run(0)
        tre = run(3)
        assert len(lin) == 24
        assert tre == lin


class TestPlanWarmupLattice:
    def test_warmup_precompiles_spec_fused_lattice(self):
        """warmup(long_prompts=True) on a plans-on speculative engine
        records the composed (S_total, K) spec+rider variants, counts
        the lattice in plan_variants_compiled, and _select_plan falls
        back to the riderless plan for an unwarmed scratch shape."""
        eng = _engine(speculative_k=2, speculative_tree_branches=2,
                      fused_prefill=True, step_plans=True)
        eng.warmup(long_prompts=True, long_prompt_lengths=(40,))
        assert (48, 1) in eng._warm_spec_fused
        assert (48, 2) in eng._warm_spec_fused
        assert StepPlan(decode_k=2, spec_k=2, tree_branches=2,
                        rider_width=16, rider_s_total=48) in eng._warm_plans
        assert eng.metrics.plan_variants_compiled == len(eng._warm_plans) > 0
        assert eng.metrics.snapshot()["plan_variants_compiled"] \
            == len(eng._warm_plans)
        # Unwarmed scratch shape: the rider is dropped, not compiled.
        from generativeaiexamples_tpu.serving.engine import _LongPrefill

        lp = _LongPrefill(GenRequest(prompt_ids=[1] * 100), 0, None,
                          [1] * 100, 112, None, 16)
        assert not eng._fuse_ready(lp)
        eng._long_prefills.append(lp)
        eng.slots[0] = lp.slot  # None is lp.slot -> candidate filter
        plan, cand = eng._select_plan(2, spec_mode=True)
        assert plan.rider_width == 0 and cand is None
        eng._long_prefills.clear()

    def test_no_cold_plan_after_warmup(self):
        """Every plan dispatched after warmup is in the warmed lattice
        (the GL401-adjacent no-cold-compile invariant, stated on plans
        instead of raw shapes)."""
        eng = _engine(speculative_k=2, fused_prefill=True, step_plans=True,
                      max_seq_len=256)
        eng.warmup(long_prompts=True, long_prompt_lengths=(40,))
        dispatched = []
        real = engine_model.plan_step

        def spy(params, cfg, plan, **kw):
            dispatched.append(plan)
            return real(params, cfg, plan, **kw)

        engine_model.plan_step, orig = spy, engine_model.plan_step
        try:
            short = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=30)
            eng.submit(short)
            for _ in range(2):
                _step(eng)
            long_req = GenRequest(prompt_ids=[(i * 7) % TINY.vocab_size
                                              for i in range(40)],
                                  max_new_tokens=3)
            eng.submit(long_req)
            for _ in range(200):
                _step(eng)
                if all(s is None for s in eng.slots) and not eng._inflight \
                        and not eng._pending_first:
                    break
        finally:
            engine_model.plan_step = orig
        assert dispatched
        for plan in dispatched:
            assert plan in eng._warm_plans, plan

    def test_plan_metrics_always_present(self):
        snap = _engine().metrics.snapshot()
        assert snap["spec_tokens_per_step"] == 0
        assert snap["plan_variants_compiled"] == 0
        assert snap["spec_fallback_steps"] == 0


class TestSampledFallback:
    def test_mixed_sampled_and_greedy_on_spec_engine(self):
        """A sampled request live alongside greedy traffic on a
        speculative engine: both complete with exact token counts, the
        fallback counter moves, and a follow-up greedy stream still
        matches offline greedy (verify plans resume)."""
        eng = _engine(speculative_k=2, max_batch_size=4,
                      decode_steps_per_dispatch=4).start()
        try:
            results = {}

            def run(i, n, temp):
                results[i] = [e["token_id"] for e in eng.generate_stream(
                    [i + 1, i + 2, i + 3], max_new_tokens=n,
                    temperature=temp, top_p=0.9)
                    if e["token_id"] >= 0]

            threads = [threading.Thread(target=run, args=(0, 9, 0.8)),
                       threading.Thread(target=run, args=(1, 12, 0.0))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert len(results[0]) == 9
            assert len(results[1]) == 12
            assert eng.metrics.spec_fallback_steps > 0
            prompt = [10, 11, 12]
            got = [e["token_id"] for e in
                   eng.generate_stream(prompt, max_new_tokens=8)
                   if e["token_id"] >= 0]
            want = np.asarray(llama.greedy_generate(
                eng.params, TINY, jnp.asarray([prompt]), 8))[0, 3:]
            np.testing.assert_array_equal(got, want)
        finally:
            eng.stop()

    def test_sampled_never_rides_verify_plan(self):
        """While a sampled slot is dispatchable, the engine selects the
        spec-state plain plan — never a verify plan that would silently
        greedy-ify the sampled stream."""
        eng = _engine(speculative_k=2)
        plans = []
        real = engine_model.plan_step

        def spy(params, cfg, plan, **kw):
            plans.append(plan)
            return real(params, cfg, plan, **kw)

        engine_model.plan_step, orig = spy, engine_model.plan_step
        try:
            req = GenRequest(prompt_ids=[1, 2], max_new_tokens=6,
                             temperature=0.7)
            eng.submit(req)
            for _ in range(60):
                _step(eng)
                if all(s is None for s in eng.slots) and not eng._inflight \
                        and not eng._pending_first:
                    break
        finally:
            engine_model.plan_step = orig
        assert len(_drain(req)) == 6
        decode_plans = [p for p in plans if p.decode_k > 0]
        assert decode_plans
        assert all(p.spec_state and p.spec_k == 0 for p in decode_plans)
