"""Radix-tree prefix KV cache: allocator ref-counting, SequencePages
adopt/copy-on-write, radix insert/match/evict, and engine-level
cross-request reuse (second identical prompt prefills only the uncached
suffix, outputs byte-identical to offline greedy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import (
    PageAllocator, SequencePages)
from generativeaiexamples_tpu.serving.prefix_cache import RadixPrefixCache
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()


class TestPageAllocatorRefcount:
    def test_double_free_raises(self):
        a = PageAllocator(8)
        (p,) = a.alloc(1)
        a.free([p])
        with pytest.raises(ValueError, match="double free"):
            a.free([p])

    def test_free_of_unallocated_page_raises(self):
        a = PageAllocator(8)
        with pytest.raises(ValueError, match="double free"):
            a.free([3])  # in range but never allocated

    def test_free_out_of_range_raises(self):
        a = PageAllocator(8)
        with pytest.raises(ValueError, match="out of range"):
            a.free([8])
        with pytest.raises(ValueError, match="out of range"):
            a.free([0])  # the sink is never allocatable

    def test_retain_release_lifecycle(self):
        a = PageAllocator(8)
        (p,) = a.alloc(1)
        a.retain([p])
        assert a.refcount(p) == 2
        a.release([p])
        assert a.refcount(p) == 1 and p not in a._free
        a.release([p])
        assert a.refcount(p) == 0 and p in a._free

    def test_retain_unallocated_raises(self):
        a = PageAllocator(8)
        with pytest.raises(ValueError, match="retain of unallocated"):
            a.retain([3])

    def test_alloc_shortfall_invokes_reclaim(self):
        a = PageAllocator(4)  # 3 usable pages
        held = a.alloc(3)
        calls = []

        def reclaim(n):
            calls.append(n)
            a.release(held[:n])  # free exactly what was asked

        a.reclaim = reclaim
        got = a.alloc(2)
        assert calls == [2] and len(got) == 2

    def test_alloc_raises_when_reclaim_cannot_cover(self):
        a = PageAllocator(4)
        a.alloc(3)
        a.reclaim = lambda n: None
        with pytest.raises(MemoryError):
            a.alloc(1)


class TestSequencePages:
    def test_release_is_idempotent_and_nulls_pages(self):
        a = PageAllocator(8)
        seq = SequencePages(a, page_size=4, max_pages=4)
        seq.ensure(10)
        assert len(seq.pages) == 3
        seq.release()
        assert seq.pages == [] and seq.length == 0
        n_free = a.n_free
        seq.release()  # engine error paths may release twice
        assert a.n_free == n_free

    def test_adopt_full_pages_shares_and_extends_privately(self):
        a = PageAllocator(16)
        shared = a.alloc(2)  # stands in for tree-owned pages
        seq = SequencePages(a, page_size=4, max_pages=4)
        cow = seq.adopt(shared, 8)
        assert cow is None
        assert seq.pages == shared and seq.n_shared == 2
        assert all(a.refcount(p) == 2 for p in shared)
        seq.ensure(13)  # 4 pages total: 2 shared + 2 private
        assert len(seq.pages) == 4
        seq.release()
        # Shared pages drop back to the "tree's" single reference;
        # private ones return to the free list.
        assert all(a.refcount(p) == 1 for p in shared)

    def test_adopt_partial_tail_is_copy_on_write(self):
        a = PageAllocator(16)
        shared = a.alloc(2)
        seq = SequencePages(a, page_size=4, max_pages=4)
        cow = seq.adopt(shared, 6)  # 1 full page + 2 tokens into page 2
        assert cow is not None
        src, dst = cow
        assert src == shared[1] and dst not in shared
        assert seq.pages == [shared[0], dst]
        assert seq.n_shared == 1 and seq.length == 6
        # The partially-covered source page was NOT retained by the seq.
        assert a.refcount(shared[1]) == 1
        assert a.refcount(dst) == 1
        seq.release()
        assert a.refcount(shared[0]) == 1 and a.refcount(dst) == 0


class TestRadixPrefixCache:
    def _mk(self, n_pages=32, ps=4, cap=100):
        a = PageAllocator(n_pages)
        return a, RadixPrefixCache(a, ps, cap)

    def test_insert_then_match_page_granular(self):
        a, t = self._mk()
        ids = list(range(11))  # 2 full pages + partial tail
        pages = a.alloc(2)
        assert t.insert(ids, pages) == 2
        assert t.match(ids) == pages
        assert t.match(ids[:9]) == pages  # covers both full pages
        assert t.match(ids[:7]) == pages[:1]
        assert t.match([99] + ids[1:]) == []
        assert all(a.refcount(p) == 2 for p in pages)  # tree + owner

    def test_match_stops_at_divergence(self):
        a, t = self._mk()
        pages = a.alloc(3)
        t.insert(list(range(12)), pages)
        probe = list(range(8)) + [77, 78, 79, 80]
        assert t.match(probe) == pages[:2]

    def test_reinsert_dedups_existing_chunks(self):
        a, t = self._mk()
        ids = list(range(8))
        first = a.alloc(2)
        t.insert(ids, first)
        dup = a.alloc(2)
        assert t.insert(ids, dup) == 0  # nothing newly adopted
        assert t.match(ids) == first   # original pages win
        assert all(a.refcount(p) == 1 for p in dup)  # stayed private
        assert t.n_cached_pages == 2

    def test_evict_lru_leaf_only_when_unreferenced(self):
        a, t = self._mk()
        owner_a = a.alloc(2)
        t.insert(list(range(8)), owner_a)          # chain A (2 pages)
        owner_b = a.alloc(1)
        t.insert([50, 51, 52, 53], owner_b)        # chain B (1 page)
        # Owners release: only the tree references the pages now.
        a.release(owner_a)
        a.release(owner_b)
        # Touch chain B so chain A's leaf is LRU.
        t.match([50, 51, 52, 53])
        assert t.evict(1) == 1
        assert t.match(list(range(8))) == owner_a[:1]  # leaf gone, root kept
        # A leaf still referenced by a live sequence is skipped.
        t.match([50, 51, 52, 53])
        a.retain([owner_b[0]])  # a sequence adopts it
        assert t.evict(10) == 1  # frees A's remaining page, skips B
        assert t.n_cached_pages == 1
        assert t.evictions == 2

    def test_evicting_leaf_exposes_parent(self):
        a, t = self._mk()
        pages = a.alloc(3)
        t.insert(list(range(12)), pages)
        a.release(pages)
        assert t.evict(3) == 3  # unwinds the whole cold chain
        assert t.n_cached_pages == 0
        assert a.n_free == 31

    def test_trim_to_capacity(self):
        a, t = self._mk(cap=2)
        pages = a.alloc(4)
        t.insert(list(range(16)), pages)
        a.release(pages)
        assert t.trim() == 2
        assert t.n_cached_pages == 2

    def test_reclaimable_counts_unpinned_pendant_chains(self):
        a, t = self._mk()
        pages = a.alloc(3)
        t.insert(list(range(12)), pages)
        assert t.reclaimable() == 0  # owner still holds every page
        a.release(pages[1:])  # owner keeps only the first page
        assert t.reclaimable() == 2
        a.release(pages[:1])
        assert t.reclaimable() == 3


class TestEvictionOrderPinned:
    """The lazy persistent heap (one heap reused across evict() calls,
    stale entries re-sorted on pop) must evict in EXACTLY the order of
    the old rebuild-per-call implementation: LRU over current
    timestamps among frontier leaves, live-referenced chains skipped,
    parents exposed back-to-front."""

    class _Recorder(RadixPrefixCache):
        def __init__(self, *a):
            super().__init__(*a)
            self.freed = []

        def _release(self, node):
            self.freed.append(node.page)
            super()._release(node)

    def _apply_ops(self, t, a, ops):
        """Deterministic workload: chains with shared prefixes,
        touches, interleaved evictions."""
        order = []
        for kind, arg in ops:
            if kind == "insert":
                pages = a.alloc(len(arg) // t.page_size)
                t.insert(arg, pages)
                a.release(pages)
            elif kind == "touch":
                t.match(arg)
            elif kind == "evict":
                before = len(t.freed)
                t.evict(arg)
                order.append(tuple(t.freed[before:]))
        return order

    def test_order_identical_to_rebuild_per_call_reference(self):
        ps = 4
        head = list(range(8))
        ops = [
            ("insert", head + [20, 21, 22, 23]),
            ("insert", head + [30, 31, 32, 33, 34, 35, 36, 37]),
            ("insert", [90 + i for i in range(12)]),
            ("touch", head + [30, 31, 32, 33]),
            ("evict", 2),
            ("insert", [70 + i for i in range(8)]),
            ("touch", [90 + i for i in range(8)]),
            ("evict", 3),
            ("evict", 10),
        ]

        def build():
            a = PageAllocator(64)
            return a, self._Recorder(a, ps, 100)

        a1, t_new = build()
        got = self._apply_ops(t_new, a1, ops)

        # Same workload against the pre-PR algorithm, kept verbatim as
        # the order oracle: fresh heap over every leaf per call.
        a2, t_ref = build()

        def ref_evict(n, _t=t_ref):
            import heapq
            freed = 0
            heap = [(n_.last_used, id(n_), n_) for n_ in _t._leaves()]
            heapq.heapify(heap)
            while heap and freed < n:
                _, _, node = heapq.heappop(heap)
                if node.children:
                    continue
                if not _t._evictable(node):
                    continue
                del node.parent.children[node.key]
                _t._release(node)
                _t._n_pages -= 1
                freed += 1
                parent = node.parent
                if parent is not _t.root and not parent.children:
                    heapq.heappush(heap, (parent.last_used, id(parent),
                                          parent))
            _t.evictions += freed
            return freed

        t_ref.evict = ref_evict
        want = self._apply_ops(t_ref, a2, ops)
        assert got == want
        assert t_new.n_cached_pages == t_ref.n_cached_pages

    def test_evict_never_rebuilds_from_a_leaf_walk(self):
        """The satellite perf contract: evict() must run off the
        incremental heap — an O(tree) `_leaves()` walk per call is the
        regression this pins against."""
        a = PageAllocator(64)
        t = self._Recorder(a, 4, 100)
        pages = a.alloc(4)
        t.insert(list(range(16)), pages)
        a.release(pages)

        def boom():
            raise AssertionError("evict() walked every leaf")

        t._leaves = boom
        assert t.evict(2) == 2
        t.match(list(range(16)))  # touch survivors
        assert t.evict(10) == 2


def _engine(**kw):
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    # kv_dtype float32 == TINY's model dtype: the prefix gather is then
    # bit-exact with what a full prefill wrote, so greedy token
    # comparisons cannot flake on cast tie-breaks.
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                        prefill_buckets=(16, 32), kv_dtype="float32",
                        decode_steps_per_dispatch=2, **kw)
    eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, use_pallas=False)
    return params, eng


class TestEnginePrefixReuse:
    def _run(self, eng, prompt, n=6):
        return [e["token_id"] for e in
                eng.generate_stream(prompt, max_new_tokens=n)
                if e["token_id"] >= 0]

    def _greedy(self, params, prompt, n=6):
        return np.asarray(llama.greedy_generate(
            params, TINY, jnp.asarray([prompt]), n))[0, len(prompt):]

    def test_second_identical_prompt_prefills_only_suffix(self):
        """Acceptance bar: a repeated prompt's second prefill runs
        exactly the uncached suffix (page-granular), outputs equal to
        offline greedy both times."""
        params, eng = _engine(prefix_cache=True)
        eng.start()
        try:
            prompt = [(i * 5 + 1) % TINY.vocab_size for i in range(26)]
            want = self._greedy(params, prompt)
            got1 = self._run(eng, prompt)
            s1 = eng.metrics.snapshot()
            got2 = self._run(eng, prompt)
            s2 = eng.metrics.snapshot()
            np.testing.assert_array_equal(got1, want)
            np.testing.assert_array_equal(got2, want)
            assert s1["prefill_tokens"] == 26 and s1["prefix_miss"] == 1
            # 26 tokens = 3 full pages (24) + 2: the hit covers the 3
            # cached pages, the suffix re-runs exactly 2 tokens.
            assert s2["prefix_hits"] == 1
            assert s2["prefix_hit_tokens"] == 24
            assert s2["prefill_tokens"] - s1["prefill_tokens"] == 2
        finally:
            eng.stop()

    def test_page_aligned_full_match_takes_cow_tail(self):
        """A fully-cached page-aligned prompt still prefills ONE token
        (its logits sample the first output): the match is capped at
        plen-1, which lands mid-page and exercises the copy-on-write
        tail — the CoW page is rewritten whole, shared pages never."""
        params, eng = _engine(prefix_cache=True)
        eng.start()
        try:
            prompt = [(i * 3 + 2) % TINY.vocab_size for i in range(24)]
            want = self._greedy(params, prompt)
            got1 = self._run(eng, prompt)
            s1 = eng.metrics.snapshot()
            got2 = self._run(eng, prompt)
            s2 = eng.metrics.snapshot()
            np.testing.assert_array_equal(got1, want)
            np.testing.assert_array_equal(got2, want)
            assert s2["prefix_hit_tokens"] - s1["prefix_hit_tokens"] == 23
            assert s2["prefill_tokens"] - s1["prefill_tokens"] == 1
        finally:
            eng.stop()

    def test_divergent_prompt_reuses_common_prefix_only(self):
        params, eng = _engine(prefix_cache=True)
        eng.start()
        try:
            head = [(i * 7 + 3) % TINY.vocab_size for i in range(16)]
            p_a = head + [1, 2, 3, 4, 5]
            p_b = head + [9, 8, 7, 6, 5]
            got_a = self._run(eng, p_a)
            s1 = eng.metrics.snapshot()
            got_b = self._run(eng, p_b)
            s2 = eng.metrics.snapshot()
            np.testing.assert_array_equal(got_a, self._greedy(params, p_a))
            np.testing.assert_array_equal(got_b, self._greedy(params, p_b))
            # B reuses the 2 shared head pages, prefills its 5-token tail.
            assert s2["prefix_hit_tokens"] - s1["prefix_hit_tokens"] == 16
            assert s2["prefill_tokens"] - s1["prefill_tokens"] == 5
        finally:
            eng.stop()

    def test_cache_off_engine_reports_zero_and_prefills_fully(self):
        params, eng = _engine()
        eng.start()
        try:
            prompt = [(i * 5 + 1) % TINY.vocab_size for i in range(26)]
            want = self._greedy(params, prompt)
            np.testing.assert_array_equal(self._run(eng, prompt), want)
            np.testing.assert_array_equal(self._run(eng, prompt), want)
            snap = eng.metrics.snapshot()
            assert eng.prefix_cache is None
            assert snap["prefix_hits"] == 0 and snap["prefix_miss"] == 0
            assert snap["prefill_tokens"] == 52  # both ran in full
        finally:
            eng.stop()

    def test_eviction_under_allocator_pressure(self):
        """A tight pool serving fresh prompts must evict cold cached
        pages (never fail admission while the cache hoards pages)."""
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=1, max_seq_len=32, page_size=8,
                            prefill_buckets=(16,), kv_dtype="float32",
                            decode_steps_per_dispatch=2,
                            prefix_cache=True, prefix_cache_capacity=1.0)
        # 5 usable pages; every request needs 3 (16-token prompt + 4
        # generated), so serving a second distinct prompt forces
        # eviction of the first one's cached pages.
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, n_pages=6,
                        use_pallas=False).start()
        try:
            for seed in range(3):
                prompt = [(i * 7 + seed) % TINY.vocab_size
                          for i in range(16)]
                got = self._run(eng, prompt, n=4)
                want = self._greedy(params, prompt, n=4)
                np.testing.assert_array_equal(got, want, err_msg=str(seed))
            snap = eng.metrics.snapshot()
            assert snap["prefix_evictions"] > 0
        finally:
            eng.stop()

    def test_cow_source_page_pinned_against_eviction(self):
        """_lookup_prefix pins the gather-only tail page: between the
        match and the gather dispatch, adopt()/ensure() allocations can
        trigger reclaim eviction of refcount-1 tree pages — the pinned
        tail must survive (it used to be evictable, failing the
        request with 'error' on a servable hit)."""
        params, eng = _engine(prefix_cache=True)
        eng.start()
        try:
            prompt = [(i * 3 + 2) % TINY.vocab_size for i in range(24)]
            assert len(self._run(eng, prompt, n=2)) == 2
            import time
            deadline = time.time() + 20
            while eng.prefix_cache.n_cached_pages != 3 and \
                    time.time() < deadline:
                time.sleep(0.05)
            hit = eng._lookup_prefix(prompt)
            pages, m = hit
            assert m == 23 and m % 8 != 0  # mid-page: tail is pinned
            assert eng.allocator.refcount(pages[-1]) == 2
            # Under full pressure, eviction must not free the pinned
            # tail (and its unexposed ancestors stay put too).
            assert eng.prefix_cache.evict(10) == 0
            eng._release_hit_pin(hit)
            assert eng.allocator.refcount(pages[-1]) == 1
            assert eng.prefix_cache.evict(10) == 3
        finally:
            eng.stop()

    def test_no_compiles_on_live_hit_after_warmup(self):
        """The hit path (pool_to_cache gather + suffix-bucket chunk
        steps + the chunked-prefill finish sampler) must be fully
        precompiled by warmup() when the cache is enabled — a cold
        variant compiling on the scheduler thread freezes every live
        stream. Subprocess: jit caches are process-global and sibling
        tests would pre-warm the exact variants this guards."""
        import os
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
            params = llama.init_params(TINY, jax.random.PRNGKey(0))
            ecfg = EngineConfig(max_batch_size=4, max_seq_len=64,
                                page_size=8, prefill_buckets=(16, 32),
                                kv_dtype="float32",
                                decode_steps_per_dispatch=2,
                                prefix_cache=True)
            eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg,
                            use_pallas=False)
            eng.warmup()
            records = []
            handler = logging.Handler()
            handler.emit = lambda r: records.append(r.getMessage())
            jax.config.update("jax_log_compiles", True)
            logging.getLogger("jax").addHandler(handler)
            jax.jit(lambda x: x * 3 + 7)(jnp.arange(5))
            canary = [m for m in records if m.startswith("Compiling ")]
            assert canary, "instrumentation lost: no compile record"
            records.clear()
            eng.start()
            prompt = [(i * 5 + 1) % TINY.vocab_size for i in range(26)]
            for _ in range(2):  # second run is the prefix-cache hit
                got = [e["token_id"] for e in
                       eng.generate_stream(prompt, max_new_tokens=4)
                       if e["token_id"] >= 0]
                assert len(got) == 4
            snap = eng.metrics.snapshot()
            assert snap["prefix_hits"] == 1, snap
            eng.stop()
            compiles = [m for m in records if m.startswith("Compiling ")]
            assert not compiles, compiles
            print("OK")
        """)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=600,
                              env=env)
        assert proc.returncode == 0 and "OK" in proc.stdout, (
            proc.stdout, proc.stderr[-4000:])

    def test_hits_keep_tree_stable_and_pages_balanced(self):
        """Repeated hits must not grow the tree or leak pages: after
        all streams drain, allocated pages == cached pages exactly."""
        params, eng = _engine(prefix_cache=True)
        eng.start()
        try:
            free0 = eng.allocator.n_free
            prompt = [(i * 5 + 1) % TINY.vocab_size for i in range(26)]
            for _ in range(4):
                assert len(self._run(eng, prompt, n=4)) == 4
            import time
            deadline = time.time() + 20
            cached = eng.prefix_cache.n_cached_pages
            while time.time() < deadline and \
                    eng.allocator.n_free != free0 - cached:
                time.sleep(0.05)
            assert cached == 3  # the prompt's full pages, once
            assert eng.allocator.n_free == free0 - cached
        finally:
            eng.stop()
