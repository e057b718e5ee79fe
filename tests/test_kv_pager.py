"""Session KV pager (serving/kv_pager.py): demote->promote byte
identity across all three tiers (incl. int8 codes+scales verbatim),
off-by-default byte identity, reclaim-hook demotion instead of
destruction, crash-safe spill rewrites, the always-present counter
contract, concurrent submit vs background demotion, and the graftlint
coverage pins for the pager's tier lock and hot-path markers."""

import os
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import (
    PageAllocator, PagePool, QuantPagePool)
from generativeaiexamples_tpu.serving.kv_pager import (
    KV_PAGER_KEYS, KVPager, PagedPrefixCache)
from generativeaiexamples_tpu.serving.prefix_cache import (
    TIER_DEVICE, TIER_DISK, TIER_HOST)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PS = 4


def _filled_pool(dtype="float32", n_pages=16, seed=0):
    """A small pool whose every byte is recognizable random data."""
    rng = np.random.default_rng(seed)
    pool = PagePool.zeros(TINY, n_pages, PS, dtype=dtype)
    if pool.quantized:
        return QuantPagePool(
            jnp.asarray(rng.integers(-127, 127, pool.kv.shape)
                        .astype(np.int8)),
            jnp.asarray(rng.random(pool.s.shape).astype(np.float32)), PS)
    return PagePool(
        jnp.asarray(rng.random(pool.k.shape).astype(np.float32)),
        jnp.asarray(rng.random(pool.v.shape).astype(np.float32)), PS)


def _mk(dtype="float32", host_mb=4, n_pages=16, **pager_kw):
    state = {"pool": _filled_pool(dtype, n_pages)}
    alloc = PageAllocator(n_pages)
    pager = KVPager(state["pool"], host_budget_mb=host_mb, **pager_kw)
    cache = PagedPrefixCache(alloc, PS, 100, pager, lambda: state["pool"])
    return state, alloc, pager, cache


def _page_bytes(pool, page):
    if pool.quantized:
        return (np.asarray(pool.kv)[:, :, :, page],
                np.asarray(pool.s)[:, :, :, page])
    return (np.asarray(pool.k)[:, :, page], np.asarray(pool.v)[:, :, page])


class TestPagerRoundtrip:
    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_demote_promote_is_byte_identical(self, dtype):
        """The core contract: a page's bytes after device -> host ->
        device are EXACTLY what the pool held before demotion (int8
        pools move codes + narrow scales verbatim, never re-quantized)."""
        state, alloc, pager, cache = _mk(dtype)
        ids = list(range(12))
        pages = alloc.alloc(3)
        cache.insert(ids, pages)
        alloc.release(pages)
        before = [_page_bytes(state["pool"], p) for p in pages]
        assert cache.evict(10) == 3
        assert alloc.n_free == 15  # every device page back on the list
        nodes = cache.match_nodes(ids)
        assert [n.tier for n in nodes] == [TIER_HOST] * 3
        # Scribble over the freed pages so a promotion that read the
        # (stale) device pool instead of the host copy would fail.
        junk = alloc.alloc(3)
        p = state["pool"]
        state["pool"] = PagePool(p.k.at[:, :, junk].set(-1.0),
                                 p.v.at[:, :, junk].set(-1.0), PS) \
            if not p.quantized else QuantPagePool(
                p.kv.at[:, :, :, junk].set(0),
                p.s.at[:, :, :, junk].set(0), PS)
        alloc.release(junk)
        state["pool"] = cache.promote(state["pool"], nodes)
        assert [n.tier for n in nodes] == [TIER_DEVICE] * 3
        for want, node in zip(before, nodes):
            got = _page_bytes(state["pool"], node.page)
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
        s = pager.stats()
        assert s["kv_demotions"] == 3 and s["kv_promotions"] == 3
        assert s["kv_promote_tokens"] == 3 * PS
        assert s["kv_host_pages"] == 0
        pager.close()

    def test_disk_tier_roundtrip(self):
        """host_budget 0: demotions go straight to the mmap'd spill
        and promote back byte-identically."""
        state, alloc, pager, cache = _mk(host_mb=0)
        ids = list(range(12))
        pages = alloc.alloc(3)
        cache.insert(ids, pages)
        alloc.release(pages)
        before = [_page_bytes(state["pool"], p) for p in pages]
        cache.evict(10)
        nodes = cache.match_nodes(ids)
        assert [n.tier for n in nodes] == [TIER_DISK] * 3
        assert pager.stats()["kv_spill_pages"] == 3
        state["pool"] = cache.promote(state["pool"], nodes)
        for want, node in zip(before, nodes):
            got = _page_bytes(state["pool"], node.page)
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
        pager.close()

    def test_background_spill_host_to_disk_then_promote(self):
        """run_maintenance pushes host-LRU pages into the spill once
        the host tier is near budget; a later promote reads the disk
        record byte-identically."""
        # Budget for ~3 host pages at the tiny geometry (one page is
        # 2 KB f32): insert 6 -> demote all -> 3 land on disk
        # directly, maintenance may move more.
        state, alloc, pager, cache = _mk(
            host_mb=(3 * 2048) // (1 << 20) + 1, n_pages=16)
        pager.n_host_slots = 3  # force the tiny budget deterministically
        pager._host_free = list(range(2, -1, -1))
        pager._host_codes = pager._host_codes[:3]
        ids = list(range(24))
        pages = alloc.alloc(6)
        cache.insert(ids, pages)
        alloc.release(pages)
        before = [_page_bytes(state["pool"], p) for p in pages]
        cache.evict(10)
        pager.wait_maintenance()
        pager._run_maintenance()  # deterministic second pass
        nodes = cache.match_nodes(ids)
        tiers = [n.tier for n in nodes]
        assert TIER_DISK in tiers and TIER_DEVICE not in tiers
        state["pool"] = cache.promote(state["pool"], nodes)
        for want, node in zip(before, nodes):
            got = _page_bytes(state["pool"], node.page)
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
        pager.close()

    def test_promote_memoryerror_leaves_cold_tiers_intact(self):
        """When the allocator cannot cover the cold pages, promote
        raises MemoryError and every node keeps its cold-tier bytes
        (the engine then serves the resident prefix only)."""
        state, alloc, pager, cache = _mk(n_pages=8)
        ids = list(range(12))
        pages = alloc.alloc(3)
        cache.insert(ids, pages)
        alloc.release(pages)
        cache.evict(10)
        nodes = cache.match_nodes(ids)
        hold = alloc.alloc(7)  # drain the free list (7 usable pages)
        with pytest.raises(MemoryError):
            cache.promote(state["pool"], nodes)
        assert [n.tier for n in nodes] == [TIER_HOST] * 3
        assert pager.stats()["kv_host_pages"] == 3
        alloc.release(hold)
        state["pool"] = cache.promote(state["pool"], nodes)
        assert [n.tier for n in nodes] == [TIER_DEVICE] * 3
        pager.close()

    def test_reinsert_reattaches_demoted_chunk_without_dispatch(self):
        """A re-played prompt whose chunk was demoted re-adopts the
        fresh device page in place (no promotion dispatch) and frees
        the cold copy."""
        state, alloc, pager, cache = _mk()
        ids = list(range(8))
        pages = alloc.alloc(2)
        cache.insert(ids, pages)
        alloc.release(pages)
        cache.evict(10)
        assert cache.n_cached_pages == 0
        fresh = alloc.alloc(2)
        cache.insert(ids, fresh)
        nodes = cache.match_nodes(ids)
        assert [n.tier for n in nodes] == [TIER_DEVICE] * 2
        assert [n.page for n in nodes] == fresh
        s = pager.stats()
        assert s["kv_host_pages"] == 0 and s["kv_promotions"] == 0
        assert cache.n_cached_pages == 2
        pager.close()


class TestSpillFile:
    def test_crash_safe_spill_mid_rewrite(self, monkeypatch):
        """A crash during a compaction rewrite (os.replace never
        happens) leaves the OLD file — and the live mapping — intact:
        handles stay valid, the pager keeps serving, the temp file is
        gone, and the single-flight gate is released. (Growth never
        rewrites: it extends the file in place, which only ever adds
        unused slots.)"""
        state, alloc, pager, cache = _mk(host_mb=0, n_pages=16)
        ids_a, ids_b = list(range(8)), [50 + i for i in range(8)]
        pa, pb = alloc.alloc(2), alloc.alloc(2)
        cache.insert(ids_a, pa)
        cache.insert(ids_b, pb)
        alloc.release(pa)
        alloc.release(pb)
        before_b = [_page_bytes(state["pool"], p) for p in pb]
        cache.evict(10)  # 4 spill records
        nodes_a = cache.match_nodes(ids_a)
        state["pool"] = cache.promote(state["pool"], nodes_a)  # 2 dead
        old_size = os.path.getsize(pager._spill_path)

        real_replace = os.replace

        def boom(src, dst):
            if dst == pager._spill_path:
                raise OSError("simulated crash mid-rewrite")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            pager._compact()
        monkeypatch.undo()
        assert os.path.getsize(pager._spill_path) == old_size
        assert not os.path.exists(pager._spill_path + ".tmp")
        assert not pager._compacting  # single-flight gate released
        assert pager.stats()["kv_spill_compactions"] == 0
        # The index keeps serving from the intact old generation.
        nodes_b = cache.match_nodes(ids_b)
        state["pool"] = cache.promote(state["pool"], nodes_b)
        for want, node in zip(before_b, nodes_b):
            got = _page_bytes(state["pool"], node.page)
            np.testing.assert_array_equal(got[0], want[0])
        pager.close()

    def test_compaction_drops_dead_records_and_remaps_live(self):
        """Promotions leave dead spill records; compaction rewrites the
        file with live ones only, remapping surviving handles."""
        state, alloc, pager, cache = _mk(host_mb=0, n_pages=16)
        ids_a, ids_b = list(range(8)), [50 + i for i in range(8)]
        pa, pb = alloc.alloc(2), alloc.alloc(2)
        cache.insert(ids_a, pa)
        cache.insert(ids_b, pb)
        alloc.release(pa)
        alloc.release(pb)
        before_b = [_page_bytes(state["pool"], p) for p in pb]
        cache.evict(10)  # 4 spill records
        nodes_a = cache.match_nodes(ids_a)
        state["pool"] = cache.promote(state["pool"], nodes_a)  # 2 dead
        pager._compact()
        s = pager.stats()
        assert s["kv_spill_compactions"] == 1
        assert s["kv_spill_pages"] == 2  # only B's records survive
        nodes_b = cache.match_nodes(ids_b)
        state["pool"] = cache.promote(state["pool"], nodes_b)
        for want, node in zip(before_b, nodes_b):
            got = _page_bytes(state["pool"], node.page)
            np.testing.assert_array_equal(got[0], want[0])
        pager.close()

    def test_close_removes_ephemeral_spill_dir(self):
        _, alloc, pager, cache = _mk(host_mb=0)
        pages = alloc.alloc(1)
        cache.insert(list(range(PS)), pages)
        alloc.release(pages)
        cache.evict(1)
        spill_dir = pager._spill_dir
        assert os.path.isdir(spill_dir)
        pager.close()
        assert not os.path.exists(spill_dir)


def _engine(**kw):
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    # kv_dtype float32 == TINY's model dtype so greedy comparisons
    # cannot flake on cast tie-breaks (same as test_prefix_cache).
    base = dict(max_batch_size=1, max_seq_len=32, page_size=8,
                prefill_buckets=(16,), kv_dtype="float32",
                decode_steps_per_dispatch=2,
                prefix_cache=True, prefix_cache_capacity=1.0)
    base.update(kw)
    ecfg = EngineConfig(**base)
    eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, n_pages=6,
                    use_pallas=False)
    return params, eng


def _run(eng, prompt, n=4):
    return [e["token_id"] for e in
            eng.generate_stream(prompt, max_new_tokens=n)
            if e["token_id"] >= 0]


def _greedy(params, prompt, n=4):
    return list(np.asarray(llama.greedy_generate(
        params, TINY, jnp.asarray([prompt]), n))[0, len(prompt):])


class TestEngineTiering:
    def test_reclaim_hook_demotes_instead_of_destroying(self):
        """Tight pool + distinct prompts: live traffic forces the
        reclaim hook, which must PARK cold sessions (demotions > 0,
        prefixes still fully matchable) rather than delete their KV —
        and every stream stays byte-identical to offline greedy."""
        params, eng = _engine(kv_pager=True, kv_host_budget_mb=4)
        eng.start()
        try:
            prompts = [[(i * 7 + s) % TINY.vocab_size for i in range(16)]
                       for s in range(4)]
            for p in prompts:
                assert _run(eng, p) == _greedy(params, p)
            snap = eng.metrics.snapshot()
            assert snap["kv_demotions"] > 0
            assert snap["kv_host_pages"] > 0
            resident = sum(len(eng.prefix_cache.match_nodes(p)) == 2
                           for p in prompts)
            assert resident == 4  # nothing was destroyed
        finally:
            eng.stop()

    def test_warm_resume_from_host_tier_is_byte_identical(self):
        """Resuming a demoted session promotes its pages back and the
        stream equals never-demoted offline greedy; the hit counts as
        a prefix HIT (not a miss) with kv_promotions > 0."""
        params, eng = _engine(kv_pager=True, kv_host_budget_mb=4)
        eng.start()
        try:
            prompts = [[(i * 7 + s) % TINY.vocab_size for i in range(16)]
                       for s in range(4)]
            for p in prompts:
                _run(eng, p)
            s1 = eng.metrics.snapshot()
            got = _run(eng, prompts[0])
            assert got == _greedy(params, prompts[0])
            s2 = eng.metrics.snapshot()
            assert s2["kv_promotions"] > 0
            assert s2["prefix_hits"] == s1["prefix_hits"] + 1
            assert s2["kv_promote_tokens"] > 0
        finally:
            eng.stop()

    def test_lookup_without_promote_never_dispatches(self):
        """promote=False (the scratch-lane-full discard path): a match
        over demoted nodes serves only the device-resident prefix and
        spends ZERO promotions — the doomed hit must not scatter."""
        params, eng = _engine(kv_pager=True, kv_host_budget_mb=4)
        eng.start()
        try:
            prompts = [[(i * 7 + s) % TINY.vocab_size for i in range(16)]
                       for s in range(4)]
            for p in prompts:
                _run(eng, p)
            s1 = eng.metrics.snapshot()
            assert s1["kv_demotions"] > 0
            hit = eng._lookup_prefix(prompts[0], promote=False)
            s2 = eng.metrics.snapshot()
            assert s2["kv_promotions"] == s1["kv_promotions"]
            if hit is not None:  # leading resident run only
                eng._release_hit_pin(hit)
            # ...and the promoting path still works afterwards.
            assert _run(eng, prompts[0]) == _greedy(params, prompts[0])
        finally:
            eng.stop()

    def test_int8_engine_resume_byte_identical(self):
        """int8 pools demote codes+scales verbatim: a resumed stream
        must equal the FIRST (never-demoted) run exactly."""
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=1, max_seq_len=32, page_size=8,
                            prefill_buckets=(16,), kv_dtype="int8",
                            decode_steps_per_dispatch=2,
                            prefix_cache=True, prefix_cache_capacity=1.0,
                            kv_pager=True, kv_host_budget_mb=4)
        eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, n_pages=6,
                        use_pallas=False).start()
        try:
            prompts = [[(i * 7 + s) % TINY.vocab_size for i in range(16)]
                       for s in range(4)]
            first = [_run(eng, p) for p in prompts]
            snap = eng.metrics.snapshot()
            assert snap["kv_demotions"] > 0
            assert _run(eng, prompts[0]) == first[0]
            assert eng.metrics.snapshot()["kv_promotions"] > 0
        finally:
            eng.stop()

    def test_pager_off_is_byte_identical_with_zero_counters(self):
        """engine.kv_pager off: no pager object, every kv_* key is 0
        (present, never absent), and streams equal the pager-on engine
        token for token."""
        params, eng_off = _engine()  # prefix cache on, pager off
        _, eng_on = _engine(kv_pager=True, kv_host_budget_mb=4)
        eng_off.start()
        eng_on.start()
        try:
            prompts = [[(i * 7 + s) % TINY.vocab_size for i in range(16)]
                       for s in range(3)] + \
                      [[(i * 7) % TINY.vocab_size for i in range(16)]]
            for p in prompts:
                assert _run(eng_off, p) == _run(eng_on, p)
            snap = eng_off.metrics.snapshot()
            assert eng_off.kv_pager is None
            for key in KV_PAGER_KEYS:
                assert snap[key] == 0, key
        finally:
            eng_off.stop()
            eng_on.stop()

    def test_kv_pager_requires_prefix_cache(self):
        with pytest.raises(ValueError, match="requires engine.prefix_cache"):
            _engine(kv_pager=True, prefix_cache=False)

    def test_counters_always_present_in_snapshot(self):
        from generativeaiexamples_tpu.serving.engine import EngineMetrics

        snap = EngineMetrics().snapshot()
        for key in KV_PAGER_KEYS:
            assert snap[key] == 0, key

    def test_concurrent_submit_vs_background_demotion(self):
        """Threads replaying sessions while maintenance kicks run
        demotion/promotion/spill concurrently: every stream must stay
        byte-identical to offline greedy."""
        params, eng = _engine(kv_pager=True, kv_host_budget_mb=4)
        eng.start()
        prompts = [[(i * 7 + s) % TINY.vocab_size for i in range(16)]
                   for s in range(4)]
        want = [_greedy(params, p) for p in prompts]
        errors = []
        stop = threading.Event()

        def churn():
            # Race the scheduler's demote/promote against the
            # single-flight worker (host->disk spill + compaction).
            while not stop.is_set():
                eng.kv_pager.kick_maintenance()
                stop.wait(0.002)

        t = threading.Thread(target=churn, daemon=True)
        t.start()

        def worker(idx):
            try:
                for rep in range(3):
                    got = _run(eng, prompts[idx])
                    if got != want[idx]:
                        errors.append((idx, rep, got, want[idx]))
            except Exception as e:  # surfaces in the main thread
                errors.append((idx, repr(e)))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
        finally:
            stop.set()
            t.join(timeout=10)
            eng.stop()
        assert not errors, errors[:2]
        assert eng.metrics.snapshot()["kv_pager_errors"] == 0


class TestLintCoverage:
    def test_gl201_covers_pager_tier_lock(self, tmp_path):
        """GL201 must treat the pager's tier lock like any engine
        lock: a seeded bare write of a counter the shipped class
        mutates under self._lock is flagged, and the shipped module is
        clean."""
        from generativeaiexamples_tpu.lint import lint_paths

        src_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "generativeaiexamples_tpu",
            "serving", "kv_pager.py")
        with open(src_path) as fh:
            src = fh.read()
        bad = src + textwrap.dedent("""

        class _SeededBadPager(KVPager):
            # Inherits self._lock from KVPager: GL201 must merge
            # same-module base locks and flag the bare write.
            def locked_ok(self):
                with self._lock:
                    self._demotions += 1

            def hack(self):
                self._demotions += 1  # bare write, no tier lock
        """)
        mod = tmp_path / "kv_pager.py"
        mod.write_text(bad)
        findings = [f for f in lint_paths([str(mod)])
                    if f.check == "GL201"]
        assert any("_demotions" in f.message for f in findings)
        assert not [f for f in lint_paths([src_path])
                    if f.check == "GL201"]

    def test_hot_path_markers_cover_pager_functions(self):
        """demote / promote_into / promote / _lookup_prefix carry the
        `# graftlint: hot-path` marker, so GL401 scans them directly
        and GL402 inherits everything they call."""
        from generativeaiexamples_tpu.lint import callgraph
        from generativeaiexamples_tpu.lint.checks import host_sync
        from generativeaiexamples_tpu.lint.core import load_project

        pkg = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "generativeaiexamples_tpu")
        project = load_project([pkg])
        graph = callgraph.build(project)
        hot_keys = host_sync.hot_root_keys(graph)
        names = {graph.nodes[k].module + ":" + graph.nodes[k].name
                 for k in hot_keys}
        assert "kv_pager.py:demote" in names
        assert "kv_pager.py:promote_into" in names
        assert "kv_pager.py:promote" in names
        assert "engine.py:_lookup_prefix" in names
        # ...and the inferred closure reaches the helpers they call.
        hot = host_sync.inferred_hot(graph)
        inferred = {graph.nodes[k].module + ":" + graph.nodes[k].name
                    for k in hot}
        assert "kv_pager.py:_store_locked" in inferred
