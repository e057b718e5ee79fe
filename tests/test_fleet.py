"""Serving fleet: prefix-locality router over data-parallel replicas.

Covers the placement policy units (locality beats load-only on a
replayed conversation, session affinity, stable-hash fallback), shadow
-tree consistency under real cache eviction, health-eviction with
requeue, graceful drain, the always-present counter surface, and the
N-thread end-to-end gate: a 2-replica fleet's streams are
byte-identical to a single engine's.
"""

import os
import queue
import textwrap
import threading
import time

import jax
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.fleet import (
    EngineFleet, FleetUnavailableError, LocalReplica, sse_json_events)
from generativeaiexamples_tpu.serving.kv_cache import PageAllocator
from generativeaiexamples_tpu.serving.prefix_cache import RadixPrefixCache
from generativeaiexamples_tpu.serving.router import (
    PrefixLocalityRouter, ShadowRadixTree)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PS = 8  # page size used throughout


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def make_engine(params, **over):
    cfg = dict(max_batch_size=2, max_seq_len=256, page_size=PS,
               prefill_buckets=(16, 32), prefix_cache=True,
               pace_emission_max_streams=0)
    cfg.update(over)
    return LLMEngine(params, TINY, ByteTokenizer(), EngineConfig(**cfg),
                     use_pallas=False)


def make_fleet(params, n=2, **fleet_kw):
    engines = [make_engine(params) for _ in range(n)]
    reps = [LocalReplica(f"r{i}", e) for i, e in enumerate(engines)]
    fleet = EngineFleet(reps, ByteTokenizer(), PS, **fleet_kw).start()
    return fleet, engines


def collect(req, timeout=120):
    toks = []
    while True:
        ev = req.stream.get(timeout=timeout)
        if ev["token_id"] >= 0:
            toks.append(ev["token_id"])
        if ev["finished"]:
            return toks, ev["finish_reason"]


def run_one(target, prompt, session="", max_new=16):
    req = GenRequest(prompt_ids=list(prompt), max_new_tokens=max_new,
                     session_id=session)
    target.submit(req)
    return collect(req)[0]


# ---------------------------------------------------------------------------
# router policy units (no engines)
# ---------------------------------------------------------------------------

class TestPlacementPolicy:
    def _router(self, policy="prefix", **kw):
        r = PrefixLocalityRouter(PS, policy=policy, **kw)
        r.add_replica("r0", self_feed=False)
        r.add_replica("r1", self_feed=False)
        return r

    def test_locality_beats_load_only_on_replayed_conversation(self):
        """Turn 2 of a conversation goes back to the replica holding
        its prefix KV even though it is the DEEPER queue; a load-only
        policy sends it to the shallow one and re-prefills from zero."""
        turn1 = list(range(40))
        turn2 = turn1 + [99] * 24
        for policy, expect in (("prefix", "r0"), ("least_load", "r1")):
            r = self._router(policy, load_penalty_tokens=8)
            # Replica r0 cached turn 1 (admission report), then got busy.
            r.reporter_for("r0")("insert", tuple(turn1))
            for _ in range(3):
                r.note_submitted("r0", 16)
            assert r.place(turn2) == expect, policy
        r = self._router("prefix", load_penalty_tokens=8)
        r.reporter_for("r0")("insert", tuple(turn1))
        for _ in range(3):
            r.note_submitted("r0", 16)
        r.place(turn2)
        snap = r.snapshot()
        assert snap["router_prefix_hits"] == 1
        # 40 prompt tokens = 5 full pages of locality credited.
        assert snap["router_hit_tokens"] == 40

    def test_locality_yields_when_owner_is_drowning(self):
        """A cached prefix stops winning once its replica is deeper
        than the skipped prefill is worth."""
        r = self._router("prefix", load_penalty_tokens=16)
        turn1 = list(range(16))
        r.reporter_for("r0")("insert", tuple(turn1))
        for _ in range(8):  # 8 * 16 penalty >> 16 matched tokens
            r.note_submitted("r0", 16)
        assert r.place(turn1 + [5] * 8) == "r1"

    def test_session_affinity_and_ttl(self):
        r = self._router("prefix", affinity_ttl_s=30.0)
        first = r.place([1, 2, 3] * 10, session="alice")
        # A completely different prompt sticks to the session's replica.
        assert r.place([9] * 30, session="alice") == first
        assert r.snapshot()["router_affinity_hits"] == 1
        r2 = self._router("prefix", affinity_ttl_s=0.0)
        r2.place([1, 2, 3] * 10, session="bob")  # expires immediately
        # No affinity hit on the second placement (TTL elapsed).
        r2.place([1, 2, 3] * 10, session="bob")
        assert r2.snapshot()["router_affinity_hits"] == 0

    def test_stable_hash_fallback_converges_and_respects_overload(self):
        r = self._router("prefix")
        cold = [42] * 24
        rids = {r.place(cold) for _ in range(4)}
        assert len(rids) == 1  # identical cold template -> one replica
        (rid,) = rids
        # Drown the hash choice: fallback overrides to least-loaded.
        for _ in range(8):
            r.note_submitted(rid, 16)
        assert r.place(cold) != rid

    def test_no_admitting_replica_raises(self):
        r = self._router()
        r.set_admitting("r0", False)
        r.set_admitting("r1", False)
        with pytest.raises(LookupError):
            r.place([1, 2, 3])

    def test_round_robin_rotates(self):
        r = self._router("round_robin")
        seen = [r.place([1] * 8) for _ in range(4)]
        assert seen[0] != seen[1] and seen[0] == seen[2]


# ---------------------------------------------------------------------------
# shadow-tree consistency
# ---------------------------------------------------------------------------

class TestShadowConsistency:
    def test_shadow_mirrors_cache_insert_and_eviction(self):
        """Wire a real RadixPrefixCache's reporter into a shadow tree:
        after inserts AND LRU evictions the shadow scores exactly what
        the cache still holds."""
        alloc = PageAllocator(64)
        cache = RadixPrefixCache(alloc, PS, capacity_pages=64)
        shadow = ShadowRadixTree(PS, 4096)

        def apply(kind, ids):
            if kind == "insert":
                shadow.insert(ids)
            else:
                shadow.remove_path(ids)

        cache.reporter = apply
        a = list(range(32))            # 4 pages
        b = list(range(16)) + [7] * 16  # shares 2 pages with a
        pa = alloc.alloc(4)
        cache.insert(a, pa)
        pb = alloc.alloc(4)
        cache.insert(b, pb)
        assert shadow.match_tokens(a) == 32
        assert shadow.match_tokens(b) == 32
        # Free the sequences' own references so leaves become evictable,
        # then evict everything the cache holds.
        alloc.release(pa)
        alloc.release(pb[2:])  # pb[:2] were dedup'd duplicates
        evicted = cache.evict(64)
        assert evicted == cache.evictions == 6
        assert shadow.match_tokens(a) == 0
        assert shadow.match_tokens(b) == 0
        assert shadow.n_cached_pages == 0

    def test_remove_path_prunes_deeper_self_fed_subtree(self):
        shadow = ShadowRadixTree(PS, 4096)
        shadow.insert(list(range(32)))
        # Eviction report for the 3rd page: its subtree (page 4) goes too.
        shadow.remove_path(list(range(24)))
        assert shadow.match_tokens(list(range(32))) == 16

    def test_shadow_trim_is_lru(self):
        shadow = ShadowRadixTree(PS, 2)
        shadow.insert([1] * 8)
        shadow.insert([2] * 8)
        shadow.match_tokens([1] * 8)  # touch 1 -> 2 is LRU
        shadow.insert([3] * 8)
        assert shadow.trim() == 1
        assert shadow.match_tokens([2] * 8) == 0
        assert shadow.match_tokens([1] * 8) == 8

    def test_remove_path_then_trim_drops_stale_heap_entries(self):
        """An out-of-band removal (replica eviction report) must mark
        removed nodes dead for the persistent eviction heap: a later
        trim() over fresh inserts used to pop the removed node's stale
        entry and KeyError on the placement path — or, when the same
        chunk was re-inserted first, delete the live twin."""
        shadow = ShadowRadixTree(PS, 2)
        shadow.insert(list(range(PS)))
        shadow.remove_path(list(range(PS)))
        shadow.insert([100 + i for i in range(2 * PS)])
        shadow.insert([200 + i for i in range(2 * PS)])
        assert shadow.trim() == 2  # used to KeyError on the stale entry
        assert shadow.n_cached_pages == 2
        # Re-inserted twin of a removed chunk survives its stale entry.
        twin = ShadowRadixTree(PS, 100)
        twin.insert(list(range(PS)))
        twin.remove_path(list(range(PS)))
        twin.insert(list(range(PS)))
        assert twin.evict(1) == 1 and twin.n_cached_pages == 0

    def test_remove_path_exposes_parent_to_eviction(self):
        """Removing a subtree must re-queue the surviving parent when
        it becomes a frontier leaf. On a 3-deep chain A->B->D,
        evict(1) discards A's and B's heap entries (not frontier),
        evicts D and re-queues only B; a replica eviction report then
        removing B leaves A with NO heap entry — without the re-push
        A is permanently unevictable (trim() evicts fresher nodes
        instead: LRU inversion + unbounded stale growth)."""
        shadow = ShadowRadixTree(PS, 100)
        shadow.insert(list(range(3 * PS)))       # A -> B -> D
        assert shadow.evict(1) == 1              # D out; only B re-queued
        shadow.remove_path(list(range(2 * PS)))  # report drops B
        assert shadow.n_cached_pages == 1        # A survives...
        assert shadow.evict(1) == 1              # ...and is evictable
        assert shadow.n_cached_pages == 0

    def test_fleet_kv_pager_view_sums_replica_stats(self):
        """/health's fleet kv_pager facade: enabled when any local
        replica pages KV, stats summed — never contradicting /metrics
        (which sums the same kv_* keys)."""
        from generativeaiexamples_tpu.serving.fleet import (
            _FleetKVPagerView)

        class _P:
            def __init__(self, n):
                self._n = n

            def stats(self):
                return {"kv_demotions": self._n, "kv_host_pages": 2}

        view = _FleetKVPagerView([_P(3), _P(5)])
        assert view.stats() == {"kv_demotions": 8, "kv_host_pages": 4}


# ---------------------------------------------------------------------------
# fleet lifecycle with fake replicas (no engines)
# ---------------------------------------------------------------------------

class FakeReplica:
    def __init__(self, rid):
        self.rid = rid
        self.state = "active"
        self.has_prefix_cache = False
        self.submitted = []
        self.alive = True
        self.stopped = False

    def set_reporter(self, fn):
        pass

    def submit(self, req):
        self.submitted.append(req)

    def healthy(self):
        return self.alive

    def start(self):
        pass

    def stop(self):
        self.stopped = True

    def warmup(self, **kw):
        pass

    def metrics_snapshot(self):
        return {}


class TestHealthEvictionAndRequeue:
    def _fleet(self, threshold=1):
        # threshold=1 evicts on the first failed probe — these tests
        # exercise eviction mechanics, not the K-consecutive counting
        # (TestProbeThreshold covers that).
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        return EngineFleet(fakes, ByteTokenizer(), PS,
                           health_fail_threshold=threshold).start(), fakes

    def test_dead_replica_evicted_and_waiting_request_requeued(self):
        fleet, fakes = self._fleet()
        req = GenRequest(prompt_ids=[3] * 24, max_new_tokens=8)
        fleet.submit(req)
        victim = next(f for f in fakes if f.submitted)
        other = next(f for f in fakes if not f.submitted)
        victim.alive = False
        health = fleet.check_health()
        assert health[victim.rid] is False and health[other.rid] is True
        assert victim.state == "evicted" and victim.stopped
        # The untouched request moved to the survivor, same stream.
        assert other.submitted == [req]
        snap = fleet.metrics.snapshot()
        assert snap["replica_evictions"] == 1
        assert snap["router_requeued"] == 1
        assert snap["router_rebalances"] == 1
        assert fleet.fleet_health()["replicas"][victim.rid]["state"] == \
            "evicted"
        # Evicted replicas never admit again until restore().
        for _ in range(4):
            r = GenRequest(prompt_ids=[4] * 24, max_new_tokens=8)
            fleet.submit(r)
            assert r in other.submitted

    def test_midstream_request_terminated_not_replayed(self):
        fleet, fakes = self._fleet()
        req = GenRequest(prompt_ids=[5] * 24, max_new_tokens=8)
        fleet.submit(req)
        victim = next(f for f in fakes if f.submitted)
        other = next(f for f in fakes if not f.submitted)
        # Replica delivered one token before dying: replaying would
        # duplicate output, so the stream ends with an error event.
        req.stream.put({"text": "x", "token_id": 7, "finished": False,
                        "finish_reason": None})
        victim.alive = False
        fleet.check_health()
        assert req not in other.submitted
        toks, reason = collect(req, timeout=5)
        assert toks == [7] and reason == "error"

    def test_all_replicas_down_is_unavailable(self):
        fleet, fakes = self._fleet()
        for f in fakes:
            f.alive = False
        fleet.check_health()
        with pytest.raises(FleetUnavailableError):
            fleet.submit(GenRequest(prompt_ids=[1] * 8))


class TestRequeueFidelity:
    """A health-evicted replica's requeued request must keep its QoS
    tier and tenant, and its session must re-pin to the survivor."""

    def test_requeue_keeps_tier_tenant_and_repins_affinity(self):
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        fleet = EngineFleet(fakes, ByteTokenizer(), PS,
                            health_fail_threshold=1).start()
        req = GenRequest(prompt_ids=[3] * 24, max_new_tokens=8,
                         priority="latency", tenant_id="acme",
                         session_id="sess-1")
        fleet.submit(req)
        victim = next(f for f in fakes if f.submitted)
        other = next(f for f in fakes if not f.submitted)
        assert fleet.router._affinity["sess-1"][0] == victim.rid
        victim.alive = False
        fleet.check_health()
        # Moved to the survivor with identity intact...
        assert other.submitted == [req]
        assert req.priority == "latency" and req.tenant_id == "acme"
        # ...tier accounting followed it (the survivor's latency-tier
        # pressure counts the requeued request)...
        assert fleet.router.tier_queue_depths()[other.rid] == \
            {"latency": 1}
        assert fleet.router.tier_queue_depths()[victim.rid] in \
            ({}, {"latency": 0})
        # ...and the session re-pinned to the survivor.
        assert fleet.router._affinity["sess-1"][0] == other.rid
        # A follow-up turn in the session lands there too.
        req2 = GenRequest(prompt_ids=[3] * 24, max_new_tokens=8,
                          priority="latency", tenant_id="acme",
                          session_id="sess-1")
        fleet.submit(req2)
        assert req2 in other.submitted


class TestProbeThreshold:
    """Satellite: K consecutive probe failures before eviction; any
    success resets the count."""

    def _fleet(self, threshold):
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        fleet = EngineFleet(fakes, ByteTokenizer(), PS,
                            health_fail_threshold=threshold).start()
        return fleet, fakes

    def test_eviction_needs_k_consecutive_failures(self):
        fleet, fakes = self._fleet(threshold=3)
        fakes[0].alive = False
        for i in range(2):
            fleet.check_health()
            assert fakes[0].state == "active", f"evicted at {i + 1} < K"
            assert fleet.fleet_health()["replicas"]["r0"]["probe_fails"] \
                == i + 1
        fleet.check_health()  # 3rd consecutive: eviction
        assert fakes[0].state == "evicted"
        assert fleet.metrics.snapshot()["replica_evictions"] == 1

    def test_one_slow_poll_cannot_kill_a_replica(self):
        fleet, fakes = self._fleet(threshold=3)
        fakes[0].alive = False
        fleet.check_health()
        fleet.check_health()  # 2/3
        fakes[0].alive = True  # the replica was merely loaded
        fleet.check_health()   # success resets the count
        assert fleet.fleet_health()["replicas"]["r0"]["probe_fails"] == 0
        fakes[0].alive = False
        fleet.check_health()
        fleet.check_health()  # 2/3 again — still not evicted
        assert fakes[0].state == "active"

    def test_http_probe_uses_short_dedicated_timeout(self):
        """HttpReplica probes ride probe_timeout_s, not the 300 s
        stream timeout — and back the deadline off with consecutive
        failures."""
        from generativeaiexamples_tpu.serving.fleet import HttpReplica

        rep = HttpReplica("h0", "http://127.0.0.1:9", timeout_s=300.0,
                          probe_timeout_s=0.2)
        t0 = time.monotonic()
        assert rep.healthy() is False
        assert time.monotonic() - t0 < 5.0  # not the stream timeout
        assert rep._probe_fails == 1
        assert rep.healthy() is False
        assert rep._probe_fails == 2


class TestStuckThreadJoins:
    def test_stop_counts_threads_alive_after_join_timeout(self, params):
        """A stop()-path join that times out must be counted, not
        silently ignored."""

        class Immortal:
            name = "llm-engine-immortal"

            def join(self, timeout=None):
                pass

            def is_alive(self):
                return True

        eng = make_engine(params)
        eng.start()
        eng.stop()
        assert eng.metrics.stuck_thread_joins == 0
        eng._reader = Immortal()
        eng.stop()
        assert eng.metrics.stuck_thread_joins == 1
        assert eng.metrics.snapshot()["stuck_thread_joins"] == 1

    def test_fleet_sums_engine_stuck_joins(self):
        class StuckFake(FakeReplica):
            def metrics_snapshot(self):
                return {"stuck_thread_joins": 2}

        fleet = EngineFleet([StuckFake("r0"), FakeReplica("r1")],
                            ByteTokenizer(), PS)
        assert fleet.metrics.snapshot()["stuck_thread_joins"] == 2
        # The fleet's own control-thread stuck joins add on top.
        fleet.ops.note_stuck_join()
        assert fleet.metrics.snapshot()["stuck_thread_joins"] == 3


# ---------------------------------------------------------------------------
# end-to-end over real engines (CPU, tiny model)
# ---------------------------------------------------------------------------

class TestFleetE2E:
    def test_nthread_streams_byte_identical_to_single_engine(self, params):
        """The fleet acceptance gate: N threads of greedy traffic
        through 2 replicas produce exactly the single-engine streams,
        and a replayed conversation turn scores a router prefix hit."""
        single = make_engine(params).start()
        prompts = [[(7 * i + j) % 250 + 1 for j in range(20 + 2 * i)]
                   for i in range(6)]
        want = [run_one(single, p) for p in prompts]
        single.stop()

        fleet, engines = make_fleet(params)
        try:
            got = [None] * len(prompts)
            errs = []

            def worker(i):
                try:
                    got[i] = run_one(fleet, prompts[i])
                except Exception as e:  # surfaced below
                    errs.append(e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errs
            assert got == want
            snap = fleet.metrics.snapshot()
            assert snap["router_requests"] == len(prompts)
            assert set(snap["router_queue_depth"]) == {"r0", "r1"}
            assert all(v == 0 for v in snap["router_queue_depth"].values())
        finally:
            fleet.stop()

    def test_conversation_replay_hits_same_replica(self, params):
        fleet, engines = make_fleet(params)
        try:
            turn1 = [11] * 40
            out1 = run_one(fleet, turn1, session="s1")
            turn2 = turn1 + out1 + [13] * 8
            run_one(fleet, turn2, session="s1")
            snap = fleet.metrics.snapshot()
            assert snap["router_prefix_hits"] >= 1
            assert snap["router_hit_tokens"] >= 40
            # The ENGINE-level cache hit proves the router sent turn 2
            # to the replica that really holds the KV pages.
            assert sum(e.metrics.prefix_hits for e in engines) == 1
            assert snap["prefix_hits"] == 1  # aggregated surface
        finally:
            fleet.stop()

    def test_restore_after_evict_restarts_local_engine(self, params):
        """Evicting a dead local replica stops its engine; restore()
        must actually RESTART the scheduler (the stop leaves the joined
        thread object behind), or re-admitted traffic would queue on a
        parked engine forever."""
        fleet, engines = make_fleet(params, health_fail_threshold=1)
        try:
            engines[0].stop()  # dies out from under the fleet
            assert fleet.check_health()["r0"] is False
            assert fleet.fleet_health()["replicas"]["r0"]["state"] == \
                "evicted"
            fleet.restore("r0")
            assert engines[0]._running and engines[0]._thread.is_alive()
            # Drain r1 so traffic MUST land on the restored replica.
            fleet.drain("r1", timeout_s=60.0)
            assert run_one(fleet, [3] * 16, max_new=8)
        finally:
            fleet.stop()

    def test_evict_requeues_and_purges_dead_queue(self, params):
        """A request parked in a dead replica's waiting deque is
        requeued to a survivor AND purged from the dead engine, so a
        later restore() cannot replay it into the survivor's stream."""
        fleet, engines = make_fleet(params, router_policy="round_robin",
                                    health_fail_threshold=1)
        try:
            engines[0].stop()  # r0's scheduler parks; deque accumulates
            reqs = [GenRequest(prompt_ids=[i + 3] * 16, max_new_tokens=6)
                    for i in range(2)]
            for r in reqs:
                fleet.submit(r)
            assert len(engines[0].waiting) == 1  # round-robin -> one on r0
            fleet.check_health()  # evicts r0, requeues its request to r1
            assert not engines[0].waiting  # purged
            fleet.restore("r0")
            for r in reqs:
                toks, reason = collect(r, timeout=120)
                assert toks and reason != "error"
                assert r.stream.empty()  # exactly one terminal, no replay
        finally:
            fleet.stop()

    def test_graceful_drain_finishes_inflight_stream(self, params):
        fleet, engines = make_fleet(params)
        try:
            req = GenRequest(prompt_ids=[9] * 24, max_new_tokens=64)
            fleet.submit(req)
            rid = next(r for r, d in
                       fleet.router.queue_depths().items() if d)
            done = fleet.drain(rid, timeout_s=120.0)
            assert done
            toks, reason = collect(req, timeout=5)
            assert len(toks) == 64 or reason == "stop"
            assert reason != "error"
            assert fleet.fleet_health()["replicas"][rid]["state"] == \
                "drained"
            # Drained replica admits nothing; traffic flows to the other.
            other = run_one(fleet, [8] * 16)
            assert other  # served
            assert fleet.router.queue_depths()[rid] == 0
            assert fleet.metrics.snapshot()["router_rebalances"] == 1
            # restore() re-admits it.
            fleet.restore(rid)
            assert fleet.fleet_health()["replicas"][rid]["state"] == \
                "active"
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# disaggregated prefill/decode (serving/disagg.py)
# ---------------------------------------------------------------------------

class TestDisaggPlacement:
    """Two-stage placement units (no engines)."""

    def _router(self, roles=("prefill", "decode"), **kw):
        r = PrefixLocalityRouter(PS, **kw)
        for i, role in enumerate(roles):
            r.add_replica(f"r{i}", self_feed=True, role=role)
        return r

    def test_prefill_role_never_receives_decode_placement(self):
        r = self._router(("prefill", "decode", "mixed"))
        for i in range(16):
            rid = r.place([i] * 24, session=f"s{i}")
            assert r.roles()[rid] != "prefill"
        # With ONLY prefill-role replicas admitting, decode placement
        # has nowhere to go — 503, not a silent prefill-side decode.
        lone = self._router(("prefill",))
        with pytest.raises(LookupError):
            lone.place([1] * 24)

    def test_place_disagg_emits_two_stage_plan(self):
        r = self._router(("prefill", "decode"))
        plan = r.place_disagg([7] * 24)
        assert plan == ("r0", "r1")
        assert r.snapshot()["router_disagg_plans"] == 1
        # One placement's worth of bookkeeping, not two.
        assert r.snapshot()["router_requests"] == 1

    def test_place_disagg_colocated_when_decode_holds_prefix(self):
        """A decode replica already shadowing the full-page prefix
        serves colocated — the transfer would move bytes it has. The
        shadow-coverage check must read the PRE-placement state (a
        self-feeding shadow absorbs the prompt during placement)."""
        r = self._router(("prefill", "decode"))
        prompt = [5] * 24
        plan = r.place_disagg(prompt)
        assert plan == ("r0", "r1")  # first sight: transfer
        plan2 = r.place_disagg(prompt)
        assert plan2 == ("", "r1")   # replay: the prefix is there
        assert r.snapshot()["router_disagg_plans"] == 1

    def test_place_disagg_none_without_prefill_role(self):
        r = self._router(("decode", "mixed"))
        assert r.place_disagg([3] * 24) is None

    def test_place_disagg_subpage_prompt_is_colocated(self):
        r = self._router(("prefill", "decode"))
        prid, drid = r.place_disagg([1] * (PS - 1))
        assert prid == "" and drid == "r1"
        assert r.snapshot()["router_disagg_plans"] == 0


class TestDisaggE2E:
    def _pair(self, params, **fleet_kw):
        reps = [LocalReplica("r0", make_engine(params), role="prefill"),
                LocalReplica("r1", make_engine(params), role="decode")]
        fleet = EngineFleet(reps, ByteTokenizer(), PS, disagg=True,
                            **fleet_kw).start()
        return fleet, reps

    def test_two_stage_streams_byte_identical(self, params):
        """The acceptance gate: disagg streams equal colocated greedy,
        pages move, and the decode replica's radix tree gains the
        transferred prefix (its engine scores a real prefix hit)."""
        prompts = [[(7 * i + j) % 250 + 1 for j in range(20 + 4 * i)]
                   for i in range(3)]
        single = make_engine(params).start()
        want = [run_one(single, p) for p in prompts]
        single.stop()
        fleet, reps = self._pair(params)
        try:
            got = [run_one(fleet, p) for p in prompts]
            assert got == want
            snap = fleet.metrics.snapshot()
            assert snap["kv_transfer_pages"] > 0
            assert snap["kv_transfer_ms"] > 0
            assert snap["router_disagg_plans"] == len(prompts)
            assert snap["disagg_requests"] == len(prompts)
            assert snap["disagg_fallbacks"] == 0
            # Decode tree gained each transferred prefix -> hit path.
            assert reps[1].engine.prefix_cache.n_cached_pages > 0
            assert reps[1].engine.metrics.prefix_hits == len(prompts)
            # Prefill role never decoded a client stream: exactly one
            # stage token per plan.
            assert reps[0].engine.metrics.tokens_out == len(prompts)
            health = fleet.fleet_health()
            assert health["disagg"]["enabled"] is True
            assert health["disagg"]["plans"] == len(prompts)
            assert health["replicas"]["r0"]["role"] == "prefill"
        finally:
            fleet.stop()

    def test_transfer_failure_falls_back_colocated_same_stream(
            self, params):
        prompt = [9] * 24
        single = make_engine(params).start()
        want = run_one(single, prompt)
        single.stop()
        fleet, reps = self._pair(params)

        def broken(ids, codes, scales, timeout_s=60.0):
            raise RuntimeError("injected transfer fault")

        reps[1].import_kv_pages = broken
        try:
            assert run_one(fleet, prompt) == want
            snap = fleet.metrics.snapshot()
            assert snap["disagg_fallbacks"] == 1
            assert snap["kv_transfer_pages"] == 0
        finally:
            fleet.stop()

    def test_prefill_stage_bails_fast_when_replica_evicted(self):
        """The internal prefill stage carries no _ReqRecord, so an
        eviction delivers it no terminal event — the wait loop must
        notice the replica state and fall back NOW, not after the
        full disagg_prefill_timeout_s."""
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        fakes[0].role, fakes[1].role = "prefill", "decode"
        fleet = EngineFleet(fakes, ByteTokenizer(), PS, disagg=True,
                            disagg_prefill_timeout_s=30.0).start()
        try:
            req = GenRequest(prompt_ids=[3] * 24, max_new_tokens=4)

            def evict_soon():
                time.sleep(0.3)
                with fleet._lock:
                    fakes[0].state = "evicted"

            threading.Thread(target=evict_soon).start()
            t0 = time.monotonic()
            fleet.submit(req)  # fake replicas emit nothing; the stage
            elapsed = time.monotonic() - t0
            assert elapsed < 10.0, f"stage spun {elapsed:.1f}s"
            assert fleet.metrics.snapshot()["disagg_fallbacks"] == 1
            # The client request itself still dispatched (to r1).
            assert req in fakes[1].submitted
        finally:
            fleet.stop()

    def test_decode_load_reserved_during_stage_window(self):
        """Concurrent disagg placements must see the planned decode
        replica's load DURING the prefill/transfer window, not only
        after the decode dispatch."""
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        fakes[0].role, fakes[1].role = "prefill", "decode"
        fleet = EngineFleet(fakes, ByteTokenizer(), PS,
                            disagg=True).start()
        try:
            seen = {}

            def spy(prid, drid, req):
                seen["depth"] = fleet.router.queue_depths()[drid]
                return False

            fleet._run_disagg_stages = spy
            fleet.submit(GenRequest(prompt_ids=[6] * 24,
                                    max_new_tokens=4))
            assert seen["depth"] == 1  # the reservation, mid-stage
            # ...and it was released: depth now reflects only the
            # real dispatch's tracking record.
            assert fleet.router.queue_depths()["r1"] == 1
        finally:
            fleet.stop()

    def test_min_prompt_tokens_keeps_shorts_on_decode_pool(self, params):
        fleet, reps = self._pair(params, disagg_min_prompt_tokens=64)
        try:
            assert run_one(fleet, [4] * 24)  # short: below the bar
            snap = fleet.metrics.snapshot()
            assert snap["router_disagg_plans"] == 0
            assert snap["disagg_requests"] == 0
            # ...and it served on the decode replica, not the prefill
            # one (role discipline holds for colocated shorts too).
            assert reps[0].engine.metrics.tokens_out == 0
            assert reps[1].engine.metrics.tokens_out > 0
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# surfaces
# ---------------------------------------------------------------------------

class TestCounterSurfaces:
    def test_single_engine_snapshot_carries_router_zeros(self, params):
        eng = make_engine(params)
        snap = eng.metrics.snapshot()
        for key in ("router_requests", "router_prefix_hits",
                    "router_hit_tokens", "router_affinity_hits",
                    "router_rebalances", "replica_evictions",
                    "router_requeued", "router_disagg_plans",
                    "kv_transfer_pages", "kv_transfer_ms",
                    "disagg_requests", "disagg_fallbacks"):
            assert snap[key] == 0
        assert snap["router_queue_depth"] == {}

    def test_fleet_snapshot_shape(self):
        fleet = EngineFleet([FakeReplica("r0"), FakeReplica("r1")],
                            ByteTokenizer(), PS)
        snap = fleet.metrics.snapshot()
        assert set(snap["per_replica"]) == {"r0", "r1"}
        assert snap["router_requests"] == 0
        assert snap["tokens_generated"] == 0

    def test_fleet_merges_histograms_and_flight_counters(self, params):
        """Fleet aggregation of the flight surface: hist_* keys merge
        element-wise across replicas (fleet TTFT percentiles come from
        the MERGED histogram), flight_beats/events sum, and every key
        is present even when idle."""
        from generativeaiexamples_tpu.serving import flight as flight_mod

        fleet, engines = make_fleet(params)
        try:
            # Distinct sessions so both replicas serve traffic.
            for i in range(4):
                run_one(fleet, [3 + i, 5, 7, 9], session=f"s{i}",
                        max_new=4)
            # Quiesce: pipelined blocks can still land AFTER the last
            # stream's terminal event — the fleet-vs-replica sum
            # comparison below needs both sides frozen.
            deadline = time.monotonic() + 30
            while any(e._inflight or any(s is not None for s in e.slots)
                      for e in engines):
                assert time.monotonic() < deadline
                time.sleep(0.01)
            time.sleep(0.05)
            snap = fleet.metrics.snapshot()
            for key in flight_mod.HIST_KEYS:
                assert "count" in snap[key] and "buckets" in snap[key]
            per = [engines[0].metrics.snapshot(),
                   engines[1].metrics.snapshot()]
            assert snap["hist_ttft_ms"]["count"] == \
                sum(s["hist_ttft_ms"]["count"] for s in per) == 4
            assert snap["flight_beats"] == \
                sum(s["flight_beats"] for s in per) > 0
            assert snap["flight_events"] == \
                sum(s["flight_events"] for s in per)
            assert snap["flight_enabled"] == 1
            assert snap["ttft_p50_ms"] is not None
            # Process-global monotonic counter (other tests exercise
            # tracing failure paths in-process): present, not zero.
            assert snap["trace_export_errors"] >= 0
            # The fleet's /debug/timeline lanes: one per local replica
            # plus the control-plane lane (fleet upgrades; autoscaler/
            # chaos lanes join it when attached).
            recs = fleet.flight_recorders()
            assert set(recs) == {"r0", "r1", "fleet"}
            trace = flight_mod.chrome_trace(recs)
            assert {e["pid"] for e in trace["traceEvents"]} == {0, 1, 2}
        finally:
            fleet.stop()

    def test_fleet_hist_merge_tolerates_missing_keys(self):
        """Remote replicas that predate the histogram surface (or
        error snapshots) contribute nothing instead of crashing."""
        fleet = EngineFleet([FakeReplica("r0"), FakeReplica("r1")],
                            ByteTokenizer(), PS)
        snap = fleet.metrics.snapshot()
        assert snap["hist_ttft_ms"]["count"] == 0
        assert snap["ttft_p50_ms"] is None
        assert snap["flight_beats"] == 0

    def test_sse_event_parser(self):
        lines = [
            b'data: {"choices": [{"text": "he", "finish_reason": null}]}\n',
            b"\n",
            b": comment\n",
            b'data: {"choices": [{"text": "y", "finish_reason": "stop"}]}\n',
            b"data: [DONE]\n",
            b'data: {"never": "reached"}\n',
        ]
        evs = list(sse_json_events(iter(lines)))
        assert [e["choices"][0]["text"] for e in evs] == ["he", "y"]


class TestLintCoverage:
    def test_gl201_covers_router_replica_state_lock(self, tmp_path):
        """GL201's lock-discipline check must treat the router's
        replica-state lock like any engine lock: a seeded bare write of
        a counter that place() mutates under self._lock is flagged."""
        from generativeaiexamples_tpu.lint import lint_paths

        src_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "generativeaiexamples_tpu",
            "serving", "router.py")
        with open(src_path) as fh:
            src = fh.read()
        bad = src + textwrap.dedent("""

        class _SeededBadRouter(PrefixLocalityRouter):
            # Inherits self._lock from PrefixLocalityRouter: GL201 must
            # merge same-module base locks and flag the bare write.
            def locked_ok(self):
                with self._lock:
                    self.router_requests += 1

            def hack(self):
                self.router_requests += 1  # bare write, no lock
        """)
        mod = tmp_path / "router.py"
        mod.write_text(bad)
        findings = [f for f in lint_paths([str(mod)])
                    if f.check == "GL201"]
        assert any("router_requests" in f.message for f in findings)
        # ... and the shipped router itself is clean.
        assert not [f for f in lint_paths([src_path])
                    if f.check == "GL201"]
