"""SLO-aware multi-tenant QoS (serving/qos.py + engine.qos).

Engine tests drive the scheduler INLINE (the test_fused_prefill idiom):
the dispatch schedule is then a pure function of engine state, so
preempted-vs-unpreempted runs see identical chunk programs and their
token streams compare exactly.
"""

import asyncio
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig, ServingConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving.engine import (
    MAX_ADMISSION_RETRIES, GenRequest, LLMEngine)
from generativeaiexamples_tpu.serving.qos import (
    EdgeAdmission, TierScheduler, bursty_trace, goodput, normalize_tier)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.PRNGKey(3))


def _engine(**kw):
    n_pages = kw.pop("n_pages", None)
    base = dict(max_batch_size=2, max_seq_len=256, page_size=8,
                prefill_buckets=(16,), decode_steps_per_dispatch=2,
                pace_emission_max_streams=0)
    base.update(kw)
    return LLMEngine(PARAMS, TINY, ByteTokenizer(), EngineConfig(**base),
                     n_pages=n_pages, use_pallas=False)


def _step(eng):
    """One deterministic scheduler iteration (mirrors _loop's body,
    single-threaded)."""
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
            out.append(req.stream.get_nowait())
        except queue.Empty:
            return out


def _toks(req):
    return [e["token_id"] for e in _drain(req) if e["token_id"] >= 0]


def _run_until_idle(eng, max_steps=500):
    for _ in range(max_steps):
        _step(eng)
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._long_prefills and not eng._inflight
                and not eng._pending_first):
            return
    raise AssertionError("engine did not go idle")


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------

class TestTierScheduler:
    def test_latency_wins_at_equal_service(self):
        sched = TierScheduler()
        waiting = [GenRequest(prompt_ids=[1], priority="batch"),
                   GenRequest(prompt_ids=[1], priority="latency"),
                   GenRequest(prompt_ids=[1], priority="standard")]
        assert waiting[sched.pick(waiting)].priority == "latency"

    def test_weighted_share_never_starves_batch(self):
        # Simulate sustained latency load: after enough latency service
        # the batch tier's normalized service is lower and it MUST win
        # the next admission — the starvation bound is structural.
        sched = TierScheduler()
        lat = GenRequest(prompt_ids=[1] * 8, max_new_tokens=8,
                         priority="latency")
        bat = GenRequest(prompt_ids=[1] * 8, max_new_tokens=8,
                         priority="batch")
        picks = []
        for _ in range(18):
            waiting = [lat, bat]
            i = sched.pick(waiting)
            picks.append(waiting[i].priority)
            sched.note_admitted(waiting[i])
        assert "batch" in picks
        # ... and latency still gets the supermajority of admissions.
        assert picks.count("latency") > picks.count("batch")

    def test_tenant_fairness_within_tier(self):
        sched = TierScheduler()
        a = GenRequest(prompt_ids=[1] * 64, max_new_tokens=64,
                       priority="latency", tenant_id="a")
        sched.note_admitted(a)  # tenant a has been served a lot
        waiting = [GenRequest(prompt_ids=[1], priority="latency",
                              tenant_id="a"),
                   GenRequest(prompt_ids=[1], priority="latency",
                              tenant_id="b")]
        assert waiting[sched.pick(waiting)].tenant_id == "b"

    def test_fifo_within_tenant_and_weight_floor(self):
        sched = TierScheduler({"latency": 0})  # floored to 1, not off
        assert sched.weights["latency"] == 1
        waiting = [GenRequest(prompt_ids=[1], priority="latency",
                              tenant_id="a", request_id="first"),
                   GenRequest(prompt_ids=[1], priority="latency",
                              tenant_id="a", request_id="second")]
        assert waiting[sched.pick(waiting)].request_id == "first"

    def test_idle_tier_gets_no_catchup_credit(self):
        # Start-time fair queuing: an hour of latency-only service must
        # not buy a later batch flood a strict-priority catch-up window
        # (served[] is floored to the virtual time on the idle ->
        # backlogged transition). Without the floor, batch would win
        # EVERY pick here until it caught up ~1/8 of latency's total.
        sched = TierScheduler()
        lat = GenRequest(prompt_ids=[1] * 8, max_new_tokens=8,
                         priority="latency")
        bat = GenRequest(prompt_ids=[1] * 8, max_new_tokens=8,
                         priority="batch")
        for _ in range(1000):  # long latency-only history
            sched.pick([lat])
            sched.note_admitted(lat)
        picks = []
        for _ in range(18):  # batch arrives; both backlogged from now on
            waiting = [lat, bat]
            i = sched.pick(waiting)
            picks.append(waiting[i].priority)
            sched.note_admitted(waiting[i])
        assert picks.count("latency") > picks.count("batch")
        assert "batch" in picks  # still gets its weighted share

    def test_pick_window_bounds_scan(self):
        sched = TierScheduler()
        waiting = [GenRequest(prompt_ids=[1], priority="batch")
                   for _ in range(sched.PICK_WINDOW + 50)]
        waiting.append(GenRequest(prompt_ids=[1], priority="latency"))
        # The latency request sits beyond the window: the pick stays
        # inside the head (FIFO entry into the window), O(window).
        assert sched.pick(waiting) == 0

    def test_normalize_tier(self):
        assert normalize_tier("LATENCY ") == "latency"
        assert normalize_tier("") == "standard"
        assert normalize_tier("gold") == "standard"
        assert normalize_tier(None) == "standard"


class TestEdgeAdmission:
    def test_bound_sheds_with_retry_after(self):
        edge = EdgeAdmission(bounds={"latency": 2}, retry_after_s=3.0,
                             enabled=True)
        assert edge.try_admit("latency") is None
        assert edge.try_admit("latency") is None
        assert edge.try_admit("latency") == 3.0
        # Other tiers are unbounded (0) and unaffected.
        assert edge.try_admit("batch") is None
        edge.release("latency")
        assert edge.try_admit("latency") is None
        snap = edge.snapshot()
        assert snap["qos_shed_latency"] == 1
        assert snap["qos_shed_total"] == 1
        # 2 admits - 1 release + 1 re-admit (the shed never counted).
        assert snap["qos_edge_depth"]["latency"] == 2

    def test_disabled_admits_everything_but_tracks_depth(self):
        edge = EdgeAdmission(bounds={"latency": 1}, enabled=False)
        for _ in range(5):
            assert edge.try_admit("latency") is None
        snap = edge.snapshot()
        assert snap["qos_shed_total"] == 0
        assert snap["qos_edge_depth"]["latency"] == 5


class TestTrace:
    def test_seeded_and_replayable(self):
        a = bursty_trace(seed=5)
        b = bursty_trace(seed=5)
        assert a == b
        assert a != bursty_trace(seed=6)

    def test_shapes_and_bounds(self):
        tr = bursty_trace(seed=1, batch_requests=4)
        tiers = {r.tier for r in tr}
        assert tiers == {"batch", "latency"}
        assert sum(1 for r in tr if r.tier == "batch") == 4
        for r in tr:
            assert r.prompt_len >= 1 and r.max_new_tokens >= 1
            if r.tier == "batch":
                assert 48 <= r.prompt_len <= 220
            else:
                assert 6 <= r.prompt_len <= 24
        assert [r.t for r in tr] == sorted(r.t for r in tr)

    def test_goodput_counts_shed_and_error_against(self):
        res = [{"tier": "latency", "shed": True, "error": False,
                "ttft_s": None, "gap_p95_s": None, "wall_s": 0},
               {"tier": "latency", "shed": False, "error": False,
                "ttft_s": 0.1, "gap_p95_s": 0.0, "wall_s": 1.0}]
        g = goodput(res, {"latency": {"ttft_s": 1.0}})
        assert g["latency"] == 0.5


# ---------------------------------------------------------------------------
# engine scheduling
# ---------------------------------------------------------------------------

class TestEngineQos:
    def test_qos_off_is_fifo_and_counters_zero_but_present(self):
        # max_batch 1 serializes admissions, so completion order IS
        # admission order: FIFO must follow submission order even when
        # a latency request arrives behind a batch one.
        eng = _engine(max_batch_size=1)
        assert eng.qos is None
        reqs = [GenRequest(prompt_ids=[3, 4], max_new_tokens=2,
                           priority="batch"),
                GenRequest(prompt_ids=[5, 6], max_new_tokens=2,
                           priority="latency"),
                GenRequest(prompt_ids=[7, 8], max_new_tokens=2)]
        done = []
        for r in reqs:
            eng.submit(r)
        for _ in range(200):
            _step(eng)
            for i, r in enumerate(reqs):
                if i not in done and any(e["finished"] for e in _drain(r)):
                    done.append(i)
            if len(done) == 3:
                break
        assert done == [0, 1, 2]
        snap = eng.metrics.snapshot()
        assert snap["qos_preemptions"] == 0
        assert snap["admission_failures"] == 0
        assert snap["qos_queue_depth"] == {"latency": 0, "standard": 0,
                                           "batch": 0}

    def test_qos_on_prioritizes_latency_over_queued_batch(self):
        eng = _engine(max_batch_size=1, qos=True)
        first = GenRequest(prompt_ids=[3, 4], max_new_tokens=2)
        batch = GenRequest(prompt_ids=[5, 6], max_new_tokens=2,
                           priority="batch")
        lat = GenRequest(prompt_ids=[7, 8], max_new_tokens=2,
                         priority="latency")
        eng.submit(first)
        _step(eng)          # first takes the only slot
        eng.submit(batch)   # queued first...
        eng.submit(lat)     # ...but latency must be admitted next
        assert eng.metrics.snapshot()["qos_queue_depth"] == {
            "latency": 1, "standard": 0, "batch": 1}
        done = []
        for _ in range(200):
            _step(eng)
            for name, r in (("first", first), ("batch", batch),
                            ("lat", lat)):
                if name not in done and any(e["finished"]
                                            for e in _drain(r)):
                    done.append(name)
            if len(done) == 3:
                break
        assert done == ["first", "lat", "batch"]

    def test_uniform_traffic_qos_on_equals_fifo(self):
        # All-standard single-tenant traffic: the weighted-fair pick
        # degenerates to arrival order, so qos on is byte-identical to
        # the FIFO path on the same inline schedule.
        def run(qos):
            eng = _engine(qos=qos)
            reqs = [GenRequest(prompt_ids=[3 + i, 4 + i], max_new_tokens=6)
                    for i in range(4)]
            for r in reqs:
                eng.submit(r)
            _run_until_idle(eng)
            return [_toks(r) for r in reqs]

        assert run(False) == run(True)

    def test_preempted_prefill_resumes_byte_identical(self):
        long_prompt = [(i * 7) % TINY.vocab_size for i in range(200)]

        def run(arrival):
            eng = _engine(qos=True)
            bat = GenRequest(prompt_ids=long_prompt, max_new_tokens=4,
                             priority="batch")
            eng.submit(bat)
            for _ in range(2):
                _step(eng)
            lat = None
            if arrival:
                lat = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=8,
                                 priority="latency")
                eng.submit(lat)
            _run_until_idle(eng)
            return (_toks(bat), _toks(lat) if lat else None,
                    eng.metrics.snapshot())

        b_plain, _, m_plain = run(arrival=False)
        b_preempt, l_toks, m_preempt = run(arrival=True)
        # The latency arrival paused the in-progress chunked prefill...
        assert m_preempt["qos_preemptions"] >= 1
        assert m_plain["qos_preemptions"] == 0
        # ...and the resumed prefill's stream is byte-identical to the
        # never-paused run AND to the offline greedy continuation —
        # pausing moves WHEN chunks dispatch, never what they compute.
        assert b_preempt == b_plain
        want = np.asarray(llama.greedy_generate(
            PARAMS, TINY, jnp.asarray([long_prompt]), 4))[0, 200:]
        np.testing.assert_array_equal(b_preempt, want)
        assert l_toks and len(l_toks) == 8

    def test_latency_tier_prefill_never_pauses_itself(self):
        eng = _engine(qos=True)
        lat_long = GenRequest(
            prompt_ids=[(i * 3) % 250 for i in range(100)],
            max_new_tokens=2, priority="latency")
        eng.submit(lat_long)
        for _ in range(3):
            _step(eng)
            for lp in eng._long_prefills:
                assert not lp.paused
        _run_until_idle(eng)
        assert eng.metrics.snapshot()["qos_preemptions"] == 0

    def test_batch_progresses_under_sustained_latency_load(self):
        # The starvation bound: keep >= 2 latency requests waiting at
        # all times; a batch request must still finish.
        eng = _engine(max_batch_size=1, qos=True)
        batch = GenRequest(prompt_ids=[9, 10], max_new_tokens=4,
                           priority="batch", tenant_id="flood-victim")
        eng.submit(batch)  # behind a latency stream once one is live
        live = []
        finished = False
        for step in range(300):
            while len([r for r in live
                       if not any(e.get("finished")
                                  for e in r._seen)]) < 2:
                r = GenRequest(prompt_ids=[11, 12], max_new_tokens=2,
                               priority="latency", tenant_id="chatty")
                r._seen = []
                eng.submit(r)
                live.append(r)
            _step(eng)
            for r in live:
                r._seen.extend(_drain(r))
            if any(e.get("finished") for e in _drain(batch)):
                finished = True
                break
        assert finished, "batch tier starved under latency load"

    def test_admission_fails_never_fitting_request_fast(self):
        # n_pages=4 total (3 usable past the sink): a 100-token prompt
        # needs 13 pages and can NEVER be admitted — it must fail with
        # an error event on its first attempt (no amount of draining
        # helps) and traffic behind it must then flow.
        eng = _engine(n_pages=4)
        poison = GenRequest(prompt_ids=list(range(1, 101)),
                            max_new_tokens=2)
        small = GenRequest(prompt_ids=[5, 6], max_new_tokens=2)
        eng.submit(poison)
        eng.submit(small)
        events = []
        for _ in range(10):
            _step(eng)
            events.extend(_drain(poison))
            if events:
                break
        assert events and events[-1]["finished"]
        assert events[-1]["finish_reason"] == "error"
        assert eng.metrics.snapshot()["admission_failures"] >= 1
        for _ in range(100):
            _step(eng)
            evs = _drain(small)
            if any(e["finished"] for e in evs):
                assert all(e["finish_reason"] != "error" for e in evs
                           if e["finished"])
                break
        else:
            raise AssertionError("request behind poison never served")

    def test_waiting_behind_live_decode_is_not_failed(self):
        # A request that fits the pool but must wait for pages held by
        # a live stream is a QUEUE, not a failure: attempts advance
        # only while nothing in flight could free pages, so it admits
        # once the holder retires — however many beats that takes.
        eng = _engine(n_pages=8, max_batch_size=2)  # 7 usable pages
        holder = GenRequest(prompt_ids=list(range(1, 41)),
                            max_new_tokens=8)   # 5-6 pages while live
        waiter = GenRequest(prompt_ids=list(range(1, 31)),
                            max_new_tokens=2)   # needs 4: must wait
        eng.submit(holder)
        _step(eng)
        eng.submit(waiter)
        finished = []
        for _ in range(200):
            _step(eng)
            finished += [e for e in _drain(waiter) if e["finished"]]
            if finished:
                break
        assert finished, "waiter never served after the holder retired"
        assert finished[-1]["finish_reason"] != "error"
        assert waiter.admission_attempts == 0  # busy engine: cap frozen
        assert eng.metrics.snapshot()["admission_failures"] >= 1

    def test_retry_cap_backstop_fails_terminally(self):
        # The backstop branch itself: a request already at the cap
        # fails terminally on its next admission failure.
        eng = _engine(n_pages=8, max_batch_size=2)
        holder = GenRequest(prompt_ids=list(range(1, 41)),
                            max_new_tokens=64)
        eng.submit(holder)
        _step(eng)
        capped = GenRequest(prompt_ids=list(range(1, 31)),
                            max_new_tokens=2)
        capped.admission_attempts = MAX_ADMISSION_RETRIES
        eng.submit(capped)
        events = []
        for _ in range(20):
            _step(eng)
            events += _drain(capped)
            if any(e["finished"] for e in events):
                break
        assert events and events[-1]["finish_reason"] == "error"


# ---------------------------------------------------------------------------
# router tier pressure
# ---------------------------------------------------------------------------

class TestRouterTierPressure:
    def _router(self):
        from generativeaiexamples_tpu.serving.router import (
            PrefixLocalityRouter)

        r = PrefixLocalityRouter(page_size=8)
        r.add_replica("a", self_feed=True)
        r.add_replica("b", self_feed=True)
        return r

    def test_latency_backlog_repels_harder_than_batch(self):
        r = self._router()
        ids = list(range(100, 116))  # two full pages
        for st in r._replicas.values():
            st.shadow.insert(ids)  # equal locality on both
        for _ in range(2):
            r.note_submitted("a", 16, "batch")
            r.note_submitted("b", 16, "latency")
        # Equal raw depth (2 vs 2), but b's queue is latency-tier:
        # tier-weighted pressure must steer the hit to a.
        assert r.place(ids) == "a"
        d = r.tier_queue_depths()
        assert d["b"] == {"latency": 2}
        # note_finished unwinds the per-tier accounting.
        r.note_finished("b", 0, "latency")
        assert r.tier_queue_depths()["b"] == {"latency": 1}

    def test_all_standard_pressure_equals_raw_depth(self):
        r = self._router()
        for _ in range(3):
            r.note_submitted("a", 16, "standard")
        st = r._replicas["a"]
        assert r._tier_pressure(st) == st.inflight == 3

    def test_snapshot_carries_tier_depth(self):
        r = self._router()
        r.note_submitted("a", 16, "latency")
        snap = r.snapshot()
        assert snap["router_tier_depth"]["a"] == {"latency": 1}


# ---------------------------------------------------------------------------
# server edge (429 + surfaces)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qos_engine():
    eng = _engine(max_batch_size=2, max_seq_len=64,
                  prefill_buckets=(16, 32)).start()
    yield eng
    eng.stop()


def _client_call(eng, serving_cfg, fn):
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.serving.openai_server import OpenAIServer

    async def runner():
        srv = OpenAIServer(eng, model_name="tiny-llama",
                           serving_cfg=serving_cfg)
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


class TestServerEdge:
    def test_sheds_429_with_retry_after_past_bound(self, qos_engine):
        scfg = ServingConfig(qos_edge=True, qos_bound_latency=1,
                             qos_retry_after_s=2.0)

        async def body(c):
            r1 = await c.post("/v1/completions", json={
                "prompt": [5] * 4, "max_tokens": 48, "stream": True,
                "priority": "latency"})
            await r1.content.readline()  # admitted: holds the bound
            r2 = await c.post("/v1/completions", json={
                "prompt": [6] * 4, "max_tokens": 2, "priority": "latency"})
            shed = (r2.status, r2.headers.get("Retry-After"),
                    await r2.json())
            # Other tiers stay admittable while latency is full.
            r3 = await c.post("/v1/completions", json={
                "prompt": [7] * 4, "max_tokens": 2, "priority": "batch"})
            ok_status = r3.status
            async for _ in r1.content:
                pass
            snap = await (await c.get("/metrics")).json()
            return shed, ok_status, snap

        (status, retry_after, body_json), ok_status, snap = _client_call(
            qos_engine, scfg, body)
        assert status == 429
        assert retry_after == "2"
        assert body_json["error"]["code"] == "tier_queue_full"
        assert ok_status == 200
        assert snap["qos_shed_latency"] >= 1

    def test_metrics_and_health_qos_keys_always_present(self, qos_engine):
        async def body(c):
            return (await (await c.get("/metrics")).json(),
                    await (await c.get("/health")).json())

        snap, health = _client_call(qos_engine, None, body)
        for key in ("qos_shed_latency", "qos_shed_standard",
                    "qos_shed_batch", "qos_shed_total", "qos_edge_depth",
                    "admission_failures", "qos_preemptions",
                    "qos_queue_depth", "router_tier_depth"):
            assert key in snap, key
        assert snap["qos_shed_total"] == 0
        assert health["qos"]["enabled"] is False
        assert health["qos"]["edge_enabled"] is False
        assert health["qos"]["shed"]["qos_shed_total"] == 0

    def test_request_tier_and_tenant_parsed(self, qos_engine):
        from generativeaiexamples_tpu.serving.openai_server import (
            OpenAIServer)

        srv = OpenAIServer(qos_engine, model_name="tiny-llama")
        req = srv._gen_request(
            {"prompt": [5, 6], "priority": "LATENCY", "user": "u1"},
            chat=False, headers={"x-tenant-id": "acme"})
        assert req.priority == "latency"
        assert req.tenant_id == "acme"  # header beats the user field
        req2 = srv._gen_request({"prompt": [5, 6], "user": "u1"},
                                chat=False,
                                headers={"x-priority": "batch"})
        assert req2.priority == "batch"
        assert req2.tenant_id == "u1"


class TestFleetQos:
    def test_fleet_snapshot_aggregates_qos_counters(self):
        from generativeaiexamples_tpu.serving.fleet import (
            EngineFleet, LocalReplica)

        fleet = EngineFleet(
            [LocalReplica(f"r{i}", _engine()) for i in range(2)],
            ByteTokenizer(), 8).start()
        try:
            req = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=4,
                             priority="latency")
            fleet.submit(req)
            while not req.stream.get(timeout=120)["finished"]:
                pass
            snap = fleet.metrics.snapshot()
            assert snap["qos_preemptions"] == 0
            assert snap["admission_failures"] == 0
            assert snap["qos_queue_depth"] == {"latency": 0,
                                               "standard": 0, "batch": 0}
            assert "router_tier_depth" in snap
            assert fleet.metrics.qos_preemptions == 0
        finally:
            fleet.stop()
