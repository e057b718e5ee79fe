"""OpenAI-compatible server + encoder engines, hermetic (tiny models)."""

import asyncio
import json

import jax
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import bert, llama
from generativeaiexamples_tpu.serving.encoders import (
    EmbeddingEngine, RerankEngine)
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.openai_server import OpenAIServer
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY_LLM = llama.LlamaConfig.tiny()
TINY_BERT = bert.BertConfig.tiny(vocab_size=512)


@pytest.fixture(scope="module")
def server():
    tk = ByteTokenizer()
    llm = LLMEngine(
        llama.init_params(TINY_LLM, jax.random.PRNGKey(0)), TINY_LLM, tk,
        EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                     prefill_buckets=(16, 32)),
        use_pallas=False).start()
    emb = EmbeddingEngine(bert.init_params(TINY_BERT, jax.random.PRNGKey(1)),
                          TINY_BERT, tk, max_batch=4, buckets=(16, 32))
    rr_cfg = bert.BertConfig(vocab_size=512, dim=32, n_layers=2, n_heads=2,
                             mlp_dim=64, max_position=64, n_labels=1)
    rr = RerankEngine(bert.init_params(rr_cfg, jax.random.PRNGKey(2)), rr_cfg,
                      tk, max_batch=4, buckets=(32, 64))
    yield (llm, emb, rr)
    llm.stop()


def _client_call(engines, fn):
    """Run an async test body against an in-process aiohttp TestClient.
    The OpenAIServer (and its web.Application) is built inside the test's
    event loop — aiohttp binds an Application to the loop that runs it."""
    from aiohttp.test_utils import TestClient, TestServer

    llm, emb, rr = engines

    async def runner():
        srv = OpenAIServer(llm, emb, rr, model_name="tiny-llama")
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


def test_health_and_models(server):
    async def body(c):
        h = await (await c.get("/health")).json()
        m = await (await c.get("/v1/models")).json()
        return h, m

    h, m = _client_call(server, body)
    assert h["status"] == "healthy" and h["engines"]["llm"]
    assert {x["id"] for x in m["data"]} == {"tiny-llama",
                                            "snowflake-arctic-embed-l"}


def test_health_and_metrics_surface_prefix_cache_counters():
    """With a prefix-cache-enabled engine, /health carries the cache
    block and /metrics passes the hit/miss/evict counters through."""
    from aiohttp.test_utils import TestClient, TestServer

    class _Metrics:
        prefix_hits, prefix_miss = 3, 1
        prefix_evictions, prefix_hit_tokens = 2, 48

        def snapshot(self):
            return {"prefix_hits": 3, "prefix_miss": 1,
                    "prefix_evictions": 2, "prefix_hit_tokens": 48,
                    "prefill_tokens": 64}

    class _Cache:
        n_cached_pages = 5

    class _LLM:
        metrics = _Metrics()
        prefix_cache = _Cache()

    async def runner():
        srv = OpenAIServer(_LLM())
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            h = await (await client.get("/health")).json()
            m = await (await client.get("/metrics")).json()
            return h, m
        finally:
            await client.close()

    h, m = asyncio.run(runner())
    assert h["prefix_cache"] == {
        "enabled": True, "cached_pages": 5, "hits": 3, "misses": 1,
        "evictions": 2, "hit_tokens": 48}
    assert m["prefix_hits"] == 3 and m["prefix_hit_tokens"] == 48


def test_health_and_metrics_surface_fused_counters(server):
    """The fused-prefill AND step-plan/speculation counters are always
    present: /health carries the section (enabled=false, zeros) and
    /metrics reports every key as 0 — never absent — when the knobs
    are off (the PR-5 counter convention; spec_tokens_per_step used to
    vanish whenever spec_slot_steps was zero)."""
    async def body(c):
        h = await (await c.get("/health")).json()
        m = await (await c.get("/metrics")).json()
        return h, m

    h, m = _client_call(server, body)
    assert h["fused_prefill"] == {
        "enabled": False, "fused_steps": 0, "fused_prefill_tokens": 0,
        "prefill_stall_beats": 0}
    assert m["fused_steps"] == 0
    assert m["fused_prefill_tokens"] == 0
    assert m["prefill_stall_beats"] == 0
    assert m["spec_tokens_per_step"] == 0
    assert m["plan_variants_compiled"] == 0
    assert m["spec_fallback_steps"] == 0


def test_health_and_metrics_surface_fleet_counters(server):
    """The fleet/router surface follows the same always-present
    convention: a single-engine server reports fleet.enabled=false in
    /health and zeroed router counters in /metrics — the keys never
    flicker with deployment topology."""
    async def body(c):
        h = await (await c.get("/health")).json()
        m = await (await c.get("/metrics")).json()
        return h, m

    h, m = _client_call(server, body)
    assert h["fleet"] == {"enabled": False, "replicas": {}}
    for key in ("router_requests", "router_prefix_hits",
                "router_hit_tokens", "router_affinity_hits",
                "router_rebalances", "replica_evictions",
                "router_requeued"):
        assert m[key] == 0
    assert m["router_queue_depth"] == {}


def test_health_and_metrics_surface_kv_pager_counters(server):
    """The session-KV-pager surface follows the always-present
    convention: /health carries a kv_pager section (enabled=false,
    zeroed tiers) and /metrics reports every kv_* key as 0 — never
    absent — when engine.kv_pager is off."""
    from generativeaiexamples_tpu.serving.kv_pager import KV_PAGER_KEYS

    async def body(c):
        h = await (await c.get("/health")).json()
        m = await (await c.get("/metrics")).json()
        return h, m

    h, m = _client_call(server, body)
    assert h["kv_pager"]["enabled"] is False
    for key in KV_PAGER_KEYS:
        assert h["kv_pager"][key] == 0
        assert m[key] == 0


def test_health_kv_pager_section_with_pager_enabled():
    """A kv_pager-enabled engine's /health section carries the live
    tier gauges from the pager's stats()."""
    from aiohttp.test_utils import TestClient, TestServer

    class _Pager:
        def stats(self):
            from generativeaiexamples_tpu.serving.kv_pager import (
                KV_PAGER_KEYS)
            out = dict.fromkeys(KV_PAGER_KEYS, 0)
            out.update({"kv_demotions": 7, "kv_promotions": 3,
                        "kv_host_pages": 4, "kv_spill_pages": 2})
            return out

    class _Metrics:
        def snapshot(self):
            return {}

    class _LLM:
        metrics = _Metrics()
        kv_pager = _Pager()

    async def runner():
        srv = OpenAIServer(_LLM())
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            return await (await client.get("/health")).json()
        finally:
            await client.close()

    h = asyncio.run(runner())
    assert h["kv_pager"]["enabled"] is True
    assert h["kv_pager"]["kv_demotions"] == 7
    assert h["kv_pager"]["kv_host_pages"] == 4
    assert h["kv_pager"]["kv_spill_pages"] == 2


def test_flight_and_histogram_surfaces_always_present(server):
    """The flight-recorder/histogram surface follows the always-
    present convention: /metrics carries flight_* counters and every
    hist_* key (empty-but-present dicts when idle), /health carries a
    flight_recorder section, and trace_export_errors exists."""
    from generativeaiexamples_tpu.serving.flight import (
        FLIGHT_KEYS, HIST_KEYS)

    async def body(c):
        h = await (await c.get("/health")).json()
        m = await (await c.get("/metrics")).json()
        return h, m

    h, m = _client_call(server, body)
    for key in FLIGHT_KEYS:
        assert key in m
    assert m["flight_enabled"] == 1  # recorder defaults ON
    assert m["flight_beats"] >= 0
    for key in HIST_KEYS:
        assert "count" in m[key] and "buckets" in m[key]
    # Process-global monotonic counter (other tests exercise failure
    # paths in the same process): present and sane, not necessarily 0.
    assert isinstance(m["trace_export_errors"], int)
    assert m["trace_export_errors"] >= 0
    fr = h["flight_recorder"]
    assert fr["enabled"] is True
    assert fr["timeline"] == "/debug/timeline"
    assert fr["lanes"] == 1


def test_flight_section_enabled_false_without_recorder():
    """A recorder-less llm object (or flight_recorder=False engines
    behind a facade) still gets the /health section — enabled false,
    zeros, never absent."""
    from aiohttp.test_utils import TestClient, TestServer

    class _Metrics:
        def snapshot(self):
            return {}

    class _LLM:
        metrics = _Metrics()

    async def runner():
        srv = OpenAIServer(_LLM())
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            h = await (await client.get("/health")).json()
            t = await (await client.get("/debug/timeline")).json()
            return h, t
        finally:
            await client.close()

    h, t = asyncio.run(runner())
    assert h["flight_recorder"] == {
        "enabled": False, "flight_beats": 0, "flight_events": 0,
        "lanes": 0, "timeline": "/debug/timeline"}
    assert t == {"traceEvents": [], "displayTimeUnit": "ms"}


def test_metrics_prometheus_format(server):
    """?format=prometheus serves text exposition: gauges for scalars,
    labelled gauges for tier maps, native histogram lines for the
    hist_* keys; default stays JSON."""
    async def body(c):
        # Serve one request so counters are nonzero.
        await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3})
        r = await c.get("/metrics", params={"format": "prometheus"})
        return r.headers["Content-Type"], await r.text()

    ctype, txt = _client_call(server, body)
    assert ctype.startswith("text/plain")
    assert "# TYPE gaie_tokens_generated gauge" in txt
    assert "# TYPE gaie_ttft_ms histogram" in txt
    assert 'gaie_ttft_ms_bucket{le="+Inf"}' in txt
    assert 'gaie_qos_queue_depth{key="latency"}' in txt
    assert "gaie_flight_beats" in txt


def test_debug_timeline_endpoint(server):
    """/debug/timeline serves Chrome trace JSON whose request spans
    carry the server-issued rid."""
    async def body(c):
        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4})
        data = await r.json()
        t = await (await c.get("/debug/timeline")).json()
        return data["id"], t

    rid, trace = _client_call(server, body)
    evs = trace["traceEvents"]
    assert any(e.get("cat") == "beat" for e in evs)
    assert any(e.get("cat") == "request"
               and e.get("args", {}).get("rid") == rid for e in evs)


def test_fleet_server_streams_and_health(server):
    """An OpenAIServer whose llm object IS a fleet: streaming works
    through the router unchanged, /health carries replica states, and
    the `user` field reaches the router as the session key."""
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, LocalReplica)

    llm, _, _ = server
    fleet = EngineFleet([LocalReplica("r0", llm)], llm.tokenizer,
                        llm.ecfg.page_size)

    async def body(c):
        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "user": "sess-1"})
        h = await (await c.get("/health")).json()
        m = await (await c.get("/metrics")).json()
        return r.status, await r.json(), h, m

    from aiohttp.test_utils import TestClient, TestServer

    async def runner():
        srv = OpenAIServer(fleet, model_name="tiny-llama")
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            return await body(client)
        finally:
            await client.close()

    status, data, h, m = asyncio.run(runner())
    assert status == 200
    assert data["usage"]["completion_tokens"] == 4
    assert h["fleet"]["enabled"] is True
    assert h["fleet"]["replicas"]["r0"]["state"] == "active"
    assert m["router_requests"] == 1
    assert m["router_queue_depth"] == {"r0": 0}
    assert "r0" in m["per_replica"]
    # The session key landed in the router's affinity map.
    assert fleet.router._affinity.get("sess-1", (None,))[0] == "r0"


def test_chat_completion_non_streaming(server):
    async def body(c):
        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5})
        return r.status, await r.json()

    status, data = _client_call(server, body)
    assert status == 200
    assert data["choices"][0]["message"]["role"] == "assistant"
    assert data["usage"]["completion_tokens"] == 5


def test_chat_completion_streaming_sse(server):
    async def body(c):
        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "stream": True})
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = (await r.read()).decode()
        return raw

    raw = _client_call(server, body)
    frames = [ln[6:] for ln in raw.splitlines() if ln.startswith("data: ")]
    assert frames[-1] == "[DONE]"
    parsed = [json.loads(f) for f in frames[:-1]]
    assert parsed[-1]["choices"][0]["finish_reason"] in ("length", "stop")
    assert all(p["object"] == "chat.completion.chunk" for p in parsed)


def test_embeddings_endpoint(server):
    async def body(c):
        r = await c.post("/v1/embeddings", json={"input": ["abc", "defg"]})
        return await r.json()

    data = _client_call(server, body)
    assert len(data["data"]) == 2
    v = np.asarray(data["data"][0]["embedding"])
    assert v.shape == (TINY_BERT.dim,)
    np.testing.assert_allclose(np.linalg.norm(v), 1.0, atol=1e-4)


def test_ranking_endpoint(server):
    async def body(c):
        r = await c.post("/v1/ranking", json={
            "query": {"text": "what is a tpu"},
            "passages": [{"text": "tpus are accelerators"},
                         {"text": "bananas are yellow"},
                         {"text": "tpu chips multiply matrices"}]})
        return await r.json()

    data = _client_call(server, body)
    assert len(data["rankings"]) == 3
    logits = [r["logit"] for r in data["rankings"]]
    assert logits == sorted(logits, reverse=True)


def test_embedding_engine_batching_order():
    """Results must map back to input order despite length-sorted batching."""
    tk = ByteTokenizer()
    eng = EmbeddingEngine(bert.init_params(TINY_BERT, jax.random.PRNGKey(1)),
                          TINY_BERT, tk, max_batch=2, buckets=(8, 16, 32))
    texts = ["aaaaaaaaaaaaaaaaaaaaaaaa", "b", "cc ccc", "d" * 30, "e"]
    got = eng.embed(texts)
    one_by_one = np.stack([eng.embed([t])[0] for t in texts])
    np.testing.assert_allclose(got, one_by_one, atol=1e-4)


def test_speculative_engine_serving_surface():
    """The OpenAI surface over a speculative engine: greedy requests
    serve normally AND sampled requests serve through the per-request
    plain-plan fallback (they used to 422; now they just don't
    speculate — metrics.spec_fallback_steps records the demotions)."""
    tk = ByteTokenizer()
    llm = LLMEngine(
        llama.init_params(TINY_LLM, jax.random.PRNGKey(0)), TINY_LLM, tk,
        EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                     prefill_buckets=(16,), speculative_k=2),
        use_pallas=False).start()
    try:
        async def body(c):
            ok = await c.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 5, "temperature": 0})
            sampled = await c.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 5, "temperature": 0.8})
            m = await (await c.get("/metrics")).json()
            return (ok.status, await ok.json(), sampled.status,
                    await sampled.json(), m)

        s_ok, d_ok, s_sm, d_sm, m = _client_call((llm, None, None), body)
        assert s_ok == 200
        assert d_ok["usage"]["completion_tokens"] == 5
        assert s_sm == 200
        assert d_sm["usage"]["completion_tokens"] == 5
        assert m["spec_fallback_steps"] > 0
        assert "spec_tokens_per_step" in m
    finally:
        llm.stop()


def test_replica_submit_fault_maps_to_503(server):
    """A replica-side submit fault (a chaos-injected fault, a replica
    dying between placement and submit) is a retryable 503, never a
    raw 500 — the request was fine and the fleet unwound its
    tracking."""
    llm, emb, rr = server

    class FaultyFleet:
        tokenizer = llm.tokenizer
        metrics = llm.metrics

        def submit(self, req):
            raise RuntimeError("injected submit fault on r0")

    async def body(c):
        resp = await c.post("/v1/completions", json={
            "prompt": [5] * 4, "max_tokens": 4})
        return resp.status, await resp.json()

    status, data = _client_call((FaultyFleet(), emb, rr), body)
    assert status == 503
    assert data["error"]["code"] == "replica_submit_failed"


def test_take_ready_takes_every_ready_event_and_stops_at_the_last():
    """One executor trip per burst, not per token (`_events`): what is
    already queued comes back in order, nothing past the finishing
    event, and an empty queue blocks for the next one only."""
    import queue
    import threading

    from generativeaiexamples_tpu.serving.openai_server import _take_ready

    q = queue.Queue()
    for i in range(5):
        q.put({"token_id": i, "finished": False})
    assert [e["token_id"] for e in _take_ready(q)] == [0, 1, 2, 3, 4]
    q.put({"token_id": 5, "finished": False})
    q.put({"token_id": 6, "finished": True})
    q.put({"token_id": 7, "finished": False})  # never read: the stream ended
    assert [e["token_id"] for e in _take_ready(q)] == [5, 6]
    q = queue.Queue()
    threading.Timer(0.05, q.put, [{"token_id": 9, "finished": False}]).start()
    assert [e["token_id"] for e in _take_ready(q)] == [9]


@pytest.mark.parametrize("stop, text, finish", [
    (None, "abcdef", "length"), (["de"], "abc", "stop")],
    ids=["to-the-end", "cut-mid-burst"])
def test_a_burst_of_events_is_streamed_in_order(server, stop, text, finish):
    """What `_take_ready` took in one trip leaves a frame per event, in
    order; a stop string that ends inside the burst cuts it there."""
    llm, emb, rr = server

    class Burst:
        tokenizer = llm.tokenizer
        metrics = llm.metrics

        def submit(self, req):
            for i, t in enumerate("abcdef"):
                req.stream.put({"text": t, "token_id": i, "finished": False,
                                "finish_reason": None})
            req.stream.put({"text": "", "token_id": -1, "finished": True,
                            "finish_reason": "length"})

    async def body(c):
        r = await c.post("/v1/completions", json={
            "prompt": [5] * 4, "max_tokens": 6, "stream": True,
            **({"stop": stop} if stop else {})})
        return (await r.read()).decode()

    raw = _client_call((Burst(), emb, rr), body)
    frames = [ln[6:] for ln in raw.splitlines() if ln.startswith("data: ")]
    assert frames[-1] == "[DONE]"
    parsed = [json.loads(f)["choices"][0] for f in frames[:-1]]
    assert "".join(p["text"] for p in parsed) == text
    assert [p["finish_reason"] for p in parsed] == \
        [None] * (len(parsed) - 1) + [finish]
