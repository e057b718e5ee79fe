"""One request timeline from /generate to the first frame: the stages
tile the request, the caller's id survives both HTTP hops, the encoders
report their own times, the repaired span parenting, and what tracing
no longer costs while it is off. CPU, hermetic, tiny models."""

import asyncio
import contextlib
import json
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.api.server import ChainServer
from generativeaiexamples_tpu.config.schema import EngineConfig, replace
from generativeaiexamples_tpu.config.wizard import load_config
from generativeaiexamples_tpu.connectors.fakes import HashEmbedder
from generativeaiexamples_tpu.connectors.openai_http import (
    OpenAIChatLLM, OpenAIEmbedder)
from generativeaiexamples_tpu.models import bert, llama
from generativeaiexamples_tpu.obs import tracing
from generativeaiexamples_tpu.pipelines.base import get_example_class
from generativeaiexamples_tpu.pipelines.resources import Resources
from generativeaiexamples_tpu.serving.encoders import EmbeddingEngine
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.flight import EV_FIRST_TOKEN, EV_SUBMIT
from generativeaiexamples_tpu.serving.openai_server import OpenAIServer
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
TINY_BERT = bert.BertConfig.tiny(vocab_size=512)


# -- helpers -----------------------------------------------------------------


class _TimelineLog(logging.Handler):
    """The `gaie.timeline` lines, each with the monotonic instant it was
    written at."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines = []

    def emit(self, record):
        self.lines.append((time.monotonic(), json.loads(record.getMessage())))


@pytest.fixture()
def timeline_log():
    handler = _TimelineLog()
    logger = logging.getLogger("gaie.timeline")
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    yield handler.lines
    logger.removeHandler(handler)
    logger.setLevel(level)


class _Response:
    def __init__(self, payload=None, lines=(), headers=None):
        self._payload, self._lines = payload, lines
        self.headers = headers or {}
        self.last_line_at = None

    def raise_for_status(self):
        pass

    def json(self):
        return self._payload

    def iter_lines(self):
        for line in self._lines:
            self.last_line_at = time.monotonic()
            yield line


class _Session:
    """Stands in for requests.Session: records what was sent."""

    def __init__(self, respond):
        self.headers = {}
        self.sent = []
        self._respond = respond

    def post(self, url, json=None, headers=None, **kw):
        self.sent.append((url, dict(headers or {})))
        return self._respond(url, json)


def _sse(pieces):
    frames = [b"", b'data: {"choices":[{"delta":{}}]}']  # an empty delta first
    frames += [("data: " + json.dumps(
        {"choices": [{"delta": {"content": p}}]})).encode() for p in pieces]
    return frames + [b"data: [DONE]"]


def _fake_remote_server(tmp_path, llm_respond):
    """A chain server whose LLM and embedder are the HTTP connectors over
    fake sessions: every stage of the timeline runs, no socket opens."""
    cfg = load_config(path="", env={})
    dim = 64
    hashed = HashEmbedder(dim)

    def embed_respond(url, body):
        vecs = hashed.embed_documents(body["input"])
        return _Response(
            {"data": [{"index": i, "embedding": v.tolist()}
                      for i, v in enumerate(vecs)]},
            headers={"Server-Timing": "total;dur=5.500, tokenize;dur=0.250, "
                                      "queue;dur=0.125, ready;dur=4.000"})

    llm = OpenAIChatLLM("http://engine.invalid/v1", model="m")
    llm.session = _Session(llm_respond)
    emb = OpenAIEmbedder("http://engine.invalid/v1", model="e", dim=dim)
    emb.session = _Session(embed_respond)
    res = Resources(cfg, llm=llm, embedder=emb)
    ex = get_example_class("developer_rag")(res)
    return ChainServer(cfg, example=ex, upload_dir=str(tmp_path / "up")), \
        llm, emb


def _call(server, fn):
    from aiohttp.test_utils import TestClient, TestServer

    async def runner():
        client = TestClient(TestServer(server.app))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


async def _upload(c, name, text):
    import io

    import aiohttp

    form = aiohttp.FormData()
    form.add_field("file", io.BytesIO(text.encode()), filename=name)
    r = await c.post("/documents", data=form)
    assert r.status == 200, await r.text()


async def _generate(c, text="what text?", kb=True):
    r = await c.post("/generate", json={
        "messages": [{"role": "user", "content": text}],
        "use_knowledge_base": kb})
    raw = (await r.read()).decode()
    frames = [json.loads(f[6:]) for f in raw.split("\n\n") if f]
    assert frames[-1]["choices"][0]["finish_reason"] == "[DONE]"
    return frames


class _ServerThread:
    """An aiohttp application on 127.0.0.1:<free port> in a thread, for
    the connectors' blocking `requests` calls."""

    def __init__(self, make_app):
        self._make_app = make_app
        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self.url = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        from aiohttp import web

        asyncio.set_event_loop(self._loop)
        runner = web.AppRunner(self._make_app())
        self._loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        self._loop.run_until_complete(site.start())
        self.url = "http://127.0.0.1:%d" % runner.addresses[0][1]
        self._ready.set()
        self._loop.run_forever()
        self._loop.run_until_complete(runner.cleanup())

    def __enter__(self):
        self._thread.start()
        assert self._ready.wait(60)
        return self

    def __exit__(self, *exc):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(30)
        assert not self._thread.is_alive()


@pytest.fixture(scope="module")
def engines():
    tk = ByteTokenizer()
    llm = LLMEngine(
        llama.init_params(TINY, jax.random.PRNGKey(0)), TINY, tk,
        EngineConfig(max_batch_size=2, max_seq_len=128, page_size=8,
                     prefill_buckets=(64,)),
        use_pallas=False).start()
    emb = EmbeddingEngine(bert.init_params(TINY_BERT, jax.random.PRNGKey(1)),
                          TINY_BERT, tk, max_batch=4, buckets=(16, 32))
    yield llm, emb
    llm.stop()


@pytest.fixture()
def engine_server(engines):
    llm, emb = engines
    with _ServerThread(
            lambda: OpenAIServer(llm, emb, model_name="tiny").app) as st:
        yield st, llm


@contextlib.contextmanager
def _mini_tracing():
    exporter = tracing.MemoryExporter()
    assert tracing.setup(exporter=exporter)
    try:
        yield exporter
    finally:
        tracing._ENABLED = False  # don't leak tracing into other tests


# -- the chain server's timeline ----------------------------------------------


def test_six_stages_tile_a_generate_request(tmp_path, timeline_log):
    answer = _Response(lines=_sse(["Hel", "lo", " there"]))
    srv, llm, emb = _fake_remote_server(tmp_path, lambda url, body: answer)

    async def body(c):
        await _upload(c, "d.txt", "Timeline test document text.\n\n" * 4)
        frames = await _generate(c)
        return frames, await (await c.get("/metrics")).json()

    frames, metrics = _call(srv, body)
    assert "".join(f["choices"][0]["message"]["content"]
                   for f in frames) == "Hello there"
    assert len(timeline_log) == 1  # one line per /generate, none for /documents
    written_at, line = timeline_log[0]
    assert line["rid"] == frames[0]["id"] and line["ok"] is True
    stages = line["stages"]
    assert [s["name"] for s in stages] == list(tracing.STAGES)
    # No holes: a stage starts on the stamp the previous one ended on,
    # the first on the receipt.
    assert stages[0]["start"] == line["received"]
    for prev, nxt in zip(stages, stages[1:]):
        assert nxt["start"] == prev["end"]
    total_ms = sum(s["end"] - s["start"] for s in stages) * 1e3
    first_frame_ms = (stages[-1]["end"] - line["received"]) * 1e3
    assert abs(total_ms - first_frame_ms) < 1.0
    # ... written after the LAST frame, not the first.
    assert written_at >= answer.last_line_at >= stages[-1]["start"]
    # The encoder's own times ride the embed stage.
    embed = stages[1]
    assert embed["server"] == {"total": 5.5, "tokenize": 0.25,
                               "queue": 0.125, "ready": 4.0}
    # The operator's view: one observation per stage.
    for st in tracing.STAGES:
        assert metrics[f"hist_chain_{st}_ms"]["count"] == 1, st
    # The id rode both hops.
    for session in (llm.session, emb.session):
        url, headers = session.sent[-1]
        assert headers["x-request-id"] == line["rid"], url
    assert "traceparent" not in llm.session.sent[-1][1]  # tracing is off


def test_timeline_without_knowledge_base_skips_retrieval(tmp_path,
                                                         timeline_log):
    srv, _, _ = _fake_remote_server(
        tmp_path, lambda url, body: _Response(lines=_sse(["ok"])))
    _call(srv, lambda c: _generate(c, kb=False))
    (_, line), = timeline_log
    assert [s["name"] for s in line["stages"]] == [
        "dispatch", "assemble", "llm_first_piece", "emit"]


def test_failing_chain_closes_its_timeline_not_ok(tmp_path, timeline_log):
    def refuse(url, body):
        raise ConnectionError("engine down")

    srv, _, _ = _fake_remote_server(tmp_path, refuse)

    async def body(c):
        await _upload(c, "d.txt", "Timeline test document text.\n\n" * 4)
        return await _generate(c)

    frames = _call(srv, body)
    assert "Error from chain server" in \
        frames[0]["choices"][0]["message"]["content"]
    (_, line), = timeline_log
    assert line["ok"] is False
    names = [s["name"] for s in line["stages"]]
    # The stage that raised is closed too, and the error frame is emitted.
    assert names == list(tracing.STAGES)


def test_nested_span_stamps_only_the_outermost_and_survives_a_raise():
    tl = tracing.Timeline("r1")
    tracing.attach_timeline(tl)
    try:
        with tracing.span("outer"):
            with tracing.span("inner"):
                pass
        with pytest.raises(ValueError):
            with tracing.span("raises"):
                raise ValueError("x")
    finally:
        tracing.attach_timeline(None)
    with tracing.span("detached"):  # no timeline on the thread: nothing
        pass
    assert [s[0] for s in tl.stages] == ["outer", "raises"]
    assert tl.depth == 0
    assert tl.stages[0][1] == tl.received
    assert tl.stages[1][1] == tl.stages[0][2]
    assert tracing.outgoing_headers() == {}


def test_server_timing_round_trip():
    fields = {"total": 12.3456, "tokenize": 0.5, "queue": 0.0, "ready": 9.25}
    header = tracing.format_server_timing(fields)
    assert header == ("total;dur=12.346, tokenize;dur=0.500, "
                      "queue;dur=0.000, ready;dur=9.250")
    assert tracing.parse_server_timing(header) == {
        "total": 12.346, "tokenize": 0.5, "queue": 0.0, "ready": 9.25}
    # Foreign headers: descriptions, no duration, junk.
    assert tracing.parse_server_timing(
        'cache;desc="hit", db;dur=53.2;desc="x", bad;dur=abc, ,') == {
            "db": 53.2}
    assert tracing.parse_server_timing("") == {}


# -- the id and the encoder's times through a real OpenAIServer ---------------


def test_request_id_arrives_as_aux_of_submit(engine_server):
    st, llm = engine_server
    chat = OpenAIChatLLM(st.url + "/v1", model="tiny")
    tl = tracing.Timeline("chain-rid-0001")
    tracing.attach_timeline(tl)
    try:
        text = "".join(chat.stream_chat(
            [{"role": "user", "content": "hi"}], temperature=0.0,
            max_tokens=6))
    finally:
        tracing.attach_timeline(None)
    assert [s[0] for s in tl.stages] == ["llm_first_piece"]
    events = llm.flight.snapshot_events()
    sub = [e for e in events if e["kind"] == EV_SUBMIT
           and e["aux"] == "chain-rid-0001"]
    assert len(sub) == 1
    assert sub[0]["rid"].startswith("cmpl-")  # the response id stays
    assert 0.0 <= sub[0]["b"] < 60e3  # ms of surface work before submit
    first = [e for e in events if e["kind"] == EV_FIRST_TOKEN
             and e["rid"] == sub[0]["rid"]]
    assert len(first) == 1
    # What the hop costs: the stage as the caller saw it, less the
    # surface's and the engine's share of it, request by request.
    stage_ms = (tl.stages[0][2] - tl.stages[0][1]) * 1e3
    if text:
        assert stage_ms - (sub[0]["b"] + first[0]["a"]) > -1.0


def test_engine_direct_submit_carries_no_caller(engines):
    llm, _ = engines
    req = llm.submit(GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=2,
                                request_id="direct-1"))
    while not req.stream.get(timeout=60)["finished"]:
        pass
    sub, = [e for e in llm.flight.snapshot_events()
            if e["kind"] == EV_SUBMIT and e["rid"] == "direct-1"]
    assert sub["aux"] == "" and sub["b"] == 0.0 and sub["a"] == 3.0


def test_embeddings_server_timing_reaches_the_embed_stage(engine_server):
    st, _ = engine_server
    emb = OpenAIEmbedder(st.url + "/v1", model="e", dim=TINY_BERT.dim)
    tl = tracing.Timeline("chain-rid-0002")
    tracing.attach_timeline(tl)
    try:
        with tracing.span("embed"):
            vec = emb.embed_query("what is a TPU?")
    finally:
        tracing.attach_timeline(None)
    assert vec.shape == (TINY_BERT.dim,)
    (name, _, _, server), = tl.stages
    assert name == "embed"
    assert set(server) == {"total", "tokenize", "queue", "ready"}
    assert all(v >= 0.0 for v in server.values())
    assert server["total"] + 2e-3 >= server["tokenize"] + server["ready"]
    wall_ms = (tl.stages[0][2] - tl.stages[0][1]) * 1e3
    assert wall_ms + 2e-3 >= server["total"]


def test_microbatched_embed_reports_queue_and_ready(engines):
    _, emb = engines
    direct: dict = {}
    want = emb.embed(["a query"], is_query=True, timing=direct)
    emb.enable_microbatch(max_batch=4, max_wait_us=500)
    try:
        timing: dict = {}
        got = emb.embed(["a query"], is_query=True, timing=timing)
    finally:
        emb.disable_microbatch()
    np.testing.assert_array_equal(got, want)
    for t in (direct, timing):
        assert set(t) == {"tokenize", "queue", "ready"}
        assert all(v >= 0.0 for v in t.values())


# -- spans: the repaired parent link, and nothing while tracing is off --------


def test_engine_generate_is_a_child_of_generate_across_the_hop(
        tmp_path, engine_server):
    st, _ = engine_server
    cfg = load_config(path="", env={})
    cfg = replace(cfg, prompts=replace(cfg.prompts, chat_template="Be brief."))
    with _mini_tracing() as exporter:
        res = Resources(cfg, llm=OpenAIChatLLM(st.url + "/v1", model="tiny"),
                        embedder=HashEmbedder(64))
        srv = ChainServer(cfg, example=get_example_class("developer_rag")(res),
                          upload_dir=str(tmp_path / "up"))
        _call(srv, lambda c: _generate(c, "hi", kb=False))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:  # the engine ends its span at retire
            spans = exporter.get_finished_spans()
            if any(s.name == "engine.generate" for s in spans):
                break
            time.sleep(0.01)
    gen = next(s for s in spans if s.name == "generate")
    eng = next(s for s in spans if s.name == "engine.generate")
    assert eng.context.trace_id == gen.context.trace_id
    assert eng.parent.span_id == gen.context.span_id
    hop = next(s for s in spans if s.name == "llm_first_piece")
    assert hop.parent.span_id == gen.context.span_id


def _greedy_tokens(llm, prompt, n):
    return [ev["token_id"] for ev in llm.generate_stream(
        prompt, max_new_tokens=n, temperature=0.0) if ev["token_id"] >= 0]


def test_tracing_off_builds_no_span_and_asks_no_memory_stats(
        engines, monkeypatch):
    llm, _ = engines
    assert not tracing.enabled()
    retired, asked = [], []
    mark_done = llm._mark_done

    def spy(slot):
        retired.append(slot.span)
        return mark_done(slot)

    class _Device:
        def __init__(self, dev):
            self._dev = dev

        def memory_stats(self):
            asked.append(1)
            return self._dev.memory_stats()

        def __getattr__(self, name):
            return getattr(self._dev, name)

    real_devices = jax.devices
    monkeypatch.setattr(llm, "_mark_done", spy)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Device(d) for d in real_devices(*a)])
    _greedy_tokens(llm, [5, 6, 7], 4)
    assert retired == [None]
    assert asked == []


def test_greedy_stream_is_the_same_with_tracing_on_and_off(engines):
    llm, _ = engines
    prompt = [int(t) for t in np.random.default_rng(7).integers(1, 250, 12)]
    off = _greedy_tokens(llm, prompt, 8)
    with _mini_tracing():
        on = _greedy_tokens(llm, prompt, 8)
    want = llama.greedy_generate(llm.params, TINY,
                                 jnp.asarray([prompt], jnp.int32), 8)
    assert off == on == [int(t) for t in np.asarray(want)[0][-8:]]


# -- named scopes are metadata only -------------------------------------------


def _lowered_texts():
    params = jax.eval_shape(
        lambda: llama.init_params(TINY, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    low = jax.jit(lambda p, t: llama.forward(p, TINY, t)[0]).lower(
        params, tokens)
    bparams = jax.eval_shape(lambda: bert.fuse_qkv_params(
        bert.init_params(TINY_BERT, jax.random.PRNGKey(0))))
    blow = jax.jit(lambda p, t, n: bert.forward(
        p, TINY_BERT, t, lengths=n, use_pallas=False)[1]).lower(
            bparams, tokens, jax.ShapeDtypeStruct((2,), jnp.int32))
    return low, blow


def test_named_scopes_name_the_matmuls_and_change_no_program(monkeypatch):
    low, blow = _lowered_texts()
    named = low.as_text(debug_info=True)
    for scope in ("attn.qkv", "attn.out", "mlp.gate_up", "mlp.down",
                  "lm_head"):
        assert scope in named, scope
    bnamed = blow.as_text(debug_info=True)
    for scope in ("attn.qkv", "attn.out", "mlp.in", "mlp.out"):
        assert scope in bnamed, scope
    # What JAX hashes for its compile-cache key is the module with the
    # debug info stripped (jax._src.cache_key): that text must be what
    # the same code gives with no scope at all.
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, bbare = _lowered_texts()
    assert "attn.qkv" not in bare.as_text(debug_info=True)
    assert bare.as_text() == low.as_text()
    assert bbare.as_text() == blow.as_text()
