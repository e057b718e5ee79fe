"""Subsystem wiring tests: these exercise features THROUGH the server /
engine rather than module-level (VERDICT r01: tracing, persistence,
hybrid retrieval, and the compile cache existed but had no call sites).
"""

import asyncio
import io
import json
import os
import time

import pytest

from generativeaiexamples_tpu.api.server import ChainServer
from generativeaiexamples_tpu.config.schema import replace
from generativeaiexamples_tpu.config.wizard import load_config
from generativeaiexamples_tpu.connectors.fakes import (
    EchoLLM, HashEmbedder, OverlapReranker)
from generativeaiexamples_tpu.pipelines.base import get_example_class
from generativeaiexamples_tpu.pipelines.resources import Resources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _server(cfg, reranker=None, tmp_path=None):
    res = Resources(cfg, llm=EchoLLM(), embedder=HashEmbedder(64),
                    reranker=reranker)
    ex = get_example_class("developer_rag")(res)
    return ChainServer(cfg, example=ex,
                       upload_dir=str(tmp_path / "up") if tmp_path else
                       "/tmp/gaie_tpu_test/up")


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
    import aiohttp

    form = aiohttp.FormData()
    form.add_field("file", io.BytesIO(text.encode()), filename=name)
    r = await c.post("/documents", data=form)
    assert r.status == 200, await r.text()


def test_vector_store_persists_across_server_restarts(tmp_path):
    """persist_dir: ingested data survives a server restart (reference
    CHANGELOG.md:63 'ingested data persists across sessions')."""
    cfg = load_config(path="", env={})
    cfg = replace(cfg, vector_store=replace(
        cfg.vector_store, persist_dir=str(tmp_path / "store")))

    srv1 = _server(cfg, tmp_path=tmp_path)

    async def put(c):
        await _upload(c, "facts.txt",
                      "The TPU v5e has 16 GB of HBM per chip.\n\n" * 4)
        return await (await c.get("/documents")).json()

    assert _call(srv1, put)["documents"] == ["facts.txt"]

    # brand-new server process-equivalent: fresh Resources, same config
    srv2 = _server(cfg, tmp_path=tmp_path)

    async def check_then_delete(c):
        docs = await (await c.get("/documents")).json()
        hits = await (await c.post(
            "/search", json={"query": "HBM per chip", "top_k": 2})).json()
        await c.delete("/documents?filename=facts.txt")  # deletion persists
        return docs, hits

    docs, hits = _call(srv2, check_then_delete)
    assert docs["documents"] == ["facts.txt"]
    assert hits["chunks"] and hits["chunks"][0]["filename"] == "facts.txt"
    srv3 = _server(cfg, tmp_path=tmp_path)

    async def docs_only(c):
        return await (await c.get("/documents")).json()

    assert _call(srv3, docs_only)["documents"] == []


def test_ranked_hybrid_reachable_via_config(tmp_path, monkeypatch):
    """retriever.nr_pipeline='ranked_hybrid' + a reranker routes
    /generate's retrieval through retrieve_hybrid (VERDICT r01: the path
    existed but no pipeline or config ever invoked it)."""
    from generativeaiexamples_tpu.rag.retriever import Retriever

    calls = []
    orig = Retriever.retrieve_hybrid

    def spy(self, query, **kw):
        calls.append(query)
        return orig(self, query, **kw)

    monkeypatch.setattr(Retriever, "retrieve_hybrid", spy)

    cfg = load_config(path="", env={})
    assert cfg.retriever.nr_pipeline == "ranked_hybrid"
    srv = _server(cfg, reranker=OverlapReranker(), tmp_path=tmp_path)
    assert srv.example.res.retriever.default_hybrid

    async def body(c):
        await _upload(c, "doc.txt", "Alpha beta gamma delta.\n\n" * 5)
        r = await c.post("/generate", json={
            "messages": [{"role": "user", "content": "alpha beta?"}],
            "use_knowledge_base": True})
        return (await r.read()).decode()

    raw = _call(srv, body)
    assert "data: " in raw
    assert calls == ["alpha beta?"]

    # without a reranker the default path stays dense
    srv2 = _server(cfg, reranker=None, tmp_path=tmp_path)
    assert not srv2.example.res.retriever.default_hybrid


def test_tracing_spans_through_generate(tmp_path):
    """ENABLE_TRACING wiring: /generate extracts the W3C traceparent and
    emits `generate` and, as its children, the stages' spans into the
    configured exporter."""
    from generativeaiexamples_tpu.obs import tracing

    exporter = tracing.MemoryExporter()
    assert tracing.setup(exporter=exporter)
    try:
        cfg = load_config(path="", env={})
        srv = _server(cfg, tmp_path=tmp_path)

        trace_id = "0af7651916cd43dd8448eb211c80319c"
        headers = {"traceparent": f"00-{trace_id}-b7ad6b7169203331-01"}

        async def body(c):
            await _upload(c, "d.txt", "Tracing test document text.\n\n" * 4)
            r = await c.post("/generate", json={
                "messages": [{"role": "user", "content": "what text?"}],
                "use_knowledge_base": True}, headers=headers)
            return (await r.read()).decode()

        _call(srv, body)
        spans = exporter.get_finished_spans()
        names = {s.name for s in spans}
        assert "generate" in names
        gen = next(s for s in spans if s.name == "generate")
        assert format(gen.context.trace_id, "032x") == trace_id
        for stage in ("embed", "search", "assemble"):
            sp = next(s for s in spans if s.name == stage)
            assert sp.parent.span_id == gen.context.span_id, stage
        assert gen.attributes["tokens_generated"] > 0
        assert gen.attributes["ttft_ms"] >= 0
    finally:
        tracing._ENABLED = False  # don't leak tracing into other tests


def test_engine_emits_generation_spans():
    """The engine opens an engine.generate span per request with a
    first_token TTFT event (reference hooks on_llm_new_token for TTFT)."""
    from generativeaiexamples_tpu.obs import tracing

    exporter = tracing.MemoryExporter()
    assert tracing.setup(exporter=exporter)
    try:
        import jax

        from generativeaiexamples_tpu.config.schema import EngineConfig
        from generativeaiexamples_tpu.models import llama
        from generativeaiexamples_tpu.serving.engine import LLMEngine
        from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

        tiny = llama.LlamaConfig.tiny()
        params = llama.init_params(tiny, jax.random.PRNGKey(0))
        ecfg = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                            prefill_buckets=(16,))
        eng = LLMEngine(params, tiny, ByteTokenizer(), ecfg,
                        use_pallas=False).start()
        try:
            list(eng.generate_stream([1, 2, 3], max_new_tokens=4))
        finally:
            eng.stop()
        spans = [s for s in exporter.get_finished_spans()
                 if s.name == "engine.generate"]
        assert spans
        sp = spans[-1]
        assert sp.attributes["prompt_tokens"] == 3
        assert sp.attributes["tokens_generated"] == 4
        assert any(e.name == "first_token" for e in sp.events)
        # Span stamps are monotonic; one wall-clock stamp for export.
        assert sp.start_time <= sp.events[0].timestamp <= sp.end_time
        assert abs(sp.start_wall - time.time()) < 600
    finally:
        tracing._ENABLED = False


@pytest.fixture()
def cache_config():
    """Hand jax.config's cache settings back as they were (conftest
    keeps the persistent cache off for the suite)."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)


def test_compile_cache_env_variable_wins(tmp_path, monkeypatch, cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the directory is the operator's —
    JAX reads the variable itself and NO directory is set in code."""
    import jax

    from generativeaiexamples_tpu.utils import platform as plat

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    before = jax.config.jax_compilation_cache_dir
    assert plat.setup_compile_cache() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "cc").exists()  # nor created here
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch,
                                                        cache_config):
    """Unset: one fixed path inside the checkout — never /tmp, a
    mkdtemp, a pid or a time (the path is part of the cache key)."""
    import jax

    from generativeaiexamples_tpu.utils import platform as plat

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert plat.DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert plat.setup_compile_cache() == plat.DEFAULT_COMPILE_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == \
        plat.DEFAULT_COMPILE_CACHE_DIR
    assert plat.setup_compile_cache() == plat.DEFAULT_COMPILE_CACHE_DIR
    assert os.path.isdir(plat.DEFAULT_COMPILE_CACHE_DIR)


def test_compile_cache_setup_failure_raises(tmp_path, monkeypatch,
                                            cache_config):
    """A cache that cannot be set up is an error, not a silent `False`."""
    from generativeaiexamples_tpu.utils import platform as plat

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(plat, "DEFAULT_COMPILE_CACHE_DIR",
                        str(blocker / "cache"))
    with pytest.raises(OSError):
        plat.setup_compile_cache()


def test_chain_server_health_initialises_no_backend():
    """Remote connectors + a host-side store: the chain server answers
    /health (and boots) without initialising a JAX backend — the chip
    belongs to the engine server's process. JAX_PLATFORMS names a
    platform that does not exist, so any backend initialisation raises
    (the positive control shows it would)."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import asyncio
        from aiohttp.test_utils import TestClient, TestServer
        from generativeaiexamples_tpu.api.server import ChainServer
        from generativeaiexamples_tpu.config.wizard import load_config

        async def main():
            server = ChainServer(load_config(None))
            async with TestClient(TestServer(server.app)) as client:
                resp = await client.get("/health")
                assert resp.status == 200, await resp.text()
                print(await resp.text())

        asyncio.run(main())
        import jax
        try:
            jax.devices()
        except RuntimeError as e:
            print("CONTROL", type(e).__name__)
    """)
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform", PYTHONPATH=REPO,
               APP_LLM_MODELENGINE="openai",
               APP_LLM_SERVERURL="http://127.0.0.1:9/v1",
               APP_EMBEDDINGS_MODELENGINE="openai",
               APP_EMBEDDINGS_SERVERURL="http://127.0.0.1:9/v1",
               APP_VECTORSTORE_NAME="memory")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "Service is up." in proc.stdout
    assert "CONTROL RuntimeError" in proc.stdout, proc.stdout


def test_uses_local_device_follows_the_connectors():
    import dataclasses

    from generativeaiexamples_tpu.config.schema import AppConfig
    from generativeaiexamples_tpu.connectors.factory import uses_local_device

    cfg = AppConfig()  # defaults: in-process tpu engines
    assert uses_local_device(cfg)
    remote = dataclasses.replace(
        cfg,
        llm=dataclasses.replace(cfg.llm, model_engine="openai",
                                server_url="http://e:8000/v1"),
        embeddings=dataclasses.replace(cfg.embeddings, model_engine="tpu",
                                       server_url="http://e:8000/v1"))
    assert not uses_local_device(remote)
    assert uses_local_device(dataclasses.replace(
        remote, vector_store=dataclasses.replace(remote.vector_store,
                                                 name="tpu")))
    assert uses_local_device(dataclasses.replace(
        remote, reranker=dataclasses.replace(remote.reranker, enabled=True)))
    fakes = dataclasses.replace(
        cfg, llm=dataclasses.replace(cfg.llm, model_engine="echo"),
        embeddings=dataclasses.replace(cfg.embeddings, model_engine="hash"))
    assert not uses_local_device(fakes)


def test_tokens_per_sec_is_sliding_window():
    from generativeaiexamples_tpu.serving.engine import EngineMetrics

    m = EngineMetrics()
    m.record_tokens(100)
    time.sleep(0.05)
    m.record_tokens(100)
    rate = m.tokens_per_sec(window_s=30.0)
    assert rate > 0
    # events outside the window contribute nothing: simulate by asking
    # for a window far smaller than the event age
    time.sleep(0.05)
    assert m.tokens_per_sec(window_s=0.01) == 0.0
    # lifetime wall time is NOT the denominator: a fresh burst after a
    # long idle period still reports the burst rate, not ~0
    m2 = EngineMetrics()
    m2.started -= 3600  # engine "started an hour ago"
    m2.record_tokens(500)
    assert m2.tokens_per_sec(window_s=30.0) > 100


# -- lexical DF persistence (ADVICE r7: cross-process IDF state) ------------


def _lexical_cfg(tmp_path, dim=1024):
    cfg = load_config(path="", env={})
    return replace(
        cfg,
        embeddings=replace(cfg.embeddings, model_engine="lexical",
                           dimensions=dim),
        vector_store=replace(cfg.vector_store,
                             persist_dir=str(tmp_path / "store")))


def test_lexical_df_persists_across_restarts(tmp_path):
    """The IDF state learned at ingest time survives a restart: a fresh
    factory-built embedder (process-equivalent) reloads the DF snapshot
    persisted alongside the store, so embed_query keeps TF-IDF
    weighting instead of silently degrading to plain TF."""
    import numpy as np

    from generativeaiexamples_tpu.connectors.factory import get_embedder

    cfg = _lexical_cfg(tmp_path)
    emb1 = get_embedder(cfg)
    emb1.embed_documents(["tpu pods stack chips", "chips share hbm",
                          "the pods run jax"])
    assert emb1.n_docs == 3
    q1 = emb1.embed_query("which chips share hbm")

    emb2 = get_embedder(cfg)  # brand-new process equivalent
    assert emb2.n_docs == 3
    assert np.allclose(emb2.embed_query("which chips share hbm"), q1)

    # Without persistence the same restart degrades to plain TF.
    cfg_np = replace(cfg, vector_store=replace(cfg.vector_store,
                                               persist_dir=""))
    emb3 = get_embedder(cfg_np)
    assert emb3.n_docs == 0
    assert not np.allclose(emb3.embed_query("which chips share hbm"), q1)


def test_lexical_df_rebuilds_from_store_chunk_text(tmp_path):
    """No DF snapshot (corpus ingested before persistence existed, or
    by another engine): Resources rebuilds the DF table from the stored
    chunk text at startup."""
    import os

    from generativeaiexamples_tpu.connectors.lexical import LexicalEmbedder
    from generativeaiexamples_tpu.rag.vectorstore import MemoryVectorStore

    cfg = _lexical_cfg(tmp_path)
    seed_emb = LexicalEmbedder(1024)
    store = MemoryVectorStore(1024,
                              persist_dir=cfg.vector_store.persist_dir)
    texts = ["tpu pods stack chips", "chips share hbm"]
    store.add(texts, seed_emb.embed_documents(texts),
              [{"filename": "a.txt"}] * 2)
    df_path = os.path.join(cfg.vector_store.persist_dir,
                           "lexical_df.json")
    if os.path.exists(df_path):
        os.unlink(df_path)  # simulate a pre-persistence corpus

    res = Resources(cfg, llm=EchoLLM())
    assert res.embedder.n_docs == 2
    # ... and the rebuild itself persisted, so the NEXT restart skips it.
    assert os.path.exists(df_path)


def test_lexical_honors_configured_dimensions(tmp_path):
    """ADVICE r7: the factory must not silently widen
    embeddings.dimensions for the lexical engine — honor it, or fail
    loudly at load when it cannot be honored."""
    from generativeaiexamples_tpu.connectors.factory import get_embedder

    cfg = _lexical_cfg(tmp_path, dim=384)
    assert get_embedder(cfg).dim == 384

    with pytest.raises(ValueError, match="dimensions"):
        get_embedder(_lexical_cfg(tmp_path, dim=4))
