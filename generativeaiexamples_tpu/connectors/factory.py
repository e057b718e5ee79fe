"""Connector factory: config -> ChatLLM / Embedder / Reranker.

The analog of the reference's cached get_llm/get_embedding_model
(common/utils.py:265-318): `model_engine` selects the implementation,
`server_url` the remote. In-process TPU engines are created once per
process and shared (EngineHub), so the chain server and pipelines reuse
one device footprint.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from generativeaiexamples_tpu.config.schema import AppConfig

_LOG = logging.getLogger(__name__)


class EngineHub:
    """Lazy, process-wide owner of the in-process TPU engines."""

    _instance: Optional["EngineHub"] = None
    _lock = threading.Lock()

    def __init__(self, config: AppConfig):
        self.config = config
        self._llm = None
        self._embed = None
        self._rerank = None
        self._build_lock = threading.Lock()

    @classmethod
    def get(cls, config: AppConfig) -> "EngineHub":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls(config)
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            if cls._instance is not None and cls._instance._llm is not None:
                cls._instance._llm.stop()
            cls._instance = None

    def llm_engine(self):
        with self._build_lock:
            if self._llm is None:
                from generativeaiexamples_tpu.serving.__main__ import (
                    build_engines)

                self._llm, self._embed, self._rerank = build_engines(
                    self.config)
            return self._llm

    def embed_engine(self):
        self.llm_engine()
        return self._embed

    def rerank_engine(self):
        self.llm_engine()
        return self._rerank


_FAKE_OR_REMOTE = ("echo", "hash", "overlap", "test", "lexical", "tfidf",
                   "bm25", "openai", "nim", "remote")


def uses_local_device(config: AppConfig) -> bool:
    """True when THIS process will run JAX itself: an in-process engine
    behind a connector below, or the device-resident vector store. With
    every connector remote and a host-side store, a chain server never
    initialises a JAX backend — a chip belongs to one process, and that
    process is the engine server."""
    def in_process(section) -> bool:
        return (not section.server_url
                and section.model_engine not in _FAKE_OR_REMOTE)

    return (in_process(config.llm) or in_process(config.embeddings)
            or (config.reranker.enabled and in_process(config.reranker))
            or config.vector_store.name in ("tpu", "native"))


def get_llm(config: AppConfig, hub: Optional[EngineHub] = None):
    eng = config.llm.model_engine
    if eng in ("echo", "test"):
        from generativeaiexamples_tpu.connectors.fakes import EchoLLM

        return EchoLLM()
    if eng in ("openai", "nim", "remote") or (config.llm.server_url and
                                              eng != "tpu"):
        from generativeaiexamples_tpu.connectors.openai_http import OpenAIChatLLM

        return OpenAIChatLLM(config.llm.server_url or "http://localhost:8000/v1",
                             model=config.llm.model_name)
    if eng == "tpu":
        if config.llm.server_url:  # TPU engine behind its own server
            from generativeaiexamples_tpu.connectors.openai_http import (
                OpenAIChatLLM)

            return OpenAIChatLLM(config.llm.server_url,
                                 model=config.llm.model_name)
        from generativeaiexamples_tpu.connectors.local import LocalEngineLLM

        return LocalEngineLLM((hub or EngineHub.get(config)).llm_engine())
    raise ValueError(f"unknown llm.model_engine {eng!r}")


def get_embedder(config: AppConfig, hub: Optional[EngineHub] = None):
    eng = config.embeddings.model_engine
    if eng in ("hash", "test"):
        from generativeaiexamples_tpu.connectors.fakes import HashEmbedder

        return HashEmbedder(dim=config.embeddings.dimensions)
    if eng in ("lexical", "tfidf", "bm25"):
        import os

        from generativeaiexamples_tpu.connectors.lexical import LexicalEmbedder

        # The configured dimension is honored as-is (a too-small dim
        # raises a clear config error inside LexicalEmbedder) — the old
        # silent max(dim, 1024) widening produced vectors that no
        # longer matched a collection created at the configured dim by
        # another engine, failing at insert instead of at config load.
        # With a durable store, the DF/IDF state persists alongside it
        # so a restarted (or separate query-serving) process keeps the
        # evaluated TF-IDF weighting instead of degrading to plain TF.
        persist = config.vector_store.persist_dir
        return LexicalEmbedder(
            dim=config.embeddings.dimensions,
            persist_path=(os.path.join(persist, "lexical_df.json")
                          if persist else None))
    if eng in ("openai", "nim", "remote") or (config.embeddings.server_url and
                                              eng != "tpu"):
        from generativeaiexamples_tpu.connectors.openai_http import (
            OpenAIEmbedder)

        return OpenAIEmbedder(
            config.embeddings.server_url or "http://localhost:8000/v1",
            model=config.embeddings.model_name,
            dim=config.embeddings.dimensions)
    if eng == "tpu":
        if config.embeddings.server_url:
            from generativeaiexamples_tpu.connectors.openai_http import (
                OpenAIEmbedder)

            return OpenAIEmbedder(config.embeddings.server_url,
                                  model=config.embeddings.model_name,
                                  dim=config.embeddings.dimensions)
        from generativeaiexamples_tpu.connectors.local import LocalEmbedder

        embed = (hub or EngineHub.get(config)).embed_engine()
        if embed is None:
            raise RuntimeError(
                "no in-process embedding engine (embeddings.weights_path "
                "unset with a real LLM); set embeddings.model_engine=hash "
                "or provide weights")
        return LocalEmbedder(embed)
    raise ValueError(f"unknown embeddings.model_engine {eng!r}")


def get_reranker(config: AppConfig, hub: Optional[EngineHub] = None):
    if not config.reranker.enabled:
        return None
    eng = config.reranker.model_engine
    if eng in ("overlap", "test"):
        from generativeaiexamples_tpu.connectors.fakes import OverlapReranker

        return OverlapReranker()
    if eng in ("openai", "nim", "remote") or config.reranker.server_url:
        from generativeaiexamples_tpu.connectors.openai_http import (
            OpenAIReranker)

        return OpenAIReranker(
            config.reranker.server_url or "http://localhost:8000/v1",
            model=config.reranker.model_name)
    from generativeaiexamples_tpu.connectors.local import LocalReranker

    rr = (hub or EngineHub.get(config)).rerank_engine()
    return LocalReranker(rr) if rr is not None else None
