"""HTTP connectors: any OpenAI-compatible /v1 endpoint.

Covers both deployment shapes the reference supports: a local engine
server (NIM analog — our serving.openai_server on another port/host) and
a hosted API catalog (utils.py:276-288 switches on server_url exactly
like this). Uses `requests` with SSE line parsing mirroring the
reference frontend's ChatClient.predict (chat_client.py:84-98).
"""

from __future__ import annotations

import json
import logging
from typing import Iterator, Sequence

import numpy as np
import requests

from generativeaiexamples_tpu.connectors.base import ChatBase, Message
from generativeaiexamples_tpu.obs import tracing

_LOG = logging.getLogger(__name__)


class OpenAIChatLLM(ChatBase):
    def __init__(self, base_url: str, model: str = "", api_key: str = "",
                 timeout: float = 120.0):
        self.base_url = base_url.rstrip("/")
        self.model = model
        self.timeout = timeout
        self.session = requests.Session()
        if api_key:
            self.session.headers["Authorization"] = f"Bearer {api_key}"

    def stream_chat(self, messages: Sequence[Message], *, temperature=0.2,
                    top_p=0.7, max_tokens=1024, stop=()) -> Iterator[str]:
        yield from tracing.traced_llm_stream(
            "llm.openai", self._stream(messages, temperature, top_p,
                                       max_tokens, stop),
            {"model": self.model, "max_tokens": max_tokens})

    def _stream(self, messages, temperature, top_p, max_tokens, stop
                ) -> Iterator[str]:
        body = {
            "model": self.model, "messages": list(messages),
            "temperature": temperature, "top_p": top_p,
            "max_tokens": max_tokens, "stream": True,
        }
        if stop:
            body["stop"] = list(stop)
        # The caller's request id and trace context ride the hop (taken
        # before the stage's span opens: the engine's span is a child of
        # `generate`, beside this stage, not under it).
        headers = tracing.outgoing_headers()
        with tracing.span("llm_first_piece"):
            r = self.session.post(f"{self.base_url}/chat/completions",
                                  json=body, headers=headers, stream=True,
                                  timeout=self.timeout)
            r.raise_for_status()
            pieces = self._pieces(r.iter_lines())
            first = next(pieces, None)
        if first is not None:
            yield first
            yield from pieces

    @staticmethod
    def _pieces(lines) -> Iterator[str]:
        """The non-empty content pieces of an SSE body, to `[DONE]`."""
        for line in lines:
            if not line:
                continue
            line = line.decode() if isinstance(line, bytes) else line
            if not line.startswith("data: "):
                continue
            payload = line[6:]
            if payload.strip() == "[DONE]":
                return
            try:
                delta = json.loads(payload)["choices"][0].get("delta", {})
            except (json.JSONDecodeError, KeyError, IndexError):
                _LOG.debug("bad SSE frame: %r", payload)
                continue
            piece = delta.get("content")
            if piece:
                yield piece


class OpenAIEmbedder:
    def __init__(self, base_url: str, model: str = "", api_key: str = "",
                 dim: int = 1024, timeout: float = 60.0, batch: int = 32):
        self.base_url = base_url.rstrip("/")
        self.model = model
        self.dim = dim
        self.timeout = timeout
        self.batch = batch
        self.session = requests.Session()
        if api_key:
            self.session.headers["Authorization"] = f"Bearer {api_key}"

    def _call(self, texts, input_type):
        out = []
        for i in range(0, len(texts), self.batch):
            body = {"model": self.model, "input": list(texts[i:i + self.batch]),
                    "input_type": input_type}
            r = self.session.post(f"{self.base_url}/embeddings", json=body,
                                  headers=tracing.outgoing_headers(),
                                  timeout=self.timeout)
            r.raise_for_status()
            # The encoder's own times (tokenize, queue, ready, total)
            # onto the stage that made this call.
            tracing.note_server_timing(r.headers.get("Server-Timing"))
            data = sorted(r.json()["data"], key=lambda d: d["index"])
            out.extend(d["embedding"] for d in data)
        return np.asarray(out, np.float32)

    def embed_documents(self, texts: Sequence[str]) -> np.ndarray:
        return self._call(list(texts), "passage")

    def embed_query(self, text: str) -> np.ndarray:
        return self._call([text], "query")[0]

    def embed_queries(self, texts: Sequence[str]) -> np.ndarray:
        return self._call(list(texts), "query")


class OpenAIReranker:
    """NIM-style /v1/ranking client (our server implements it too)."""

    def __init__(self, base_url: str, model: str = "", api_key: str = "",
                 timeout: float = 60.0):
        self.base_url = base_url.rstrip("/")
        self.model = model
        self.timeout = timeout
        self.session = requests.Session()
        if api_key:
            self.session.headers["Authorization"] = f"Bearer {api_key}"

    def score(self, query: str, passages: Sequence[str]) -> np.ndarray:
        body = {"model": self.model, "query": {"text": query},
                "passages": [{"text": p} for p in passages]}
        r = self.session.post(f"{self.base_url}/ranking", json=body,
                              headers=tracing.outgoing_headers(),
                              timeout=self.timeout)
        r.raise_for_status()
        tracing.note_server_timing(r.headers.get("Server-Timing"))
        out = np.zeros((len(passages),), np.float32)
        for rk in r.json()["rankings"]:
            out[rk["index"]] = rk["logit"]
        return out
