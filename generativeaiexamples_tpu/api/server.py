"""Chain server: the reference's REST surface, TPU-backed (aiohttp).

Contract pinned to docs/api_reference/openapi_schema.json of the
reference (verified field-by-field):

  POST /generate   Prompt{messages, use_knowledge_base, temperature,
                   top_p, max_tokens, stop} -> SSE of ChainResponse
                   {id, choices:[{index, message{role,content},
                   finish_reason}]} ending with finish_reason "[DONE]"
                   sentinel frame (reference server.py:302-307).
  POST /documents  multipart upload -> ingest
  GET  /documents  -> {documents: [filenames]}
  DELETE /documents?filename=x
  POST /search     DocumentSearch{query, top_k} -> {chunks: [
                   DocumentChunk{content, filename, score}]}
  GET  /health     -> {message}

Input hygiene: the reference runs bleach.clean on every field
(server.py:63-141); here `sanitize` strips control chars + escapes HTML.
Errors: Milvus-specific + generic apology SSE parity (server.py:314-342)
becomes store-agnostic error SSE with [DONE].
"""

from __future__ import annotations

import asyncio
import html
import json
import logging
import os
import re
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

from aiohttp import web

from generativeaiexamples_tpu.config.schema import AppConfig
from generativeaiexamples_tpu.obs import tracing
from generativeaiexamples_tpu.serving.flight import ExpHistogram

_LOG = logging.getLogger(__name__)

_CTRL = re.compile(r"[\x00-\x08\x0b\x0c\x0e-\x1f\x7f]")
MAX_CONTENT_CHARS = 131072  # reference server.py:63


def sanitize(text: str) -> str:
    return html.escape(_CTRL.sub("", text or "")[:MAX_CONTENT_CHARS],
                       quote=False)


def _chain_response(rid: str, content: str = "",
                    finish_reason: str = "") -> Dict[str, Any]:
    return {"id": rid, "choices": [{
        "index": 0,
        "message": {"role": "assistant", "content": content},
        "finish_reason": finish_reason,
    }]}


class ChainServer:
    """One pipeline (example) behind the REST contract."""

    def __init__(self, config: AppConfig, example=None,
                 example_name: Optional[str] = None,
                 upload_dir: str = "/tmp/gaie_tpu/uploaded_files"):
        from generativeaiexamples_tpu.connectors.factory import (
            uses_local_device)
        from generativeaiexamples_tpu.pipelines.base import get_example_class
        from generativeaiexamples_tpu.pipelines.resources import Resources

        self.config = config
        self._uses_device = uses_local_device(config)
        tracing.setup(config)  # no-op unless tracing.enabled/ENABLE_TRACING
        if example is not None:
            self.example = example
        else:
            name = (example_name or os.environ.get("EXAMPLE_NAME")
                    or "developer_rag")
            resources = Resources(config)
            self.example = get_example_class(name)(resources)
        self.upload_dir = upload_dir
        os.makedirs(upload_dir, exist_ok=True)
        # Executor width bounds request concurrency. With micro-batching
        # on it is floored above the batch window — otherwise the
        # batcher can never see a full window's worth of concurrent
        # callers; with it off, the operator's setting stands alone.
        workers = config.serving.executor_workers
        if config.serving.microbatch_enabled:
            workers = max(workers, 2 * config.serving.microbatch_max_batch)
        self._executor = ThreadPoolExecutor(max_workers=workers,
                                            thread_name_prefix="chain-srv")
        # One histogram per stage of a /generate request's timeline
        # (obs/tracing.py::STAGES), observed by the loop thread only,
        # after a request's last frame.
        self._stage_hists = {st: ExpHistogram() for st in tracing.STAGES}
        self.app = web.Application(client_max_size=100 * 1024 * 1024)
        self.app.add_routes([
            web.get("/health", self.handle_health),
            web.get("/metrics", self.handle_metrics),
            web.post("/generate", self.handle_generate),
            web.post("/documents", self.handle_upload),
            web.get("/documents", self.handle_list_documents),
            web.delete("/documents", self.handle_delete_document),
            web.post("/search", self.handle_search),
        ])

    # -- /health -----------------------------------------------------------

    async def handle_health(self, request: web.Request) -> web.Response:
        # Device liveness only where this process owns a device: with
        # remote connectors and a host-side store it must not initialise
        # a JAX backend (the engine server's process holds the chip).
        if self._uses_device:
            import jax

            try:
                jax.devices()
            except Exception as e:
                return web.json_response({"message": f"unhealthy: {e}"},
                                         status=503)
        return web.json_response({"message": "Service is up."})

    # -- /metrics ----------------------------------------------------------

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Retrieval-side observability: the vector stores' counters
        (searches, ann_probes / ann_scanned_rows / ann_recall_est /
        index_rebuilds when the IVF index is live) plus the
        cross-request micro-batcher counters per stage (embed / rerank /
        search: mean coalesced batch size, queue-wait p50/p99,
        dispatches saved — serving/batcher.py), and one histogram per
        stage of /generate up to its first frame (`hist_chain_<stage>_ms`,
        obs/tracing.py::STAGES). The serving engine's token metrics live
        on ITS /metrics (serving/openai_server.py)."""
        payload: Dict[str, Any] = {
            f"hist_chain_{st}_ms": h.snapshot()
            for st, h in self._stage_hists.items()}
        res = getattr(self.example, "res", None)
        for key in ("store", "conv_store"):
            store = getattr(res, key, None)
            if store is not None and hasattr(store, "stats"):
                payload[f"vector_{key}" if key == "store" else key] = \
                    store.stats()
        retriever = getattr(res, "retriever", None)
        if retriever is not None and hasattr(retriever, "microbatch_stats"):
            payload["microbatch"] = retriever.microbatch_stats()
        return web.json_response(payload)

    # -- /generate ---------------------------------------------------------

    async def handle_generate(self, request: web.Request) -> web.StreamResponse:
        received = time.monotonic()  # the request's timeline starts here
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"detail": "invalid JSON"}, status=422)
        messages = body.get("messages") or []
        if not isinstance(messages, list) or not messages:
            return web.json_response({"detail": "messages required"}, status=422)
        chat_history = []
        query = ""
        for m in messages:
            role = sanitize(str(m.get("role", "user")))
            content = sanitize(str(m.get("content", "")))
            chat_history.append({"role": role, "content": content})
        # last user message is the query (reference server.py:261-267).
        # Remove by INDEX: list.remove() matches by value, so a user
        # message duplicated earlier in the history would be deleted in
        # the query's place.
        for i in range(len(chat_history) - 1, -1, -1):
            if chat_history[i]["role"] == "user":
                query = chat_history[i]["content"]
                del chat_history[i]
                break
        use_kb = bool(body.get("use_knowledge_base", False))
        llm_settings = {
            "temperature": float(body.get("temperature", 0.2)),
            "top_p": float(body.get("top_p", 0.7)),
            "max_tokens": int(body.get("max_tokens", 1024)),
            "stop": [sanitize(s) for s in (body.get("stop") or [])],
        }
        rid = str(uuid.uuid4())
        timeline = tracing.Timeline(rid, received)
        # W3C traceparent from the caller (reference common/tracing.py:62-73)
        trace_ctx = tracing.extract_context(dict(request.headers))

        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream", "Cache-Control": "no-cache"})
        await resp.prepare(request)

        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        DONE = object()

        gspan = tracing.GenerationSpan("generate", context=trace_ctx)
        gspan.__enter__()
        gspan.sp.set_attribute("use_knowledge_base", use_kb)
        gspan.sp.set_attribute("request_id", rid)

        def run_chain():
            # The chain runs in an executor thread: make `generate` the
            # parent of the stages' spans there, and the timeline the
            # one they stamp.
            tok = tracing.attach_context(gspan.sp.context())
            tracing.attach_timeline(timeline)
            timeline.mark("dispatch")
            try:
                gen = (self.example.rag_chain(query, chat_history, **llm_settings)
                       if use_kb else
                       self.example.llm_chain(query, chat_history, **llm_settings))
                for piece in gen:
                    loop.call_soon_threadsafe(q.put_nowait, piece)
            except Exception as e:  # error SSE parity (server.py:314-342)
                _LOG.exception("chain failed")
                timeline.ok = False
                loop.call_soon_threadsafe(
                    q.put_nowait,
                    "Error from chain server. Please check chain-server logs "
                    f"for more details. ({type(e).__name__})")
            finally:
                tracing.attach_timeline(None)
                tracing.detach_context(tok)
                loop.call_soon_threadsafe(q.put_nowait, DONE)

        fut = loop.run_in_executor(self._executor, run_chain)
        try:
            while True:
                piece = await q.get()
                if piece is DONE:
                    break
                gspan.on_token()
                frame = json.dumps(_chain_response(rid, piece))
                await resp.write(f"data: {frame}\n\n".encode())
                if gspan.tokens == 1:
                    timeline.mark("emit")  # the first frame is written
            # sentinel frame (reference server.py:302-307)
            final = json.dumps(_chain_response(rid, "", "[DONE]"))
            await resp.write(f"data: {final}\n\n".encode())
            await resp.write_eof()
        except (ConnectionResetError, asyncio.CancelledError):
            _LOG.info("client disconnected from /generate")
            timeline.ok = False
            raise
        finally:
            try:
                await asyncio.shield(fut)
            finally:
                gspan.__exit__(None, None, None)
                # After the last frame, never before: the stage
                # histograms and the request's one `gaie.timeline` line.
                for st, ms in timeline.durations_ms().items():
                    if st in self._stage_hists:
                        self._stage_hists[st].observe(ms)
                timeline.close()
        return resp

    # -- /documents --------------------------------------------------------

    async def handle_upload(self, request: web.Request) -> web.Response:
        reader = await request.multipart()
        field = None
        async for part in reader:
            if part.name in ("file", "files"):
                field = part
                break
        if field is None:
            return web.json_response({"detail": "file field required"},
                                     status=422)
        filename = os.path.basename(field.filename or "upload.bin")
        path = os.path.join(self.upload_dir, filename)
        with open(path, "wb") as fh:
            while True:
                chunk = await field.read_chunk(1 << 20)
                if not chunk:
                    break
                fh.write(chunk)
        loop = asyncio.get_running_loop()
        try:
            await loop.run_in_executor(
                self._executor,
                lambda: self.example.ingest_docs(path, filename))
        except Exception as e:
            _LOG.exception("ingest failed for %s", filename)
            return web.json_response(
                {"detail": f"ingest failed: {type(e).__name__}: {e}"},
                status=500)
        return web.json_response(
            {"message": f"File {filename} uploaded successfully"})

    async def handle_list_documents(self, request: web.Request) -> web.Response:
        try:
            docs = self.example.get_documents()
        except NotImplementedError:
            return web.json_response({"documents": []})
        return web.json_response({"documents": docs})

    async def handle_delete_document(self, request: web.Request) -> web.Response:
        filename = request.query.get("filename", "")
        if not filename:
            return web.json_response({"detail": "filename required"}, status=422)
        try:
            ok = self.example.delete_documents([filename])
        except NotImplementedError:
            return web.json_response({"detail": "not supported"}, status=405)
        except ValueError as e:
            # e.g. the Milvus store rejects names its filter grammar
            # cannot express — bad client input, not a server fault.
            return web.json_response({"detail": str(e)}, status=422)
        if not ok:
            return web.json_response({"detail": f"{filename} not found"},
                                     status=404)
        # also remove the uploaded copy
        p = os.path.join(self.upload_dir, os.path.basename(filename))
        if os.path.isfile(p):
            os.unlink(p)
        return web.json_response({"message": f"Deleted {filename}"})

    # -- /search -----------------------------------------------------------

    async def handle_search(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"detail": "invalid JSON"}, status=422)
        query = sanitize(str(body.get("query", "")))
        top_k = int(body.get("top_k", self.config.retriever.top_k))
        loop = asyncio.get_running_loop()
        try:
            chunks = await loop.run_in_executor(
                self._executor,
                lambda: self.example.document_search(query, top_k))
        except NotImplementedError:
            return web.json_response({"chunks": []})
        except Exception as e:
            _LOG.exception("search failed")
            return web.json_response({"detail": str(e)}, status=500)
        return web.json_response({"chunks": chunks})


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description="TPU RAG chain server")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8081)
    ap.add_argument("--config", default=None)
    ap.add_argument("--example", default=None,
                    help="pipeline name (default: $EXAMPLE_NAME or developer_rag)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.INFO)

    from generativeaiexamples_tpu.config.wizard import load_config
    from generativeaiexamples_tpu.connectors.factory import uses_local_device

    config = load_config(args.config)
    if uses_local_device(config):
        from generativeaiexamples_tpu.utils.platform import (
            setup_compile_cache)

        setup_compile_cache()
    server = ChainServer(config, example_name=args.example)
    _LOG.info("chain server: example=%s on %s:%d",
              server.example.example_name, args.host, args.port)
    web.run_app(server.app, host=args.host, port=args.port, print=None)


if __name__ == "__main__":
    main()
