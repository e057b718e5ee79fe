"""OpenAI-compatible HTTP surface over the TPU engines (aiohttp).

Replaces the NIM containers' API exactly where the reference consumes it
(ChatNVIDIA/NVIDIAEmbeddings point at `/v1`, common/utils.py:276,313):

  POST /v1/chat/completions   (stream=SSE chunks or full JSON)
  POST /v1/completions
  POST /v1/embeddings
  POST /v1/ranking            (NIM-style reranker: query + passages)
  GET  /v1/models, /health, /metrics

aiohttp (not FastAPI — not in the image, and the server is thin enough
that a framework buys little). Blocking engine queues are bridged to the
event loop with run_in_executor so one slow stream never blocks another.
"""

from __future__ import annotations

import asyncio
import json
import logging
import queue
import time
import uuid
from typing import Any, Dict, Optional

from aiohttp import web

from generativeaiexamples_tpu.obs import tracing

_LOG = logging.getLogger(__name__)


def _sse(data: Any) -> bytes:
    return f"data: {json.dumps(data) if not isinstance(data, str) else data}\n\n".encode()


class StopStream:
    """Stop-sequence matching over a token stream. Emitted text never
    contains any part of a stop string, including a prefix that arrived
    in an earlier SSE chunk (held back until disambiguated)."""

    def __init__(self, stops):
        self.stops = [s for s in stops if s]
        self.full = ""
        self.sent = 0

    def push(self, new: str):
        """-> (text_safe_to_emit, hit_stop)."""
        self.full += new
        for s in self.stops:
            i = self.full.find(s)
            if i >= 0:
                emit = self.full[self.sent: i]
                self.sent = i
                return emit, True
        hold = 0
        for s in self.stops:
            for k in range(min(len(s) - 1, len(self.full)), 0, -1):
                if self.full.endswith(s[:k]):
                    hold = max(hold, k)
                    break
        end = len(self.full) - hold
        emit = self.full[self.sent: end] if end > self.sent else ""
        self.sent = max(self.sent, end)
        return emit, False

    def flush(self) -> str:
        """Release held-back text (a stop-prefix false alarm) at end of
        generation — without this, output ending in a proper prefix of a
        stop string would be silently truncated."""
        out = self.full[self.sent:]
        self.sent = len(self.full)
        return out


def _take_ready(stream) -> list:
    """Block for one event of a request's stream, then take whatever
    else is already there."""
    events = [stream.get()]
    try:
        while not events[-1]["finished"]:
            events.append(stream.get_nowait())
    except queue.Empty:
        pass
    return events


class OpenAIServer:
    def __init__(self, llm_engine=None, embed_engine=None, rerank_engine=None,
                 model_name: str = "llama3-8b-instruct",
                 embed_model_name: str = "snowflake-arctic-embed-l",
                 serving_cfg=None):
        from generativeaiexamples_tpu.config.schema import ServingConfig
        from generativeaiexamples_tpu.serving.qos import EdgeAdmission

        self.llm = llm_engine
        self.embed = embed_engine
        self.rerank = rerank_engine
        # One ledger of every program the process puts on the device
        # (serving/flight.py::ProgramLedger): the encoders behind this
        # surface stamp the LLM engine's, whose scheduler drains their
        # rows into its flight ring. A fleet has no single ledger and
        # an encoder served alone keeps its own.
        ledger = getattr(llm_engine, "programs", None)
        for enc in (embed_engine, rerank_engine):
            if ledger is not None and hasattr(enc, "programs"):
                enc.programs = ledger
        self.model_name = model_name
        self.embed_model_name = embed_model_name
        scfg = serving_cfg or ServingConfig()
        # Dedicated executor: each live stream parks one thread on a
        # blocking queue.get; the default loop executor is far too small
        # (min(32, cpu+4)) and shared, so streams would starve embeddings.
        # Width is the operator's serving.executor_workers with two
        # floors: the chain server's micro-batch rule (concurrency
        # below the window means the batcher can never fill a
        # dispatch), and this server's historical 128 — streams are
        # thread-parking, so dropping below the old hardcoded width
        # would silently halve default stream capacity.
        from concurrent.futures import ThreadPoolExecutor

        workers = max(scfg.executor_workers, 128)
        if scfg.microbatch_enabled:
            workers = max(workers, 2 * scfg.microbatch_max_batch)
        self._executor = ThreadPoolExecutor(max_workers=workers,
                                            thread_name_prefix="openai-srv")
        # Edge admission control (serving/qos.py): per-tier in-flight
        # bounds; past the bound a request is shed with 429 +
        # Retry-After BEFORE it queues on the engine. Always
        # constructed so the /metrics shed counters exist (0, never
        # absent) when shedding is off.
        self.edge = EdgeAdmission(
            bounds={"latency": scfg.qos_bound_latency,
                    "standard": scfg.qos_bound_standard,
                    "batch": scfg.qos_bound_batch},
            retry_after_s=scfg.qos_retry_after_s,
            enabled=scfg.qos_edge)
        self.app = web.Application()
        self.app.add_routes([
            web.get("/health", self.handle_health),
            web.get("/v1/models", self.handle_models),
            web.post("/v1/chat/completions", self.handle_chat),
            web.post("/v1/completions", self.handle_completions),
            web.post("/v1/embeddings", self.handle_embeddings),
            web.post("/v1/ranking", self.handle_ranking),
            web.get("/metrics", self.handle_metrics),
            web.get("/debug/timeline", self.handle_timeline),
            # Disagg KV page transfer (serving/disagg.py): replica
            # engine-server processes expose their prefix cache so an
            # HttpReplica fleet can move finished prefills' pages
            # between processes (fleet.py HttpReplica.export/import).
            web.post("/v1/kv/export", self.handle_kv_export),
            web.post("/v1/kv/import", self.handle_kv_import),
        ])

    # -- helpers -----------------------------------------------------------

    def _prompt_ids(self, body: Dict, chat: bool) -> list:
        tk = self.llm.tokenizer
        if chat:
            text = tk.apply_chat_template(body["messages"],
                                          add_generation_prompt=True)
        else:
            p = body.get("prompt", "")
            if isinstance(p, list):
                if p and all(isinstance(x, int) for x in p):
                    return list(p)  # pre-tokenized prompt
                if len(p) != 1 or not isinstance(p[0], str):
                    raise web.HTTPUnprocessableEntity(
                        text=json.dumps({"detail": "prompt must be a string, "
                                         "[string], or [token ids]"}),
                        content_type="application/json")
                p = p[0]
            text = p
        return tk.encode(text, add_bos=not chat)

    def _gen_request(self, body: Dict, chat: bool, headers=None):
        from generativeaiexamples_tpu.serving.engine import GenRequest
        from generativeaiexamples_tpu.serving.qos import normalize_tier

        headers = headers or {}
        return GenRequest(
            prompt_ids=self._prompt_ids(body, chat),
            max_new_tokens=int(body.get("max_tokens") or 128),
            temperature=float(body.get("temperature") or 0.0),
            top_p=float(body.get("top_p") or 1.0),
            top_k=int(body.get("top_k") or 0),
            request_id=f"cmpl-{uuid.uuid4().hex[:20]}",
            # Fleet session affinity: the OpenAI `user` field is the
            # natural session key; a single engine ignores it.
            session_id=str(body.get("user") or ""),
            # QoS tier (body `priority` / x-priority header; unknown ->
            # standard) and tenant identity (the same OpenAI `user` key
            # the router reads for affinity, x-tenant-id overriding).
            priority=normalize_tier(body.get("priority")
                                    or headers.get("x-priority")),
            tenant_id=str(headers.get("x-tenant-id")
                          or body.get("user") or ""),
        )

    async def _events(self, req):
        """Async iterator over engine events for one request. One trip
        to the executor takes EVERY event that is ready: an unpaced
        stream's tokens land a decode block at a time, and a thread
        handoff per token saturates this one event loop near 4,000
        tokens/s (128 streams of a 5,000 tokens/s engine then wait
        seconds for their last frame and the closed loop starves the
        slots; PERF.md, PR 33). A paced stream (one event at a time)
        sees no change."""
        loop = asyncio.get_running_loop()
        while True:
            for ev in await loop.run_in_executor(self._executor,
                                                 _take_ready, req.stream):
                yield ev
                if ev["finished"]:
                    return

    @staticmethod
    def _stop_strings(body: Dict) -> list:
        stop = body.get("stop") or []
        return [stop] if isinstance(stop, str) else list(stop)

    # -- handlers ----------------------------------------------------------

    async def handle_health(self, request: web.Request) -> web.Response:
        # Device liveness, not just process liveness (SURVEY.md §5.3).
        import jax

        try:
            devices = jax.devices()
        except Exception as e:  # device lost (e.g. TPU preemption)
            return web.json_response({"status": "unhealthy", "error": str(e)},
                                     status=503)
        payload = {
            "status": "healthy", "devices": len(devices),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            # bytes_in_use / peak_bytes_in_use / bytes_limit of device 0
            # where the backend reports them (the TPU does; CPU: {}).
            "device_memory": {
                k: v for k, v in (devices[0].memory_stats() or {}).items()
                if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
            "engines": {"llm": self.llm is not None,
                        "embedding": self.embed is not None,
                        "reranking": self.rerank is not None},
        }
        pc = getattr(self.llm, "prefix_cache", None)
        if pc is not None:
            m = self.llm.metrics
            payload["prefix_cache"] = {
                "enabled": True, "cached_pages": pc.n_cached_pages,
                "hits": m.prefix_hits, "misses": m.prefix_miss,
                "evictions": m.prefix_evictions,
                "hit_tokens": m.prefix_hit_tokens,
            }
        ecfg = getattr(self.llm, "ecfg", None)
        if ecfg is not None:
            # Always present (counters 0, enabled false when the knob
            # is off) so dashboards can alert on prefill_stall_beats
            # without the key flickering in and out of the payload.
            m = self.llm.metrics
            payload["fused_prefill"] = {
                "enabled": bool(getattr(ecfg, "fused_prefill", False)),
                "fused_steps": m.fused_steps,
                "fused_prefill_tokens": m.fused_prefill_tokens,
                "prefill_stall_beats": m.prefill_stall_beats,
            }
        # Session KV pager (serving/kv_pager.py) — always present
        # (enabled false, zeroed tiers when the knob is off): tier
        # page counts/bytes plus the demotion/promotion counters, the
        # capacity story for paused sessions at a glance.
        kp = getattr(self.llm, "kv_pager", None)
        if kp is not None:
            payload["kv_pager"] = {"enabled": True, **kp.stats()}
        else:
            from generativeaiexamples_tpu.serving.kv_pager import (
                KV_PAGER_KEYS)

            payload["kv_pager"] = {"enabled": False,
                                   **dict.fromkeys(KV_PAGER_KEYS, 0)}
        # Always present, like the fused section: a fleet (serving/
        # fleet.py as the llm object) reports replica states + drain
        # flags; a single engine reports enabled=false so the key never
        # flickers with deployment topology.
        fleet_health = getattr(self.llm, "fleet_health", None)
        payload["fleet"] = (fleet_health() if callable(fleet_health)
                            else {"enabled": False, "replicas": {}})
        # Flight recorder — always present (enabled false, zeros when
        # the knob is off or the llm object has no recorder): beat and
        # lifecycle-event counts summed across the lanes this server
        # fronts, plus where to fetch the timeline itself.
        lanes = self._flight_lanes()
        fr_section = {"enabled": False, "flight_beats": 0,
                      "flight_events": 0, "lanes": len(lanes),
                      "timeline": "/debug/timeline"}
        for rec in lanes.values():
            s = rec.stats()
            fr_section["enabled"] = (fr_section["enabled"]
                                     or bool(s["flight_enabled"]))
            fr_section["flight_beats"] += s["flight_beats"]
            fr_section["flight_events"] += s["flight_events"]
        payload["flight_recorder"] = fr_section
        # QoS — always present (enabled false, zeroed counters when the
        # knobs are off): engine-side weighted-fair scheduling +
        # preemption state and the edge's per-tier shed/depth view.
        edge = self.edge.snapshot()
        payload["qos"] = {
            "enabled": bool(getattr(ecfg, "qos", False)) if ecfg else False,
            "edge_enabled": self.edge.enabled,
            "preemptions": (self.llm.metrics.qos_preemptions
                            if self.llm is not None
                            and hasattr(self.llm.metrics,
                                        "qos_preemptions") else 0),
            "shed": {k: v for k, v in edge.items()
                     if k.startswith("qos_shed_")},
            "edge_depth": edge["qos_edge_depth"],
        }
        return web.json_response(payload)

    async def handle_models(self, request: web.Request) -> web.Response:
        models = []
        if self.llm is not None:
            models.append({"id": self.model_name, "object": "model"})
        if self.embed is not None:
            models.append({"id": self.embed_model_name, "object": "model"})
        return web.json_response({"object": "list", "data": models})

    async def handle_metrics(self, request: web.Request) -> web.Response:
        # In the executor: a fleet snapshot may fetch remote replicas'
        # /metrics over HTTP — blocking the event loop for that would
        # stall every live SSE stream for the duration of a scrape.
        loop = asyncio.get_running_loop()
        snap = await loop.run_in_executor(
            self._executor,
            lambda: self.llm.metrics.snapshot() if self.llm else {})
        # Edge shed/depth counters ride the same scrape (always
        # present — zeros when shedding is off), so one /metrics pull
        # reads the whole QoS picture: engine tier depths + preemption
        # count from the engine snapshot, shedding from the edge.
        snap.update(self.edge.snapshot())
        # ?format=prometheus: text exposition (0.0.4) — scalars as
        # gauges, flat maps labelled, the flight histograms in native
        # Prometheus histogram form. Default stays JSON.
        if request.query.get("format") == "prometheus":
            from generativeaiexamples_tpu.serving.flight import (
                prometheus_text)

            return web.Response(
                text=prometheus_text(snap),
                content_type="text/plain", charset="utf-8",
                headers={"X-Prometheus-Exposition-Version": "0.0.4"})
        return web.json_response(snap)

    def _flight_lanes(self) -> Dict[str, Any]:
        """name -> FlightRecorder for every lane this server fronts: a
        fleet exposes one per local replica, a single engine one."""
        get = getattr(self.llm, "flight_recorders", None)
        if callable(get):
            return get()
        fr = getattr(self.llm, "flight", None)
        return {"engine": fr} if fr is not None else {}

    async def handle_timeline(self, request: web.Request) -> web.Response:
        """Chrome trace-event JSON over the flight-recorder rings
        (Perfetto / chrome://tracing load the payload directly): one
        process lane per replica, beat slices + request spans
        correlated by rid. Built in the executor — a full ring render
        must not stall live SSE streams."""
        from generativeaiexamples_tpu.serving.flight import chrome_trace

        loop = asyncio.get_running_loop()
        trace = await loop.run_in_executor(
            self._executor, lambda: chrome_trace(self._flight_lanes()))
        return web.json_response(trace)

    async def handle_kv_export(self, request: web.Request) -> web.Response:
        """Disagg transfer source: the cached full-page prefix of the
        posted prompt (token ids) as a kv-transfer payload; 204 when
        nothing is cached. Served by replica engine-server processes
        — a fleet-fronting router has no single pool to export (501).
        The export runs as an engine control op (scheduler thread),
        bridged through the executor so the gather's blocking host
        fetch never stalls the event loop."""
        eng = self.llm
        if eng is None or not hasattr(eng, "export_prefix_pages"):
            return web.json_response(
                {"error": "no engine-level KV surface"}, status=501)
        from generativeaiexamples_tpu.serving.disagg import (
            serialize_kv_transfer)

        body = await request.json()
        ids = list(body.get("prompt") or [])
        # Chunked-window export (disagg pipelining): start_page /
        # max_pages select a page window of the cached prefix; absent
        # = the whole prefix (the PR-14 wire, unchanged). publish
        # first scatters any newly completed pages of an IN-FLIGHT
        # prefill into the pool/tree so the window can cover them;
        # probe returns just {"pages": covered} without the payload
        # (the poll the pipelined fleet loop rides).
        start_page = int(body.get("start_page") or 0)
        max_pages = int(body.get("max_pages") or 0)
        publish = bool(body.get("publish"))
        probe = bool(body.get("probe"))
        loop = asyncio.get_running_loop()

        def _export():
            if (publish or probe) and hasattr(eng, "publish_prefill_pages"):
                covered = eng.run_control_op(
                    lambda: eng.publish_prefill_pages(ids))
                if probe:
                    return ("probe", covered)
            elif probe:
                return ("probe", 0)
            return ("export", eng.run_control_op(
                lambda: eng.export_prefix_pages(
                    ids, start_page=start_page, max_pages=max_pages)))

        try:
            kind, out = await loop.run_in_executor(self._executor, _export)
        except Exception as e:
            _LOG.warning("kv export failed: %s", e)
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "service_unavailable",
                           "code": "kv_export_failed"}}, status=503)
        if kind == "probe":
            return web.json_response({"pages": int(out or 0)})
        if out is None:
            return web.Response(status=204)
        codes, scales, n_tokens = out
        return web.Response(
            body=serialize_kv_transfer(ids[:n_tokens], codes, scales),
            content_type="application/octet-stream")

    async def handle_kv_import(self, request: web.Request) -> web.Response:
        """Disagg transfer target: seat a kv-transfer payload's pages
        into this engine's pool + radix tree; responds {"pages": n}.
        Failures (pool pressure, stopped engine) are 503 — the fleet
        falls back to colocated serving."""
        eng = self.llm
        if eng is None or not hasattr(eng, "import_prefix_pages"):
            return web.json_response(
                {"error": "no engine-level KV surface"}, status=501)
        from generativeaiexamples_tpu.serving.disagg import (
            deserialize_kv_transfer)

        buf = await request.read()
        # Chunk seat offset (disagg pipelining): the header rides the
        # binary payload untouched — the GKVT body stays the PR-14
        # wire format for every chunk.
        first_page = int(request.headers.get("X-KV-First-Page", "0") or 0)
        loop = asyncio.get_running_loop()
        try:
            ids, codes, scales = deserialize_kv_transfer(buf)
            pages = await loop.run_in_executor(
                self._executor,
                lambda: eng.run_control_op(
                    lambda: eng.import_prefix_pages(
                        ids, codes, scales, first_page=first_page)))
        except ValueError as e:  # bad payload
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error",
                           "code": "bad_kv_payload"}}, status=422)
        except Exception as e:
            _LOG.warning("kv import failed: %s", e)
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "service_unavailable",
                           "code": "kv_import_failed"}}, status=503)
        return web.json_response({"pages": int(pages)})

    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        return await self._generate(request, chat=True)

    async def handle_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._generate(request, chat=False)

    async def _generate(self, request: web.Request, chat: bool) -> web.StreamResponse:
        received = time.perf_counter()  # the flight recorder's clock
        if self.llm is None:
            return web.json_response({"error": "no LLM engine"}, status=503)
        body = await request.json()
        req = self._gen_request(body, chat, request.headers)
        if not req.session_id:
            req.session_id = request.headers.get("x-session-id", "")
        # What the flight recorder's submit event carries besides the
        # engine's own id: the work done here before the engine saw the
        # request (JSON, chat template, tokenising) and the id the
        # caller gave, so that a caller's timeline joins the engine's.
        # The caller's trace context makes `engine.generate` its child.
        req.received_time = received
        req.caller_id = request.headers.get("x-request-id", "")[:128]
        req.trace_context = tracing.extract_context(request.headers)
        # Edge admission: shed past the tier's in-flight bound with
        # 429 + Retry-After BEFORE the engine sees the request —
        # overload must cost the caller one RTT, not an unbounded
        # queue wait (serving/qos.py EdgeAdmission).
        retry_after = self.edge.try_admit(req.priority)
        if retry_after is not None:
            return web.json_response(
                {"error": {"message": f"{req.priority}-tier queue is "
                           "full; retry later",
                           "type": "rate_limit_exceeded",
                           "code": "tier_queue_full"}},
                status=429,
                headers={"Retry-After": str(max(1, round(retry_after)))})
        try:
            return await self._generate_admitted(request, body, req, chat)
        finally:
            self.edge.release(req.priority)

    async def _generate_admitted(self, request: web.Request, body: Dict,
                                 req, chat: bool) -> web.StreamResponse:
        stops = self._stop_strings(body)
        stream = bool(body.get("stream"))
        from generativeaiexamples_tpu.serving.engine import PromptTooLongError
        from generativeaiexamples_tpu.serving.fleet import (
            FleetUnavailableError)

        try:
            self.llm.submit(req)
        except FleetUnavailableError as e:
            # Every replica is draining/evicted — a server-side
            # condition (retryable), not a bad request.
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "service_unavailable",
                           "code": "no_replica_available"}},
                status=503)
        except PromptTooLongError as e:
            # OpenAI-style context-length rejection at the API boundary
            # (no silent truncation; reference rejects at server.py:63,85).
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error",
                           "code": "context_length_exceeded"}},
                status=422)
        except ValueError as e:
            # e.g. a sampled request against a greedy-only speculative
            # engine — bad client input, not a server fault.
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error",
                           "code": "unsupported_parameter"}},
                status=422)
        except RuntimeError as e:
            # Replica-side submit fault (a replica dying between
            # placement and submit, a chaos-injected fault): the
            # request was fine and the fleet has already unwound its
            # tracking — a retryable 503, never a raw 500. (Fleet
            # unavailability is caught above; it subclasses this.)
            _LOG.warning("submit failed server-side: %s", e)
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "service_unavailable",
                           "code": "replica_submit_failed"}},
                status=503)
        created = int(time.time())
        obj = "chat.completion.chunk" if chat else "text_completion"

        def chunk(delta_text: str, finish: Optional[str]) -> Dict:
            if chat:
                choice = {"index": 0, "delta": (
                    {"content": delta_text} if delta_text else {}),
                    "finish_reason": finish}
            else:
                choice = {"index": 0, "text": delta_text, "finish_reason": finish}
            return {"id": req.request_id, "object": obj, "created": created,
                    "model": body.get("model", self.model_name),
                    "choices": [choice]}

        if stream:
            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache"})
            await resp.prepare(request)
            matcher = StopStream(stops)
            try:
                async for ev in self._events(req):
                    text, cut = matcher.push(ev["text"])
                    if text:
                        await resp.write(_sse(chunk(text, None)))
                    if cut or ev["finished"]:
                        req.cancelled = True
                        if not cut:
                            tail = matcher.flush()
                            if tail:
                                await resp.write(_sse(chunk(tail, None)))
                        await resp.write(_sse(chunk(
                            "", "stop" if cut else ev["finish_reason"])))
                        break
            except (ConnectionResetError, asyncio.CancelledError):
                req.cancelled = True
                raise
            await resp.write(_sse("[DONE]"))
            await resp.write_eof()
            return resp

        # non-streaming
        matcher = StopStream(stops)
        full = ""
        finish = None
        n_tokens = 0
        cut = False
        try:
            async for ev in self._events(req):
                text, cut = matcher.push(ev["text"])
                full += text
                n_tokens += 1 if ev["token_id"] >= 0 else 0
                finish = ev["finish_reason"]
                if cut:
                    finish = "stop"
                    req.cancelled = True
                    break
        except asyncio.CancelledError:
            req.cancelled = True  # client disconnected; stop decoding
            raise
        if not cut:
            # Track the stop-string cut separately from eos (both report
            # finish_reason "stop"): an eos-ended completion whose tail is
            # a proper prefix of a stop string must still be flushed.
            full += matcher.flush()
        msg = ({"message": {"role": "assistant", "content": full}}
               if chat else {"text": full})
        return web.json_response({
            "id": req.request_id,
            "object": "chat.completion" if chat else "text_completion",
            "created": created, "model": body.get("model", self.model_name),
            "choices": [{**msg, "index": 0, "finish_reason": finish or "stop"}],
            "usage": {"prompt_tokens": len(req.prompt_ids),
                      "completion_tokens": n_tokens,
                      "total_tokens": len(req.prompt_ids) + n_tokens},
        })

    async def handle_embeddings(self, request: web.Request) -> web.Response:
        received = time.perf_counter()
        if self.embed is None:
            return web.json_response({"error": "no embedding engine"}, status=503)
        body = await request.json()
        inputs = body.get("input", [])
        if isinstance(inputs, str):
            inputs = [inputs]
        is_query = body.get("input_type") == "query"  # NIM extension
        loop = asyncio.get_running_loop()
        timing: Dict[str, float] = {}
        vecs = await loop.run_in_executor(
            self._executor,
            lambda: self.embed.embed(inputs, is_query=is_query,
                                     timing=timing))
        return self._timed_response(received, timing, {
            "object": "list",
            "model": body.get("model", self.embed_model_name),
            "data": [{"object": "embedding", "index": i, "embedding": v.tolist()}
                     for i, v in enumerate(vecs)],
            "usage": {"prompt_tokens": 0, "total_tokens": 0},
        })

    @staticmethod
    def _timed_response(received: float, timing: Dict[str, float],
                        payload: Dict) -> web.Response:
        """The encoders' answers carry their own times in a W3C
        `Server-Timing` header (ms): `total` (this handler's entry ->
        the body serialised), and the engine's `tokenize`, `queue` and
        `ready` (serving/encoders.py). The chain's connector puts them
        on the stage that made the call."""
        resp = web.json_response(payload)
        resp.headers["Server-Timing"] = tracing.format_server_timing(
            dict(total=(time.perf_counter() - received) * 1e3, **timing))
        return resp

    async def handle_ranking(self, request: web.Request) -> web.Response:
        received = time.perf_counter()
        if self.rerank is None:
            return web.json_response({"error": "no reranking engine"}, status=503)
        body = await request.json()
        query = body["query"]["text"] if isinstance(body.get("query"), dict) \
            else body.get("query", "")
        passages = [p["text"] if isinstance(p, dict) else p
                    for p in body.get("passages", [])]
        loop = asyncio.get_running_loop()
        timing: Dict[str, float] = {}
        scores = await loop.run_in_executor(
            self._executor,
            lambda: self.rerank.score(query, passages, timing=timing))
        rankings = sorted(
            ({"index": i, "logit": float(s)} for i, s in enumerate(scores)),
            key=lambda r: -r["logit"])
        return self._timed_response(received, timing,
                                    {"rankings": rankings})


def run_server(server: OpenAIServer, host: str = "0.0.0.0", port: int = 8000):
    web.run_app(server.app, host=host, port=port, print=None)
