"""Tracing: spans across frontend -> chain server -> engine.

Parity with the reference's tracing glue (common/tracing.py +
tools/observability/*/opentelemetry_callback.py): W3C traceparent
propagation over HTTP, spans for generate/retrieve/llm with token
counts, TTFT event on first token (the reference hooks
on_llm_new_token, opentelemetry_callback.py:248). Toggled by
tracing.enabled / ENABLE_TRACING.

Backends: the OpenTelemetry SDK when importable; otherwise a built-in
minimal tracer with the same span/propagation semantics (spans with
attributes + events, parent/child via W3C traceparent, pluggable
exporter with `.export([spans])`). The built-in path keeps tracing real
in environments that ship only the otel namespace shim (this image).

Beside the spans, and ALWAYS on: the request `Timeline`. The chain
server creates one per /generate request and attaches it to the thread
that runs the chain; every outermost `span(name)` on that thread then
stamps (name, start, end) on it, on `time.monotonic()`, whether or not
tracing is enabled. A stage starts where the previous one ended, so the
stages tile the request with no holes. One JSON line per request goes to
the logger `gaie.timeline` after the last frame (docs/observability.md).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import random
import threading
import time
from typing import Dict, Iterator, List, Optional

_LOG = logging.getLogger(__name__)

_TRACER = None
_ENABLED = False
_PROVIDER = None
_BACKEND = None  # "otel" | "mini"
_TLS = threading.local()  # mini-backend attached context; the Timeline
_TIMELINE_LOG = logging.getLogger("gaie.timeline")

# Export/attribute failure accounting (the trainer idiom: logged once,
# counted always — a sick exporter must show up in /metrics, not
# silently drop span enrichment). Surfaced by EngineMetrics.snapshot()
# as the always-present `trace_export_errors` counter.
_ERR_LOCK = threading.Lock()
_EXPORT_ERRORS = 0
_ERR_LOGGED = False


def note_trace_error(where: str, exc: Optional[BaseException] = None) -> None:
    """Count one span export/attribute failure; log the FIRST one at
    warning (with traceback when given) so the log isn't flooded but
    the failure mode is never invisible."""
    global _EXPORT_ERRORS, _ERR_LOGGED
    with _ERR_LOCK:
        _EXPORT_ERRORS += 1
        first = not _ERR_LOGGED
        _ERR_LOGGED = True
    if first:
        _LOG.warning("span %s failed (counted in trace_export_errors; "
                     "further failures logged at debug)", where,
                     exc_info=exc)
    else:
        _LOG.debug("span %s failed", where, exc_info=exc)


def trace_export_errors() -> int:
    """Total span export/attribute failures this process (monotonic)."""
    with _ERR_LOCK:
        return _EXPORT_ERRORS


def span_trace_id(manual_span) -> str:
    """Hex trace id of a ManualSpan (or "" when tracing is off / the
    span is closed) — the rid <-> trace-id correlation key the flight
    recorder stamps onto retire events so /debug/timeline request
    spans link back to the request's distributed trace."""
    sp = getattr(manual_span, "_span", None)
    if sp is None:
        return ""
    try:
        ctx = getattr(sp, "context", None)
        if ctx is None and hasattr(sp, "get_span_context"):
            ctx = sp.get_span_context()
        tid = getattr(ctx, "trace_id", 0)
        return f"{tid:032x}" if tid else ""
    except Exception:
        return ""


# ---------------------------------------------------------------------------
# Built-in minimal tracer (used when the otel SDK is unavailable)
# ---------------------------------------------------------------------------


class _MiniContext:
    """Span context: ints like otel's SpanContext."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: int, span_id: int):
        self.trace_id = trace_id
        self.span_id = span_id


class _MiniEvent:
    __slots__ = ("name", "attributes", "timestamp")

    def __init__(self, name: str, attributes: Dict):
        self.name = name
        self.attributes = dict(attributes)
        self.timestamp = time.monotonic()


class _MiniSpan:
    def __init__(self, name: str, context: _MiniContext,
                 parent: Optional[_MiniContext], exporters: List):
        self.name = name
        self.context = context
        self.parent = parent
        self.attributes: Dict = {}
        self.events: List[_MiniEvent] = []
        # Start, end and event stamps are time.monotonic() (a wall clock
        # can step under a span); the ONE wall-clock stamp places the
        # span in calendar time for an exporter.
        self.start_time = time.monotonic()
        self.start_wall = time.time()
        self.end_time: Optional[float] = None
        self._exporters = exporters

    def set_attribute(self, key: str, value) -> None:
        self.attributes[key] = value

    def add_event(self, name: str, attributes: Optional[Dict] = None) -> None:
        self.events.append(_MiniEvent(name, attributes or {}))

    def end(self) -> None:
        if self.end_time is not None:
            return
        self.end_time = time.monotonic()
        for ex in self._exporters:
            try:
                ex.export([self])
            except Exception as e:
                # Counted, logged once — never silently dropped.
                note_trace_error("export", e)

    # context-manager protocol so `with span(...)` keeps working
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class _MiniTracer:
    """start_span-compatible subset of an otel Tracer."""

    def __init__(self):
        self.exporters: List = []

    def start_span(self, name: str, context=None, attributes=None) -> _MiniSpan:
        parent = context if isinstance(context, _MiniContext) else \
            getattr(_TLS, "ctx", None)
        trace_id = parent.trace_id if parent else random.getrandbits(128)
        sp = _MiniSpan(name, _MiniContext(trace_id, random.getrandbits(64)),
                       parent, self.exporters)
        for k, v in (attributes or {}).items():
            sp.set_attribute(k, v)
        return sp

    @contextlib.contextmanager
    def start_as_current_span(self, name: str, context=None):
        sp = self.start_span(name, context=context)
        prev = getattr(_TLS, "ctx", None)
        _TLS.ctx = sp.context
        try:
            yield sp
        finally:
            _TLS.ctx = prev
            sp.end()


class MemoryExporter:
    """In-memory exporter for the built-in backend (API-compatible with
    otel's InMemorySpanExporter where tests need it)."""

    def __init__(self):
        self._spans: List[_MiniSpan] = []
        self._lock = threading.Lock()

    def export(self, spans) -> None:
        with self._lock:
            self._spans.extend(spans)

    def get_finished_spans(self):
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


class LogExporter:
    """Default mini-backend exporter: one structured log line per span."""

    def export(self, spans) -> None:
        for s in spans:
            _LOG.info(
                "span name=%s trace=%032x dur_ms=%.1f attrs=%s events=%s",
                s.name, s.context.trace_id,
                ((s.end_time or time.monotonic()) - s.start_time) * 1e3,
                s.attributes, [e.name for e in s.events])


def _parse_traceparent(value: str) -> Optional[_MiniContext]:
    try:
        parts = value.strip().split("-")
        if len(parts) != 4:
            return None
        return _MiniContext(int(parts[1], 16), int(parts[2], 16))
    except Exception:
        return None


def setup(config=None, exporter=None) -> bool:
    """Initialize the tracer once per process. Returns enabled state.

    Re-invocation (e.g. a second ChainServer in one test process) reuses
    the existing provider — OTel's global provider can only be set once —
    and an injected `exporter` is attached with a synchronous processor
    (tests use InMemorySpanExporter).
    """
    global _TRACER, _ENABLED, _PROVIDER, _BACKEND
    enabled = (os.environ.get("ENABLE_TRACING", "").lower() in ("1", "true")
               or (config is not None and config.tracing.enabled)
               or exporter is not None)
    if not enabled:
        # Never downgrade: a disabled-config setup() after an explicit
        # enable (e.g. ChainServer init after test/process-level setup)
        # leaves the active tracer in place.
        return _ENABLED
    try:
        from opentelemetry import trace
        from opentelemetry.sdk.resources import Resource
        from opentelemetry.sdk.trace import TracerProvider
        from opentelemetry.sdk.trace.export import (
            BatchSpanProcessor, ConsoleSpanExporter, SimpleSpanProcessor)

        if _PROVIDER is None:
            service = (config.tracing.service_name if config
                       else "chain-server")
            _PROVIDER = TracerProvider(
                resource=Resource.create({"service.name": service}))
            otlp = None
            endpoint = (config.tracing.otlp_endpoint if config
                        else os.environ.get("OTEL_EXPORTER_OTLP_ENDPOINT", ""))
            if endpoint and exporter is None:
                try:
                    from opentelemetry.exporter.otlp.proto.grpc \
                        .trace_exporter import OTLPSpanExporter

                    otlp = OTLPSpanExporter(endpoint=endpoint, insecure=True)
                except Exception:
                    _LOG.warning("OTLP exporter unavailable; using console")
            if exporter is None:
                _PROVIDER.add_span_processor(
                    BatchSpanProcessor(otlp or ConsoleSpanExporter()))
            trace.set_tracer_provider(_PROVIDER)
        if exporter is not None:
            _PROVIDER.add_span_processor(SimpleSpanProcessor(exporter))
        _TRACER = trace.get_tracer("generativeaiexamples_tpu")
        _BACKEND = "otel"
        _ENABLED = True
        return True
    except Exception:
        # otel SDK unavailable: built-in minimal tracer (real spans,
        # W3C propagation, log/in-memory export).
        if _TRACER is None or _BACKEND != "mini":
            _TRACER = _MiniTracer()
            _BACKEND = "mini"
        if exporter is not None:
            _TRACER.exporters.append(exporter)
        elif not _TRACER.exporters:
            _TRACER.exporters.append(LogExporter())
        _ENABLED = True
        _LOG.info("tracing enabled with built-in tracer (otel SDK absent)")
        return True


def extract_context(headers: Dict[str, str]):
    """W3C traceparent from incoming HTTP headers (reference
    tracing.py:62-73)."""
    if not _ENABLED:
        return None
    if _BACKEND == "mini":
        hdrs = {k.lower(): v for k, v in dict(headers).items()}
        tp = hdrs.get("traceparent", "")
        return _parse_traceparent(tp) if tp else None
    try:
        from opentelemetry.propagate import extract

        return extract(dict(headers))
    except Exception:
        return None


def inject_context(headers: Dict[str, str]) -> Dict[str, str]:
    """Inject the current span context into outgoing headers (reference
    frontend/tracing.py:46-50)."""
    if not _ENABLED:
        return headers
    if _BACKEND == "mini":
        ctx = getattr(_TLS, "ctx", None)
        if ctx is not None:
            headers["traceparent"] = (
                f"00-{ctx.trace_id:032x}-{ctx.span_id:016x}-01")
        return headers
    try:
        from opentelemetry.propagate import inject

        inject(headers)
    except Exception:
        pass
    return headers


def current_context():
    """The active trace context in this thread (None when disabled) —
    handed to GenRequest.trace_context so engine spans parent onto the
    request trace across the scheduler-thread boundary."""
    if not _ENABLED:
        return None
    if _BACKEND == "mini":
        return getattr(_TLS, "ctx", None)
    try:
        from opentelemetry import context as otel_context

        return otel_context.get_current()
    except Exception:
        return None


def attach_context(ctx):
    """Attach an extracted context to the current thread; returns a
    detach token (None if disabled/no ctx)."""
    if not _ENABLED or ctx is None:
        return None
    if _BACKEND == "mini":
        prev = getattr(_TLS, "ctx", None)
        _TLS.ctx = ctx
        return ("mini", prev)
    try:
        from opentelemetry import context as otel_context

        return otel_context.attach(ctx)
    except Exception:
        return None


def detach_context(token) -> None:
    if token is None:
        return
    if isinstance(token, tuple) and token and token[0] == "mini":
        _TLS.ctx = token[1]
        return
    try:
        from opentelemetry import context as otel_context

        otel_context.detach(token)
    except Exception:
        pass


@contextlib.contextmanager
def span(name: str, attributes: Optional[Dict] = None,
         context=None) -> Iterator:
    """The one call a stage makes. Always: the outermost span on a thread
    that has a request Timeline attached stamps (name, start, end) on it
    when it ends (an exception ends it too). When tracing is enabled it
    is also an OTel/mini span, current for the block."""
    tl = getattr(_TLS, "timeline", None)
    if tl is not None:
        tl.depth += 1
    try:
        if _ENABLED and _TRACER is not None:
            with _TRACER.start_as_current_span(name, context=context) as sp:
                for k, v in (attributes or {}).items():
                    sp.set_attribute(k, v)
                yield sp
        else:
            yield _NULL_SPAN
    finally:
        if tl is not None:
            tl.depth -= 1
            if tl.depth == 0:
                tl.mark(name)


class _NullSpan:
    def set_attribute(self, *a, **k):
        pass

    def add_event(self, *a, **k):
        pass


_NULL_SPAN = _NullSpan()


# ---------------------------------------------------------------------------
# The request timeline (always on)
# ---------------------------------------------------------------------------

# The stages of a /generate request up to its first frame, in order
# (api/server.py opens `dispatch` and closes `emit`; the others are
# spans of the retriever, the pipeline and the LLM connector). The
# chain server keeps one histogram for each.
STAGES = ("dispatch", "embed", "search", "assemble", "llm_first_piece",
          "emit")


class Timeline:
    """One request's stages on `time.monotonic()` (CLOCK_MONOTONIC, which
    Linux shares between processes: the engine server's flight recorder
    stamps the same clock). `mark(name)` ends a stage NOW and starts the
    next where it ended, so consecutive stages have no gap between them
    and their durations sum to (last end - received).

    Writers: the thread the timeline is attached to (through `span`) and
    the chain server's loop thread (`mark("emit")`, `close`); the lock
    makes the cursor hand-over between the two safe."""

    __slots__ = ("rid", "received", "stages", "ok", "depth", "_cursor",
                 "_fields", "_lock")

    def __init__(self, rid: str, received: Optional[float] = None):
        self.rid = rid
        self.received = time.monotonic() if received is None else received
        # (name, start, end, fields): fields are what a callee reported
        # about its own inside (note_server_timing), ms by name.
        self.stages: List[tuple] = []
        self.ok = True
        self.depth = 0  # open spans on the attached thread
        self._cursor = self.received
        self._fields: Dict[str, float] = {}
        self._lock = threading.Lock()

    def mark(self, name: str) -> None:
        with self._lock:
            now = time.monotonic()
            self.stages.append((name, self._cursor, now, self._fields))
            self._cursor = now
            self._fields = {}

    def add_fields(self, fields: Dict[str, float]) -> None:
        """Attach a callee's own times to the stage that is open now
        (summed when the stage makes several calls)."""
        with self._lock:
            for k, v in fields.items():
                self._fields[k] = self._fields.get(k, 0.0) + v

    def durations_ms(self) -> Dict[str, float]:
        """Stage name -> ms (a stage that ran twice is summed)."""
        out: Dict[str, float] = {}
        for name, start, end, _ in self.stages:
            out[name] = out.get(name, 0.0) + (end - start) * 1e3
        return out

    def close(self) -> None:
        """The request's access-log line: ONE JSON object on the logger
        `gaie.timeline` at INFO. Called after the last frame."""
        _TIMELINE_LOG.info("%s", json.dumps({
            "rid": self.rid, "received": self.received, "ok": self.ok,
            "stages": [dict({"name": n, "start": s, "end": e},
                            **({"server": f} if f else {}))
                       for n, s, e, f in self.stages]}))


def attach_timeline(timeline: Optional[Timeline]) -> None:
    """Make `timeline` the one `span` stamps on this thread (None
    detaches: executor threads are reused)."""
    _TLS.timeline = timeline


def outgoing_headers() -> Dict[str, str]:
    """Headers for a call made on behalf of the request this thread
    serves: `x-request-id` (the timeline's rid, which the engine server
    writes into its flight recorder's submit event) and, when tracing is
    enabled, the W3C `traceparent` of the current span."""
    headers: Dict[str, str] = {}
    tl = getattr(_TLS, "timeline", None)
    if tl is not None:
        headers["x-request-id"] = tl.rid
    return inject_context(headers)


def format_server_timing(fields: Dict[str, float]) -> str:
    """{"total": 12.3} -> "total;dur=12.300" (W3C Server-Timing, ms)."""
    return ", ".join(f"{k};dur={v:.3f}" for k, v in fields.items())


def parse_server_timing(value: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for part in (value or "").split(","):
        name, _, params = part.strip().partition(";")
        for p in params.split(";"):
            k, _, v = p.strip().partition("=")
            if k == "dur":
                try:
                    out[name.strip()] = float(v)
                except ValueError:
                    pass
    return out


def note_server_timing(value: Optional[str]) -> None:
    """A callee's `Server-Timing` response header onto the stage open on
    this thread's timeline (no timeline, no header: nothing)."""
    tl = getattr(_TLS, "timeline", None)
    if tl is not None and value:
        tl.add_fields(parse_server_timing(value))


def enabled() -> bool:
    return _ENABLED


class ManualSpan:
    """Explicitly started/ended span for code that crosses threads (the
    engine scheduler opens one at prefill and ends it at slot retire —
    start_as_current_span's thread-local context doesn't fit there).
    No-ops when tracing is disabled."""

    def __init__(self, name: str, context=None,
                 attributes: Optional[Dict] = None):
        self._span = None
        if _ENABLED and _TRACER is not None:
            try:
                self._span = _TRACER.start_span(name, context=context,
                                                attributes=attributes or {})
            except Exception:
                self._span = None

    def set_attribute(self, key: str, value) -> None:
        if self._span is not None:
            self._span.set_attribute(key, value)

    def add_event(self, name: str, attributes: Optional[Dict] = None) -> None:
        if self._span is not None:
            self._span.add_event(name, attributes or {})

    def context(self):
        """This span as a parent: for `attach_context`, or a child's
        `context=` (None when tracing is off or the span has ended)."""
        if self._span is None:
            return None
        if _BACKEND == "mini":
            return self._span.context
        try:
            from opentelemetry import trace

            return trace.set_span_in_context(self._span)
        except Exception:
            return None

    def end(self) -> None:
        if self._span is not None:
            try:
                self._span.end()
            except Exception as e:
                note_trace_error("end", e)
            self._span = None


class GenerationSpan:
    """Per-request span helper: records TTFT as an event on the first
    token and token counts at the end. Built on ManualSpan (not
    thread-local "current span") so it is safe across asyncio task
    interleaving and executor threads."""

    def __init__(self, name: str = "generate", context=None):
        self.sp = ManualSpan(name, context=context)
        self.t0 = time.perf_counter()
        self.first: Optional[float] = None
        self.tokens = 0

    def __enter__(self):
        return self

    def on_token(self):
        if self.first is None:
            self.first = time.perf_counter() - self.t0
            self.sp.add_event("first_token",
                              {"ttft_ms": round(self.first * 1e3, 2)})
        self.tokens += 1

    def __exit__(self, *exc):
        self.sp.set_attribute("tokens_generated", self.tokens)
        if self.first is not None:
            self.sp.set_attribute("ttft_ms", round(self.first * 1e3, 2))
        self.sp.end()
        return False


def traced_llm_stream(name: str, iterator, attributes: Optional[Dict] = None):
    """Wrap an LLM token iterator in a span with the reference's
    callback-handler semantics (opentelemetry_callback.py:161-674):
    span opens at call, a first_token event records TTFT, and chunk/char
    counts land as attributes at end. Built on ManualSpan, NOT
    start_as_current_span: a generator span held open across yields
    would leak into the consumer's context between tokens (mis-parenting
    any span the caller opens mid-stream, and detaching out of order for
    interleaved/abandoned streams). No-op overhead when disabled."""
    if not _ENABLED:
        yield from iterator
        return
    import time as _time

    sp = ManualSpan(name, context=current_context(),
                    attributes=attributes)
    t0 = _time.perf_counter()
    first = True
    chunks = 0
    chars = 0
    try:
        for piece in iterator:
            if first:
                sp.add_event("first_token", {
                    "ttft_ms": round((_time.perf_counter() - t0) * 1e3, 2)})
                first = False
            chunks += 1
            chars += len(piece)
            yield piece
    finally:
        sp.set_attribute("chunks", chunks)
        sp.set_attribute("chars", chars)
        sp.set_attribute("duration_ms",
                         round((_time.perf_counter() - t0) * 1e3, 2))
        sp.end()
