"""Embedding + reranking engines: TPU-native NeMo Retriever replacement.

The reference runs two Triton microservices (embedding `NV-Embed-QA`,
reranking `nv-rerank-qa-mistral-4b`; docker-compose-nim-ms.yaml:24-84)
reached over HTTP. Here both are in-process JAX engines over the
models.bert encoder, with bucketed padding so each (batch, seq) shape
compiles once.

Both engines support cross-request dynamic micro-batching
(`enable_microbatch`, serving/batcher.py — the Triton dynamic-batcher
role): concurrent callers coalesce into one bucketed forward instead of
queueing batch-of-1 dispatches behind the engine lock. Off by default;
off is byte-identical to the pre-batcher engines.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import bert
from generativeaiexamples_tpu.serving.batcher import (
    MicroBatcher, MicroBatcherClosed, MicroBatchHost)
from generativeaiexamples_tpu.serving.flight import (
    PROG_ENCODER, ProgramLedger)


# One request's own times, ms by name, for the surface's Server-Timing
# header (serving/openai_server.py): `tokenize`, `queue` (the wait in
# the micro-batcher where one is on, else for the engine's lock: another
# caller's forward) and `ready` (dispatch -> result on the host: device
# time PLUS the wait behind what the device was already running; the
# program ledger splits the two, below).
Timing = Optional[Dict[str, float]]

# Rows of an encoder's own ledger while no engine drains it (an encoder
# served alone): the oldest is dropped, nothing reads them.
_OWN_LEDGER_ROWS = 256


def _enqueue_forward(engine, phase: str, rows: int, tokens: int, S: int,
                     forward: Callable[[], Any]):
    """One forward through the ledger's stamp (serving/flight.py): a
    sequence number and t_enqueue just before the dispatch call, and
    t_dispatched where the call returned (the event's `call=`). The
    encoder's threads never write a flight ring; the rows are the
    hand-off the engine's scheduler drains (`engine.programs`, which an
    OpenAIServer points at the LLM engine's ledger)."""
    ledger = engine.programs
    prog = ledger.enqueue(PROG_ENCODER, rows, tokens,
                          f"{engine.max_batch}x{S}")
    try:
        with jax.profiler.TraceAnnotation(phase, seq=prog.seq):
            out = forward()
            ledger.dispatched(prog)
    except BaseException:
        ledger.cancel(prog)
        raise
    try:
        out.copy_to_host_async()
    except AttributeError:
        pass
    return prog, out


def _fetch_forward(engine, prog, out) -> np.ndarray:
    """Block for one forward's result; the wait ends on this thread, so
    its clock reading is the program's t_ready."""
    host = np.asarray(out)
    engine.programs.ready(prog)
    return host


def _forward_timed(engine, rows, forward: Callable[[Any, Timing], Any],
                   timing: Timing):
    """The whole call rides the shared cross-request queue as ONE item
    when a micro-batcher is on (`rows` of concurrent calls that share a
    bucket merge into one pass, split back per caller); else the direct
    forward."""
    b = engine._batcher  # read once: racing disable() must not crash
    if b is not None:
        t0 = time.perf_counter()
        try:
            out, wait_ms = b.submit_timed(rows)
        except MicroBatcherClosed:
            pass  # raced a disable/re-enable: serve direct
        else:
            if timing is not None:
                timing["queue"] = wait_ms
                timing["ready"] = (time.perf_counter() - t0) * 1e3 - wait_ms
            return out
    return forward(rows, timing)


def _note_forward(timing: Timing, t_wait: float, t_lock: float) -> None:
    """A direct forward's `queue` (asked for the lock -> held it) and
    `ready` (held it -> results on the host)."""
    if timing is not None:
        timing["queue"] = (t_lock - t_wait) * 1e3
        timing["ready"] = (time.perf_counter() - t_lock) * 1e3


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _specials(tk):
    """(cls_id, sep_id) if the tokenizer defines them (BERT-style), else
    Nones (hermetic byte tokenizer)."""
    return getattr(tk, "cls_id", None), getattr(tk, "sep_id", None)


def _wrap(ids, cls_id, sep_id, limit):
    """[CLS] ids [SEP], truncated to limit with specials preserved."""
    extra = (cls_id is not None) + (sep_id is not None)
    ids = list(ids)[: max(1, limit - extra)]
    if cls_id is not None:
        ids = [cls_id] + ids
    if sep_id is not None:
        ids = ids + [sep_id]
    return ids


class EmbeddingEngine(MicroBatchHost):
    """Batched text -> normalized vector encoder (arctic-embed recipe:
    CLS pooling + L2 norm; query/document prefixes supported)."""

    QUERY_PREFIX = "Represent this sentence for searching relevant passages: "

    def __init__(self, params, cfg: bert.BertConfig, tokenizer,
                 max_batch: int = 16, buckets: Sequence[int] = (32, 128, 512),
                 use_pallas: Optional[bool] = None):
        # One-time QKV fusion: forward() projects with a [L, D, 3D]
        # wqkv; fusing here keeps the concat out of every jitted call
        # (~150 MB HBM transient per forward for BERT-large otherwise).
        self.params = bert.fuse_qkv_params(params)
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        self.buckets = [min(b, cfg.max_position) for b in buckets]
        self.use_pallas = use_pallas
        self._lock = threading.Lock()
        self.programs = ProgramLedger(capacity=_OWN_LEDGER_ROWS)
        self._fwd = jax.jit(
            lambda p, t, l: bert.forward(p, cfg, t, lengths=l,
                                         use_pallas=use_pallas)[1])

    @property
    def dim(self) -> int:
        return self.cfg.dim

    def warmup(self) -> None:
        """Compile every bucket's forward before serving: the first
        /v1/embeddings call must not stall behind a compile."""
        with self._lock:
            for S in sorted(set(self.buckets)):
                jax.block_until_ready(self._fwd(
                    self.params,
                    jnp.asarray(np.zeros((self.max_batch, S), np.int32)),
                    jnp.asarray(np.ones((self.max_batch,), np.int32))))

    def _build_microbatcher(self, max_batch, max_wait_us) -> MicroBatcher:
        """enable_microbatch() coalesces concurrent embed()/
        embed_query() CALLS — one queue item per call, so the stats
        read in caller units and dispatches_saved is measured against
        the real one-forward-per-call baseline. Calls merge only when
        their LONGEST row shares a `_bucket` rung (a short query is
        never dragged into a long document's padding), and the
        dispatcher flattens a group's rows into `_forward_ids`, which
        re-sorts by length and packs the same bucket ladder."""
        return MicroBatcher(
            "embed", self._embed_group,
            max_batch=max_batch or self.max_batch, max_wait_us=max_wait_us,
            bucket_fn=lambda ids: _bucket(
                max((len(r) for r in ids), default=1), self.buckets))

    def _embed_group(self, groups: List[List[List[int]]]) -> List[np.ndarray]:
        flat = [row for g in groups for row in g]
        vecs = self._forward_ids(flat)
        out, pos = [], 0
        for g in groups:
            out.append(vecs[pos: pos + len(g)])
            pos += len(g)
        return out

    def _encode_ids(self, texts: Sequence[str]) -> List[List[int]]:
        limit = self.buckets[-1]
        cls_id, sep_id = _specials(self.tokenizer)
        return [_wrap(self.tokenizer.encode(t), cls_id, sep_id, limit)
                for t in texts]

    def embed(self, texts: Sequence[str], is_query: bool = False,
              timing: Timing = None) -> np.ndarray:
        """[n] texts -> [n, D] float32 normalized embeddings. A `timing`
        dict is filled with this call's own times (see `Timing`)."""
        if not len(texts):
            return np.zeros((0, self.cfg.dim), np.float32)
        if is_query:
            texts = [self.QUERY_PREFIX + t for t in texts]
        t0 = time.perf_counter()
        ids = self._encode_ids(texts)
        if timing is not None:
            timing["tokenize"] = (time.perf_counter() - t0) * 1e3
        # Under the micro-batcher, calls whose longest rows share a
        # bucket merge into a length-sorted pass in the dispatcher. Rows
        # are batch-independent in the forward, so same-bucket
        # single-row calls (the coalescing case) match the direct path
        # bitwise; merging can re-chunk a mixed-length multi-row call,
        # which is the same masked computation at a different padding
        # width (float rounding may differ).
        return _forward_timed(self, ids, self._forward_ids, timing)

    def _forward_ids(self, ids: Sequence[List[int]],
                     timing: Timing = None) -> np.ndarray:
        """Token-id rows -> [n, D] embeddings: sort by length, pack into
        bucketed fixed-shape batches, one forward per chunk."""
        out = np.zeros((len(ids), self.cfg.dim), np.float32)
        order = sorted(range(len(ids)), key=lambda i: len(ids[i]))
        t_wait = time.perf_counter()
        with self._lock, jax.profiler.TraceAnnotation("encoder.embed"):
            t_lock = time.perf_counter()
            # Dispatch every batch asynchronously FIRST, then drain:
            # fetching inside the dispatch loop would serialize each
            # readback with the next batch's compute.
            pending = []
            for start in range(0, len(order), self.max_batch):
                chunk = order[start: start + self.max_batch]
                S = _bucket(max(len(ids[i]) for i in chunk) or 1, self.buckets)
                toks = np.zeros((self.max_batch, S), np.int32)
                lens = np.ones((self.max_batch,), np.int32)
                for row, i in enumerate(chunk):
                    n = max(1, len(ids[i]))
                    toks[row, : len(ids[i])] = ids[i]
                    lens[row] = n
                prog, vecs_dev = _enqueue_forward(
                    self, "encoder.embed", len(chunk),
                    int(lens[:len(chunk)].sum()), S,
                    lambda: self._fwd(self.params, jnp.asarray(toks),
                                      jnp.asarray(lens)))
                pending.append((prog, vecs_dev, chunk))
            for prog, vecs_dev, chunk in pending:
                vecs = _fetch_forward(self, prog, vecs_dev)
                for row, i in enumerate(chunk):
                    out[i] = vecs[row]
            _note_forward(timing, t_wait, t_lock)
        return out

    def embed_query(self, text: str) -> np.ndarray:
        return self.embed([text], is_query=True)[0]


class RerankEngine(MicroBatchHost):
    """Cross-encoder (query, passage) -> relevance score, replacing the
    reranking MS used by ranked_hybrid retrieval (fm-asr retriever.py:64)."""

    def __init__(self, params, cfg: bert.BertConfig, tokenizer,
                 max_batch: int = 8, buckets: Sequence[int] = (128, 256, 512),
                 use_pallas: Optional[bool] = None):
        assert cfg.n_labels >= 1, "reranker config must set n_labels"
        self.params = bert.fuse_qkv_params(params)  # see EmbeddingEngine
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        self.buckets = [min(b, cfg.max_position) for b in buckets]
        self._lock = threading.Lock()
        self.programs = ProgramLedger(capacity=_OWN_LEDGER_ROWS)
        self._fwd = jax.jit(
            lambda p, t, l, tt: bert.forward(p, cfg, t, lengths=l,
                                             token_types=tt,
                                             use_pallas=use_pallas)[1])

    def warmup(self) -> None:
        """Compile every bucket's forward before serving (see
        EmbeddingEngine.warmup)."""
        with self._lock:
            for S in sorted(set(self.buckets)):
                jax.block_until_ready(self._fwd(
                    self.params,
                    jnp.asarray(np.zeros((self.max_batch, S), np.int32)),
                    jnp.asarray(np.ones((self.max_batch,), np.int32)),
                    jnp.asarray(np.zeros((self.max_batch, S), np.int32))))

    def _build_microbatcher(self, max_batch, max_wait_us) -> MicroBatcher:
        """enable_microbatch() coalesces concurrent score() CALLS — one
        queue item per (query, passages) set, so stats read in caller
        units — flattening the group's pairs into one cross-encoder
        pass and splitting scores back per caller. Sets are
        bucket-keyed by their longest pair (`_forward_pairs` packs in
        order, unsorted), so a short set never pays a long set's
        padding."""
        return MicroBatcher(
            "rerank", self._score_group,
            max_batch=max_batch or self.max_batch, max_wait_us=max_wait_us,
            bucket_fn=lambda pairs: _bucket(
                max(max(1, len(p[0])) for p in pairs), self.buckets))

    def _score_group(self, groups: List[List[Tuple[List[int], int]]]
                     ) -> List[np.ndarray]:
        flat = [pair for g in groups for pair in g]
        scores = self._forward_pairs(flat)
        out, pos = [], 0
        for g in groups:
            out.append(np.asarray(scores[pos: pos + len(g)], np.float32))
            pos += len(g)
        return out

    def score(self, query: str, passages: Sequence[str],
              timing: Timing = None) -> np.ndarray:
        """[n] passages -> [n] float32 relevance scores (higher=better).
        A `timing` dict is filled with this call's own times."""
        if not len(passages):
            return np.zeros((0,), np.float32)
        t0 = time.perf_counter()
        limit = self.buckets[-1]
        cls_id, sep_id = _specials(self.tokenizer)
        q_ids = self.tokenizer.encode(query)
        pairs: List[Tuple[List[int], int]] = []  # (ids, segment-B start)
        for p in passages:
            p_ids = self.tokenizer.encode(p)
            # [CLS] q [SEP] p [SEP] — BERT sentence-pair convention
            head = _wrap(q_ids, cls_id, sep_id, limit)
            tail = list(p_ids)[: max(0, limit - len(head) - 1)]
            if sep_id is not None and tail:
                tail = tail + [sep_id]
            pairs.append((head + tail, len(head)))
        if timing is not None:
            timing["tokenize"] = (time.perf_counter() - t0) * 1e3
        # Under the micro-batcher concurrent (query, passages) sets merge
        # into one cross-encoder pass — see EmbeddingEngine.embed.
        return _forward_timed(self, pairs, self._forward_pairs, timing)

    def _forward_pairs(self, pairs: Sequence[Tuple[List[int], int]],
                       timing: Timing = None) -> np.ndarray:
        """(ids, segment-B start) rows -> [n] scores, one forward per
        bucketed chunk."""
        out = np.zeros((len(pairs),), np.float32)
        t_wait = time.perf_counter()
        with self._lock, jax.profiler.TraceAnnotation("encoder.rerank"):
            t_lock = time.perf_counter()
            # Same dispatch-all-then-drain overlap as EmbeddingEngine.
            pending = []
            for start in range(0, len(pairs), self.max_batch):
                chunk = pairs[start: start + self.max_batch]
                S = _bucket(max(len(c[0]) for c in chunk) or 1, self.buckets)
                toks = np.zeros((self.max_batch, S), np.int32)
                lens = np.ones((self.max_batch,), np.int32)
                types = np.zeros((self.max_batch, S), np.int32)
                for row, (ids, sep) in enumerate(chunk):
                    toks[row, : len(ids)] = ids
                    lens[row] = max(1, len(ids))
                    types[row, sep: len(ids)] = 1  # segment B = passage
                prog, scores_dev = _enqueue_forward(
                    self, "encoder.rerank", len(chunk),
                    int(lens[:len(chunk)].sum()), S,
                    lambda: self._fwd(self.params, jnp.asarray(toks),
                                      jnp.asarray(lens),
                                      jnp.asarray(types)))
                pending.append((prog, scores_dev, start, len(chunk)))
            for prog, scores_dev, start, n in pending:
                scores = _fetch_forward(self, prog, scores_dev)
                out[start: start + n] = scores[:n, 0]
            _note_forward(timing, t_wait, t_lock)
        return out
