"""Continuous-batching LLM engine: the TPU-native NIM replacement.

The reference delegates generation to TensorRT-LLM/Triton inside a NIM
container reached over HTTP (common/utils.py:265-288); this engine is
the in-process equivalent: paged KV cache, prefill/decode split,
slot-based continuous batching, per-request sampling params and SSE-
friendly token streams.

Scheduling model (single scheduler thread, the only writer of slot and
page state — SURVEY.md §5.2 calls out that the reference has no
concurrency discipline; this one is explicit):

  submit() -> waiting deque
  loop:  admit waiting requests (same-bucket admissions prefill in ONE
         batched dispatch; prompts beyond the largest bucket go through
         chunked prefill, paced one chunk per landed block while decode
         traffic is live — or, with engine.fused_prefill, folded INTO
         the decode dispatch as a rider so no standalone chunk program
         ever queues ahead of a decode block); keep up to
         pipeline_depth fused decode
         blocks in flight over ALL active slots (fixed batch shape,
         inactive slots masked to the page-0 sink, sampling on device,
         tokens chained device-side); block only on fetching the OLDEST
         in-flight block; emit/retire from it. A block runs AT MOST
         decode_steps_per_dispatch steps: the field is the ceiling, and
         serving/decode_block.py::choose_k, the one place a block's
         length is chosen, shortens it to a warm K that fits what an
         arrival can afford (BLOCK_BUDGET_MS = 60 ms of device time, by
         the step time the landed blocks read, whatever a step costs)
         while one can be waiting for it (an empty slot, a queued
         request, a prefill whose slot has not decoded yet): a freed
         slot's next occupant waits out about two blocks.

  Latency design (r4; the r3 study's measured failure modes shaped
  it): the blocking fetch itself runs on a reader thread that is
  ENGAGED ONLY while the scheduler is waiting for that one block —
  steady-state behavior (and throughput) is identical to the
  measured-fastest blocking design, but while the block runs (a
  decode program's enqueue -> ready in the program ledger,
  serving/flight.py) the scheduler admits new arrivals (their
  prefill dispatches queue behind the blocks in flight) instead of
  stalling them. First tokens don't ride block fetches at all:
  prefill-sampled tokens start a tiny copy_to_host_async at dispatch
  and are emitted when the scheduler's poll sees the transfer landed,
  so a request's way to its first token is its prefill program's
  queue (t_enqueue -> t_start), run (-> t_ready) and lag
  (-> `first_token`), each a number in the ledger. Decode blocks are
  never dispatched past a request's max_new_tokens (the `scheduled`
  cap) — an overshoot block would hold the next arrival's prefill
  behind a block of device time nobody consumes.

Shapes are always (group, bucket) for prefill and (max_batch,
max_pages) for decode, padded to power-of-two groups/K-buckets, so
steady state never recompiles; warmup() precompiles every variant.
"""

from __future__ import annotations

import contextlib
import os
import dataclasses
import logging
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models.llama import LlamaConfig
from generativeaiexamples_tpu.obs import tracing
from generativeaiexamples_tpu.serving import decode_block, engine_model
from generativeaiexamples_tpu.serving.kv_cache import (
    PageAllocator, SequencePages, WindowSequencePages, WindowTables,
    kernel_append)
from generativeaiexamples_tpu.serving import flight as flight_mod
from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    PAGES_PER_BLOCK, fold_pages, page_counts)
from generativeaiexamples_tpu.serving.served_models import metric_keys, served
from generativeaiexamples_tpu.serving.multihost import (
    fetch_addressable as mh_fetch_addressable,
    fetch_replicated as mh_fetch_replicated)
from generativeaiexamples_tpu.serving.flight import (
    EV_ADMIT, EV_ADMIT_RETRY, EV_DECODE_JOIN, EV_FIRST_TOKEN, EV_HOST_PAUSE,
    EV_KV_DEMOTE,
    EV_KV_PROMOTE, EV_KV_TRANSFER, EV_MOE_LOAD, EV_PREFILL_CHUNK,
    EV_WINDOW_CACHE,
    EV_PREFILL_DISPATCH, EV_PROGRAM, EV_QOS_PAUSE, EV_QOS_PICK,
    EV_QOS_RESUME, EV_RETIRE, EV_SUBMIT, PROG_CHUNK, PROG_DECODE,
    PROG_PREFILL, PROGRAM_CLASSES, RETIRE_CODES, ExpHistogram,
    FlightRecorder, Program, ProgramLedger)
from generativeaiexamples_tpu.serving.qos import request_tier, tier_id
from generativeaiexamples_tpu.utils.tokenizer import StreamDetokenizer

_LOG = logging.getLogger(__name__)

# The scheduler's phases on the host plane of a device trace: a
# jax.profiler.TraceAnnotation is a flag test while no profile runs, and
# while one runs it lands on the profiler's own clock, so an idle gap of
# the device reads as the phase the scheduler was in (PERF.md section 3
# lists the names). Never inside a per-token or per-slot loop.
_phase = jax.profiler.TraceAnnotation


# Failed admissions (page exhaustion) a single request may retry
# before it is failed with an `error` stream event. The cap is a
# BACKSTOP, not a queue-wait budget: attempts are counted only while
# nothing in flight could free pages (no live slots, no in-flight
# blocks) — a request legitimately waiting behind long decodes retries
# indefinitely, exactly like the pre-cap scheduler. A prompt whose
# worst case can NEVER fit the pool fails on its first attempt
# instead (see _admit_waiting).
MAX_ADMISSION_RETRIES = 64


def _to_host(blk):
    """Device block -> host numpy; speculative blocks are
    (targets, counts) tuples. Multi-host safe: sampled-token blocks are
    fully replicated across processes, and fetch_replicated raises an
    actionable error naming this seam if a layout change ever breaks
    that invariant (instead of XLA's transfer guard deep-failing)."""
    if isinstance(blk, tuple):
        return tuple(mh_fetch_replicated(b, "decode-block readback")
                     for b in blk)
    return mh_fetch_replicated(blk, "decode-block readback")


class PromptTooLongError(ValueError):
    """Prompt exceeds the engine's page capacity (prompts beyond the
    largest prefill bucket go through chunked prefill, so the cap is
    max_pages * page_size - 1). Raised at submit() so callers reject at
    the API boundary (the reference caps input at the API,
    common/server.py:63,85) instead of the engine silently truncating."""


@dataclasses.dataclass
class GenRequest:
    prompt_ids: List[int]
    max_new_tokens: int = 128
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    stop_ids: Sequence[int] = ()
    stream: "queue.Queue[Dict[str, Any]]" = dataclasses.field(
        default_factory=queue.Queue)
    submit_time: float = dataclasses.field(default_factory=time.perf_counter)
    request_id: str = ""
    # What the serving surface knows and the flight recorder's submit
    # event carries: perf_counter() at its handler's entry (0.0 from an
    # engine-direct caller; submit_time less this is the surface's work
    # before the engine saw the request: JSON, chat template,
    # tokenising) and the id the CALLER gave (`x-request-id`).
    received_time: float = 0.0
    caller_id: str = ""
    # Session identity for fleet routing (OpenAI `user` field /
    # x-session-id header): the router pins a session to the replica
    # holding its conversation KV. Unused by a single engine.
    session_id: str = ""
    # QoS tier (serving/qos.py: latency | standard | batch; anything
    # else normalizes to standard) and tenant identity (OpenAI `user`
    # field / x-tenant-id header). With engine.qos off both are inert.
    priority: str = "standard"
    tenant_id: str = ""
    # Admission attempts that failed on page exhaustion (scheduler
    # thread only; capped at MAX_ADMISSION_RETRIES so a poison request
    # cannot spin the scheduler forever).
    admission_attempts: int = 0
    cancelled: bool = False  # set by the server on client disconnect/stop
    truncate_prompt: bool = False  # opt-in: clamp instead of reject
    trace_context: Any = None  # OTel context from the caller (W3C)
    # Flight-recorder bookkeeping (scheduler thread only): submit is
    # recorded RETROACTIVELY at the first admission pop (stamped with
    # submit_time) so server threads never write the ring; the flag
    # keeps requeued requests from logging a duplicate submit.
    flight_seen: bool = False


class _Slot:
    def __init__(self, req: GenRequest, seq: SequencePages, detok, span=None):
        self.req = req
        self.seq = seq
        self.detok = detok
        self.span = span  # obs.tracing.ManualSpan; None with tracing off
        self.last_token: int = 0
        self.generated = 0
        # Tokens DISPATCHED for this slot (prefill token + K per decode
        # block it joined), including still-in-flight ones. Lets the
        # dispatcher cap K so it never launches pure-overshoot blocks
        # past max_new_tokens — each one would cost the next arrival's
        # prefill a whole block of device-queue wait.
        self.scheduled = 1
        self.prompt_len = len(req.prompt_ids)
        # True until this slot has joined its first decode block
        # (dispatch clears it; drives the K=1 TTFT ramp + first_col).
        self.awaiting_first = True
        # True once the first token has been EMITTED to the stream —
        # by the early async-prefill-fetch path or by the first decode
        # block's col 0, whichever lands first.
        self.first_emitted = False
        # Speculative bookkeeping: kv_len = tokens whose KV is KNOWN
        # stored (reconciled at block landing); kv_worst = worst-case
        # tokens of still-in-flight spec blocks. Page allocation must
        # cover kv_len + kv_worst — reconciling against only the block
        # that just landed under-allocates for its pipelined sibling.
        self.kv_len = self.prompt_len
        self.kv_worst = 0
        # True while a long prompt's chunked prefill is still running —
        # the slot holds its pages but must not join decode batches.
        self.prefilling = False
        # Set when the dispatcher can't advance this slot (page capacity
        # or pool exhaustion); finished with 'length' only after its
        # in-flight blocks drain — they may finish it legitimately.
        self.no_capacity = False
        # Emission pacing (scheduler thread only): events buffered
        # during the current block's processing, and when this slot's
        # previous block landed (drives the burst-spacing estimate).
        self.pace_buf: List[Dict] = []
        self.pace_last_land = 0.0


class _InFlight:
    """One dispatched-but-unprocessed decode block."""

    __slots__ = ("block", "metas", "K", "releases", "spec_worst",
                 "plain_spec", "t_dispatch", "plan", "prog", "t_ready",
                 "noted", "window")

    def __init__(self, block, metas, K, spec_worst: int = 0,
                 plain_spec: bool = False):
        # Flight-recorder provenance: perf_counter at dispatch return
        # and the StepPlan lattice point this block ran (stamped by
        # _dispatch_decode; zero/None on inline test drivers that
        # build _InFlight by hand).
        self.t_dispatch = 0.0
        self.plan = None
        # The block's row in the program ledger (None with the recorder
        # off) and the clock reading of the thread its fetch ended on.
        self.prog: Optional[Program] = None
        self.t_ready = 0.0
        # The flight event (code, a, b) the entry's `note_decode` made of
        # the lengths the block was dispatched with; None for most.
        self.noted = None
        # A model with window layers: the block's `window_cache` event
        # and, a live slot, the position its window pages are released
        # behind when the block lands (_note_window_cache); None for
        # every other.
        self.window = None
        # Plain blocks: device [B, K+1]. Speculative blocks: a
        # (targets [B, K, r], counts [B, K]) tuple.
        self.block = block
        self.metas = metas  # [(slot_idx, slot, first_col | base_len)]
        self.K = K
        # >0 marks a speculative block: worst-case tokens per slot
        # (K * (k+1)); landing refunds the unaccepted remainder.
        self.spec_worst = spec_worst
        # A plain (non-speculative) block dispatched on a SPECULATIVE
        # engine (the sampled-request fallback plan): landing advances
        # each surviving slot's kv_len by exactly K.
        self.plain_spec = plain_spec
        self.releases: List = []  # SequencePages freed once this block lands


class _LongPrefill:
    """In-progress chunked prefill for one long prompt. While other
    streams are decoding, the scheduler advances it at most ONE chunk
    per LANDED decode block (the `_beat` counter), so chunk dispatches
    interleave with decode blocks on the device queue — a long prompt
    admitted mid-stream delays live streams by at most ~one chunk's
    forward per token block instead of the whole prompt (VERDICT r2
    weak #3). Under the blocking loop this coincides with one chunk per
    iteration; the explicit beat keeps the invariant true for any
    scheduler that iterates without landing a block. With no live
    decode traffic, chunks run at full dispatch speed."""

    __slots__ = ("req", "slot_idx", "seq", "ids", "s_total", "pos", "slot",
                 "beat", "chunk", "stall_pos", "tier", "paused",
                 "published")

    def __init__(self, req, slot_idx, seq, ids, s_total, slot, chunk):
        self.req = req
        self.slot_idx = slot_idx
        self.seq = seq
        self.ids = ids
        # Scratch-cache length (the fused-variant compile key). The
        # cache itself lives in engine._scratch_caches[slot_idx] —
        # created INSIDE the record executors (_exec_plan/_exec_seed)
        # so leader and followers materialize it at the same stream
        # position.
        self.s_total = s_total
        self.pos = 0  # next prompt offset to feed
        self.slot = slot  # the placeholder occupying slots[slot_idx]
        # Pages already scattered into the pool + inserted into the
        # radix tree by publish_prefill_pages() (the pipelined-disagg
        # seam): the finish scatter sinks these rows so each page is
        # written exactly once, and the final insert dedups against
        # the already-published prefix.
        self.published = 0
        self.beat = -1  # reader beat at which the last chunk dispatched
        # pos observed at the last beat boundary (-1 = not yet seen);
        # drives the prefill_stall_beats counter.
        self.stall_pos = -1
        # QoS preemption state (engine.qos only): a lower-tier prefill
        # pauses at the beat boundary while a latency-tier request is
        # in its TTFT phase — no chunk rides or dispatches until the
        # pressure clears. Resume is byte-identical: pos + the scratch
        # cache ARE the chunk state, nothing else moves while paused.
        self.tier = request_tier(req)
        self.paused = False
        # Chunk width per forward: the largest bucket for long prompts;
        # prefix-cache hits on short prompts use the suffix's bucket so
        # a small uncached tail never pays a full-width forward.
        self.chunk = chunk


# EngineMetrics' counters that are an attribute of their key's name:
# snapshot() emits them and a fleet sums them from this ONE list.
_COUNTERS = (
    "decode_steps", "decode_blocks_short_for_arrival", "layer_passes",
    "decode_steps_direct_qkv",
    "decode_steps_kernel_append", "decode_steps_fused_append",
    "decode_attn_pages_live", "decode_attn_pages_walked",
    "decode_attn_updates", "decode_attn_grid_steps",
    "decode_attn_rows_skipped", "prefill_rows_live",
    "prefill_rows_bucket", "moe_pairs_routed", "moe_pairs_local",
    "moe_experts_hit", "moe_expert_steps",
    "window_pages_released", "decode_attn_window_pages_walked",
    "decode_attn_global_pages_walked",
    "prefill_tokens", "fused_steps", "fused_prefill_tokens",
    "prefill_stall_beats", "fused_sample_dispatches", "prefix_hits",
    "prefix_miss", "prefix_evictions", "prefix_hit_tokens",
    "plan_variants_compiled", "spec_fallback_steps", "kv_transfer_pages",
    "kv_transfer_device_pages", "kv_transfer_chunks", "admission_failures",
    "qos_preemptions", "stuck_thread_joins", "program_stalls",
    "program_stalls_host", "host_gc_collections", "host_gc_pauses",
    "host_gc_pause_ms", "host_late_wakes",
)


class EngineMetrics:
    """Serving metrics (BASELINE.md north stars): TTFT, tokens/s, batch
    occupancy. Lock-free reads, single-writer scheduler thread."""

    RATE_WINDOW_S = 30.0  # tokens_per_sec sliding window

    # The snapshot's keys that SUM across the replicas of a fleet
    # (fleet.counter_keys adds the pager's and the served architectures'
    # own); every other key is a gauge, a rate or a histogram.
    SUMMED = _COUNTERS + ("tokens_generated", "kv_transfer_ms",
                          "flight_beats", "flight_events")

    def __init__(self):
        # Exponential-bucket latency histograms (serving/flight.py)
        # replacing the old p50/p95 sliding deque: constant memory,
        # mergeable across a fleet, native Prometheus export. Single-
        # writer (scheduler thread observes, scrapes copy). Keys here
        # are HIST_KEYS minus the "hist_" prefix; snapshot() emits the
        # prefixed form, empty-but-present when idle.
        self.hists = {k[len("hist_"):]: ExpHistogram()
                      for k in flight_mod.HIST_KEYS}
        self.tokens_out = 0
        self.decode_steps = 0
        # Decode blocks whose K the time budget lowered because an
        # arrival could be waiting for them (decode_block.choose_k): how
        # often the rule engaged, before the page and token bounds.
        self.decode_blocks_short_for_arrival = 0
        # Block executions dispatched by decode steps: a step runs
        # every block once a pass (cfg.cache_rows of them), so
        # layer_passes / decode_steps is the depth a token pays for.
        self.layer_passes = 0
        # Decode steps dispatched through a program whose q, k and v
        # projections are plain matmuls (engine_model.direct_qkv: a
        # short block, or a looped model): the share of decode_steps
        # that engages it.
        self.decode_steps_direct_qkv = 0
        # Decode steps dispatched through a program whose K/V append is
        # the in-place Pallas kernel (kv_cache.kernel_append: an int8
        # pool, one new row a slot, kernels on) and not XLA's scatters:
        # the share of decode_steps that engages it in a launch of its
        # own; and the steps whose attention call writes the row itself
        # (engine_model.fuses_append: a looped model's walk), which
        # launch no append at all. A step counts under one of the two.
        self.decode_steps_kernel_append = 0
        self.decode_steps_fused_append = 0
        # Over the steps of both (their attention is then
        # serving/paged_attention_int8.py), summed over the B rows a
        # step: the pages the LIVE rows have, which is what the kernel
        # copies and multiplies, and what whole blocks over every row
        # would cover, which is what it walked before it stopped at a
        # row's last page and at the live rows
        # (paged_attention_int8.page_counts). live / walked is the
        # share of page copies that remain. And the online-softmax
        # updates the kernel folds the live pages into
        # (paged_attention_int8.fold_pages of this model's score tile):
        # live / updates is the pages an update, 1.0 while every page was
        # an update of its own and up to a block's 4 on long rows.
        self.decode_attn_pages_live = 0
        self.decode_attn_pages_walked = 0
        self.decode_attn_updates = 0
        # ... and the grid steps of a step's call: one a live row (one
        # with nobody live). busy_slots_acc over it is the rows a grid
        # step serves: 1.0 while every row takes a step of its own
        # (PERF.md section 6, PR 53: the step itself is 0.03 us of a
        # row's 0.33).
        self.decode_attn_grid_steps = 0
        # Over the same steps again: the idle rows that both int8 pool
        # kernels left out, (B - live slots) a step of a program that
        # hands them its `active` mask (decode_multi_step): how often
        # walking the live slots alone engages.
        self.decode_attn_rows_skipped = 0
        # Over the batched prefill programs dispatched, summed over the
        # N rows of every group: the rows the program computed (a
        # Llama's single prompt rounded up to one of
        # engine_model.prefill_row_counts; whole buckets for a group of
        # several and for a model whose prefill has no such form) and
        # the rows of its bucket. live / bucket is the share of bucket
        # rows still computed.
        self.prefill_rows_live = 0
        self.prefill_rows_bucket = 0
        # Programs whose device time (the ledger's t_start -> t_ready)
        # passed flight.STALL_FACTOR times the running median of their
        # class and shape; each also left one WARNING line.
        self.program_stalls = 0
        # ... and those of them of which the host's known pauses
        # (collections, dispatch calls, late wake-ups) cover half or
        # more of what the program ran over its class's median: the
        # host stood still, not the device.
        self.program_stalls_host = 0
        # The interpreter's collections while this engine ran
        # (flight.HostPauses: every one, and the ms inside them), the
        # `host_pause` rows written of them (1 ms or more, or
        # generation 2), and the scheduler's timed waits that came back
        # flight.LATE_WAKE_MS or more late.
        self.host_gc_collections = 0
        self.host_gc_pauses = 0
        self.host_gc_pause_ms = 0.0
        self.host_late_wakes = 0
        # KV pool geometry (set once at engine build): rows of the pool
        # (layers x passes) and the bytes one cached token takes over
        # all rows, scales included.
        self.kv_cache_rows = 0
        self.kv_bytes_per_token = 0
        # Sparse experts (0 for a model without them): token-expert
        # pairs the router chose in decode steps, those that fell on
        # experts held here and were computed, and the experts held;
        # the (expert layer, held expert, step) triples of landed decode
        # blocks, and those of them in which the expert took a pair.
        self.moe_pairs_routed = 0
        self.moe_pairs_local = 0
        self.moe_experts_hit = 0
        self.moe_expert_steps = 0
        self.experts_held = 0
        # What the served architectures count and describe beside these
        # (serving/served_models.py: every entry's `counters` and
        # `gauges`; docs/observability.md says what each one is): 0 and
        # present for every model that is not theirs.
        self._arch_keys = sum(metric_keys(), ())
        for key in self._arch_keys:
            setattr(self, key, 0)
        # Window rows (0 without them): the window in tokens and the bytes
        # a cached token takes in their pages (gauges; kv_bytes_per_token
        # counts the global rows alone), window pages given back behind a
        # sliding window, held now (a gauge), and walked by their calls;
        # the pages the GLOBAL rows' calls of such a model walked.
        self.window_tokens = 0
        self.window_bytes_per_token = 0
        self.window_pages_released = 0
        self.window_pages_held = 0
        self.decode_attn_window_pages_walked = 0
        self.decode_attn_global_pages_walked = 0
        self.busy_slots_acc = 0
        # Speculative decoding: committed tokens vs slot-steps, for the
        # acceptance-rate gauge (1.0 = no drafts accepted, k+1 = all).
        self.spec_committed = 0
        self.spec_slot_steps = 0
        # Step-plan counters: distinct plan-lattice points warmup()
        # precompiled (0 until warmup runs), and dispatches a
        # speculative engine demoted to the plain plan because a live
        # sampled request cannot ride greedy verification. Always
        # present in snapshot() — 0, never absent — like the fused
        # counters below.
        self.plan_variants_compiled = 0
        self.spec_fallback_steps = 0
        # Multi-host / planner gauges (always present — 0 when off):
        # process count of the jax.distributed job this engine spans
        # (0 = single-process build) and the per-device HBM bytes the
        # memory planner held back as headroom (0 = planner off).
        self.multihost_processes = 0
        self.planner_headroom_bytes = 0
        # Dispatch-replay counters (serving/multihost.py; always
        # present — 0 when single-process): records rank 0 published to
        # the dispatch log (incl. digests), and CRC divergences the
        # replay detector raised on this rank (any nonzero value means
        # the follower refused to enter further collectives).
        self.replay_records_published = 0
        self.replay_divergence = 0
        # Prompt tokens actually run through a prefill forward (valid
        # tokens, not bucket padding) — with the prefix cache on, a hit
        # adds only its uncached suffix here.
        self.prefill_tokens = 0
        # Fused prefill+decode dispatch (engine.fused_prefill): decode
        # blocks that carried a prefill chunk as a rider, real (un-
        # padded) prompt tokens fed through riders, and scheduling
        # beats (landed decode blocks) during which an in-progress
        # chunked prefill advanced zero tokens — the stall the fused
        # lane exists to close. Always present (0 when fusing is off)
        # so dashboards never see the keys appear and disappear.
        self.fused_steps = 0
        self.fused_prefill_tokens = 0
        self.prefill_stall_beats = 0
        # Fused first-token sampling (engine.fused_sampling): prompt
        # finishes whose sample + last_tokens scatter rode a single
        # dispatch — the prompt-completing chunk's in-program tail
        # (prefill_chunk_sample_step) or the merged sample_token_into
        # finish. Always present — 0, never absent, when the knob is
        # off.
        self.fused_sample_dispatches = 0
        # Prefix-cache counters (serving/prefix_cache.py): lookups that
        # adopted cached pages / that found nothing, pages LRU-evicted,
        # and prompt tokens whose prefill was skipped via the cache.
        self.prefix_hits = 0
        self.prefix_miss = 0
        self.prefix_evictions = 0
        self.prefix_hit_tokens = 0
        # Disaggregated prefill/decode (serving/disagg.py): pages this
        # engine IMPORTED from a prefill-role replica and the wall ms
        # those imports cost (scatter dispatch + radix insert). Always
        # present — 0, never absent, when fleet.disagg is off — and
        # summed fleet-wide (SUMMED above).
        self.kv_transfer_pages = 0
        self.kv_transfer_ms = 0.0
        # Device-path / chunked transfer (PR 17): pages that arrived as
        # device arrays (zero host serialization — the ICI fast path)
        # and import calls total (each chunk of a pipelined transfer is
        # one import control op). Always present — 0, never absent,
        # when the device path / chunking is off.
        self.kv_transfer_device_pages = 0
        self.kv_transfer_chunks = 0
        # QoS counters (serving/qos.py; always present — 0, never
        # absent, when engine.qos is off): admissions that failed on
        # page exhaustion (requeued or, past MAX_ADMISSION_RETRIES,
        # failed), lower-tier long prefills paused for a latency-tier
        # TTFT phase, and the per-tier waiting-queue depth gauge the
        # edge/router read for tier pressure.
        self.admission_failures = 0
        self.qos_preemptions = 0
        self.qos_queue_depth = {"latency": 0, "standard": 0, "batch": 0}
        # stop()-path joins that timed out with the thread still alive
        # (scheduler/reader/pacer wedged on a device op or a lock):
        # logged once per stop and COUNTED — a silent ignored join is
        # how zombie threads accumulate unobserved. Always present.
        self.stuck_thread_joins = 0
        # Session KV pager (serving/kv_pager.py): the pager keeps its
        # own counters behind the tier lock; the engine installs its
        # stats() here so every scrape reads live values. None (pager
        # off) emits zeros for every KV_PAGER_KEYS key — present,
        # never absent, like the router/QoS counters.
        self.kv_pager_stats = None
        # Flight recorder (serving/flight.py): same hook shape as the
        # pager — the engine installs its recorder's stats() so every
        # scrape reads live beat/event counters; None emits zeros for
        # every FLIGHT_KEYS key (present, never absent).
        self.flight_stats = None
        self.started = time.perf_counter()
        # (timestamp, n_tokens) per decode dispatch for the sliding rate.
        self._token_events: deque = deque(maxlen=8192)
        self._lock = threading.Lock()  # scheduler appends vs scrape iterates

    def record_ttft(self, ms: float) -> None:
        # Scheduler thread only (single-writer, like every histogram).
        self.hists["ttft_ms"].observe(ms)

    def record_tokens(self, n: int) -> None:
        if n <= 0:
            return
        with self._lock:
            self._token_events.append((time.perf_counter(), n))

    def reset_window(self) -> None:
        """Clear the sliding-rate event buffer so the next
        tokens_per_sec() reading covers only traffic from now on —
        benchmarks call this at phase boundaries so an idle gap before
        the measured phase can't stretch the window's span."""
        with self._lock:
            self._token_events.clear()

    def tokens_per_sec(self, window_s: Optional[float] = None) -> float:
        """Live throughput GAUGE over a sliding window (default 30 s):
        tokens between the oldest in-window emission event and now.
        This is deliberately NOT the same definition as a benchmark's
        job throughput (total tokens / job wall), which includes the
        prefill ramp before the first emission and the final drain; on
        a saturated steady state the two agree, on a short burst the
        gauge reads a few percent higher (r4 VERDICT weak #6 — the two
        meters measured different things, both correctly)."""
        window_s = window_s or self.RATE_WINDOW_S
        now = time.perf_counter()
        cutoff = now - window_s
        with self._lock:
            events = [(t, n) for t, n in self._token_events if t >= cutoff]
        if not events:
            return 0.0
        total = sum(n for _, n in events)
        # Rate over the observed span (oldest event -> now), floored so a
        # single burst doesn't divide by ~0.
        span = max(now - events[0][0], 1e-3)
        return total / span

    def snapshot(self) -> Dict[str, Any]:
        hist_snaps = {f"hist_{k}": h.snapshot()
                      for k, h in self.hists.items()}
        ttft = hist_snaps["hist_ttft_ms"]
        occ = (self.busy_slots_acc / self.decode_steps
               if self.decode_steps else 0.0)
        out = {
            # Estimated from the exponential-bucket histogram (the old
            # sliding deque's exact-window percentiles were neither
            # mergeable across a fleet nor Prometheus-exportable);
            # None until a first token has been recorded, as before.
            "ttft_p50_ms": ttft["p50"], "ttft_p95_ms": ttft["p95"],
            "tokens_generated": self.tokens_out,
            **{key: getattr(self, key) for key in _COUNTERS},
            "kv_cache_rows": self.kv_cache_rows,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "experts_held": self.experts_held,
            "window_tokens": self.window_tokens,
            "window_bytes_per_token": self.window_bytes_per_token,
            "window_pages_held": self.window_pages_held,
            **{key: getattr(self, key) for key in self._arch_keys},
            "mean_batch_occupancy": occ,
            "tokens_per_sec": self.tokens_per_sec(),
            "multihost_processes": self.multihost_processes,
            "planner_headroom_bytes": self.planner_headroom_bytes,
            "replay_records_published": self.replay_records_published,
            "replay_divergence": self.replay_divergence,
            # Always present — 0, never absent (the PR-5 counter
            # convention): dashboards must not see the speculation
            # gauge appear and disappear with traffic.
            "spec_tokens_per_step": (self.spec_committed
                                     / self.spec_slot_steps
                                     if self.spec_slot_steps else 0.0),
            "kv_transfer_ms": round(self.kv_transfer_ms, 3),
            # Copied so a scrape never observes the scheduler mutating
            # the gauge mid-iteration (dict reads are GIL-atomic, the
            # copy just freezes the snapshot).
            "qos_queue_depth": dict(self.qos_queue_depth),
        }
        # Fleet-router counters (serving/router.py): a single engine
        # never routes, but the keys are ALWAYS present — 0/{}, never
        # absent — so dashboards read one schema whether /metrics is
        # served by an engine or a fleet (which overrides these with
        # real values). One shared key list; drift cannot desync the
        # two sides.
        from generativeaiexamples_tpu.serving.router import (
            ROUTER_COUNTER_KEYS)

        out.update(dict.fromkeys(ROUTER_COUNTER_KEYS, 0))
        out["router_queue_depth"] = {}
        out["router_tier_depth"] = {}
        # Elastic-fleet control-plane counters (serving/fleet.py
        # FleetOps / serving/chaos.py ChaosStats): a single engine
        # never autoscales, upgrades or injects faults, but the keys
        # are always present — 0, never absent — so /metrics keeps one
        # schema whether an engine or a fleet serves it (the fleet
        # overrides with real values). Same shared-key-list discipline
        # as the router block above.
        from generativeaiexamples_tpu.serving.fleet import (
            CHAOS_KEYS, FLEET_OPS_KEYS)

        out.update(dict.fromkeys(FLEET_OPS_KEYS, 0))
        out.update(dict.fromkeys(CHAOS_KEYS, 0))
        # KV-pager counters/gauges (serving/kv_pager.py): one shared
        # key list, zeros when the pager is off — same always-present
        # contract as the router block above.
        from generativeaiexamples_tpu.serving.kv_pager import KV_PAGER_KEYS

        if self.kv_pager_stats is not None:
            out.update(self.kv_pager_stats())
        else:
            out.update(dict.fromkeys(KV_PAGER_KEYS, 0))
        # Flight recorder + histograms (serving/flight.py): the same
        # always-present contract — FLIGHT_KEYS zeros and empty-but-
        # present histogram dicts when the recorder/engine is idle.
        if self.flight_stats is not None:
            out.update(self.flight_stats())
        else:
            out.update(dict.fromkeys(flight_mod.FLIGHT_KEYS, 0))
        out.update(hist_snaps)
        # Span-export honesty (obs/tracing.py): attribute/export
        # failures are logged once and COUNTED, never swallowed.
        from generativeaiexamples_tpu.obs.tracing import (
            trace_export_errors)

        out["trace_export_errors"] = trace_export_errors()
        return out


# Lanes that index the page pool by layer and have no test against a
# looped model's reference (benchmark/architectures/ouro.py): option ->
# what it would run. A model with more than one pass is refused at
# engine build when one is on, by name; never served as a one-pass model.
_ONE_PASS_LANES = (
    ("speculative_k", "the verify and tree-verify steps"),
    ("step_plans", "the composed step-plan lattice"),
    ("fused_prefill", "the fused decode + prefill-chunk step"),
    ("prefix_cache", "prefix-page reuse and the disaggregated KV transfer"),
    ("kv_pager", "page demotion and promotion"),
)


def _refuse_unwalked_lanes(cfg: LlamaConfig, ecfg: EngineConfig,
                           mesh=None) -> None:
    """Refuse, by name, the lanes that are on and have no form for `cfg`'s
    architecture: `_ONE_PASS_LANES` and its entry's own."""
    entry = served(cfg)
    caches = entry.caches(cfg)
    if caches is None:
        return
    kv_dtype = jnp.dtype(ecfg.kv_dtype).name
    on = [(name, what) for name, what in _ONE_PASS_LANES
          if getattr(ecfg, name)]
    on += [(name.format(kv_dtype=kv_dtype), what)
           for is_on, name, what in entry.lanes if is_on(ecfg, mesh)]
    if on:
        raise ValueError(
            f"{caches}; not served with "
            + ", ".join(f"engine.{name} ({what})" for name, what in on)
            + f": {entry.why_not}; turn them off")


class LLMEngine:
    """Single-host engine over one jax device, or tensor-parallel over a
    device mesh.

    With `mesh`: params must already be placed with
    serving.sharding.shard_llama_params (Megatron TP layout); the KV
    page pool and the device-resident token buffer are sharded/
    replicated here, and every jitted step runs under GSPMD — XLA
    inserts the TP all-reduces over ICI. This replaces the reference's
    hidden NIM tensor parallelism (compose.env:17-18
    INFERENCE_GPU_COUNT) with in-repo, inspectable sharding.
    """

    def __init__(self, params, cfg: LlamaConfig, tokenizer,
                 engine_cfg: Optional[EngineConfig] = None,
                 n_pages: Optional[int] = None, use_pallas: Optional[bool] = None,
                 mesh=None):
        from generativeaiexamples_tpu.serving import sharding as shd

        self.params = params
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.ecfg = engine_cfg or EngineConfig()
        self.use_pallas = use_pallas
        self.mesh = mesh if shd.is_sharded(mesh) else None
        if self.mesh is not None:
            shd.validate_tp(cfg, self.mesh)
            self._replicated = shd.replicated(self.mesh)
        else:
            self._replicated = None
        # Multi-host replay runtime (serving/multihost.py): rank 0 runs
        # the scheduler and publishes each device dispatch as a record;
        # follower ranks replay them so cross-process collectives pair
        # up by launch order. Validated FIRST so an unsupported config
        # fails before any allocation.
        self._mh_log = None
        self._mh_leader = True
        if self.ecfg.multihost:
            from generativeaiexamples_tpu.serving import multihost as mh

            if jax.process_count() <= 1:
                raise mh.MultihostError(
                    "engine.multihost=true but jax.process_count() == 1; "
                    "initialize jax.distributed (mesh.coordinator_address/"
                    "num_processes/process_id or JAX_COORDINATOR_ADDRESS) "
                    "before building the engine, or turn the knob off")
            mh.validate_multihost_profile(self.ecfg, self.mesh)
            self._mh_log = mh.DispatchLog()
            self._mh_leader = jax.process_index() == 0
        self._mh_stop_sent = False
        _refuse_unwalked_lanes(cfg, self.ecfg, self.mesh)
        ps = self.ecfg.page_size
        if self.ecfg.max_seq_len < ps:
            raise ValueError(
                f"engine.max_seq_len {self.ecfg.max_seq_len} < page_size {ps}")
        self.max_pages = self.ecfg.max_seq_len // ps
        # Memory-budget planner (serving/memory_plan.py): with
        # engine.auto_pool_pages the PagePool is sized from the per-
        # device HBM accounting instead of the worst-case formula below;
        # a non-fitting plan raises MemoryPlanError here with the full
        # breakdown. Off (or explicit n_pages) = legacy sizing,
        # byte-identical.
        self.memory_plan = None
        if n_pages is None and self.ecfg.auto_pool_pages:
            from generativeaiexamples_tpu.serving.memory_plan import (
                plan_engine_memory)

            self.memory_plan = plan_engine_memory(
                cfg, self.ecfg, mesh=self.mesh,
                n_processes=jax.process_count())
            n_pages = self.memory_plan.pool_pages
            _LOG.info("auto_pool_pages: %d pages\n%s", n_pages,
                      self.memory_plan.breakdown())
        if n_pages is None:
            # +1 sequence of slack beyond the steady-state worst case:
            # retired slots' pages free only when their parked in-flight
            # block lands, and a full-batch burst can transiently want
            # one sequence more than B x max_pages; exhaustion degrades
            # to requeue/unbatched prefills, so slack is cheap insurance
            # for int8 (one fused 8b page is ~8 MB). A bf16 page at the
            # same geometry is ~16.7 MB — an extra sequence there costs
            # ~1 GB HBM at max_seq_len=8192 and can OOM configs that fit
            # before, so bf16 keeps the tight default and accepts the
            # degraded mode: in the worst-case transient (slot retired
            # with all pages parked on an in-flight block, new admission
            # fills the gap), a decode slot crossing a page boundary can
            # starve and be finished early with reason "length". Pass
            # n_pages explicitly to buy the slack back if HBM allows.
            slack = (self.max_pages
                     if jnp.dtype(self.ecfg.kv_dtype) == jnp.int8 else 0)
            n_pages = self.ecfg.max_batch_size * self.max_pages + slack + 1
        kv_sharding = scale_sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            from generativeaiexamples_tpu.serving import sharding as shd

            if jnp.dtype(self.ecfg.kv_dtype) == jnp.int8:
                kv_sharding = NamedSharding(self.mesh, shd.KV_FUSED_SPEC)
                scale_sharding = NamedSharding(self.mesh,
                                               shd.KV_FUSED_SCALE_SPEC)
            else:
                kv_sharding = NamedSharding(self.mesh, shd.KV_POOL_SPEC)
        # The architecture's entry: its pool, flags and accounting. Window
        # layers' rows have a second pool, allocator and page table; a
        # sequence holds at most `_window_table_pages` of its pages.
        self.served = entry = served(cfg)
        self.window_allocator = None
        self._window_table_pages = 0
        if entry.second_pool is not None:
            n_window_pages, self._window_table_pages = entry.second_pool(
                cfg, self.ecfg)
            self.window_allocator = PageAllocator(n_window_pages,
                                                  name="window-row KV")
        self.pool = entry.new_pool(cfg, self.ecfg, n_pages, kv_sharding,
                                   scale_sharding)
        self.allocator = PageAllocator(
            n_pages, name="KV" if self.window_allocator is None
            else "global-row KV")
        # Cross-request prefix KV reuse (serving/prefix_cache.py):
        # scheduler-thread-owned, like the allocator. The allocator's
        # reclaim hook LRU-evicts cached pages whenever live traffic
        # runs short, so the cache can never starve a sequence.
        self.prefix_cache = None
        # Session KV pager (serving/kv_pager.py): with engine.kv_pager
        # the cache's eviction DEMOTES pages HBM -> host RAM -> disk
        # (the radix tree doubles as the pager's index) and a prefix
        # match promotes non-resident pages back with one scatter —
        # paused sessions then cost ~zero HBM. None = the PR-1
        # destroy-on-evict cache, byte-identical.
        self.kv_pager = None
        if self.ecfg.kv_pager and not self.ecfg.prefix_cache:
            raise ValueError("engine.kv_pager requires engine.prefix_cache "
                             "(the radix tree is the pager's index)")
        if self.ecfg.prefix_cache:
            cap = int(max(0.0, self.ecfg.prefix_cache_capacity) * n_pages)
            if self.ecfg.kv_pager:
                from generativeaiexamples_tpu.serving.kv_pager import (
                    KVPager, PagedPrefixCache)

                self.kv_pager = KVPager(
                    self.pool,
                    host_budget_mb=self.ecfg.kv_host_budget_mb,
                    spill_dir=self.ecfg.kv_spill_dir, put=self._put,
                    max_batch_pages=self.max_pages)
                # Under multihost the pager publishes its pool_to_pages/
                # pages_to_pool launches (pager_out/pager_in records)
                # through the leader's dispatch log; followers replay
                # them from their own per-host cold store (_exec_pager_*)
                # so every rank enters the same gather/scatter programs
                # in the same order.
                self.kv_pager.mh_log = (self._mh_log if self._mh_leader
                                        else None)
                self.prefix_cache = PagedPrefixCache(
                    self.allocator, ps, cap, self.kv_pager,
                    lambda: self.pool)
            else:
                from generativeaiexamples_tpu.serving.prefix_cache import (
                    RadixPrefixCache)

                self.prefix_cache = RadixPrefixCache(self.allocator, ps,
                                                     cap)
            self.allocator.reclaim = self._reclaim_cached_pages
        self.slots: List[Optional[_Slot]] = [None] * self.ecfg.max_batch_size
        self.waiting: deque[GenRequest] = deque()
        self.metrics = EngineMetrics()
        self.metrics.kv_cache_rows = cfg.cache_rows
        self._load_rows = engine_model.expert_load_rows(cfg)
        self.metrics.experts_held = (cfg.experts_held if self._load_rows
                                     else 0)
        self.metrics.kv_bytes_per_token = sum(
            leaf.nbytes for leaf in jax.tree.leaves(entry.kv_pages(self.pool))
        ) // (n_pages * ps)
        entry.describe(self.metrics, cfg, self.ecfg, self.pool, n_pages)
        _LOG.info("kv pool: %d rows (%d layers x %d passes) x %d pages of "
                  "%d tokens, %s; %d bytes a cached token",
                  cfg.cache_rows, cfg.n_layers, cfg.n_passes, n_pages, ps,
                  jnp.dtype(self.ecfg.kv_dtype).name,
                  self.metrics.kv_bytes_per_token)
        if self.memory_plan is not None:
            self.metrics.planner_headroom_bytes = (
                self.memory_plan.headroom_bytes)
        if self._mh_log is not None:
            self.metrics.multihost_processes = jax.process_count()
            if self._mh_leader:
                # Count every record rank 0 publishes (incl. digests) —
                # followers compare it against their consumed-stream
                # position when debugging a divergence.
                m = self.metrics

                def _on_publish(kind: str) -> None:
                    m.replay_records_published += 1

                self._mh_log.on_publish = _on_publish
        if self.kv_pager is not None:
            self.metrics.kv_pager_stats = self.kv_pager.stats
        # Flight recorder (serving/flight.py): one beat record per
        # landed decode block + request lifecycle events, written by
        # the scheduler thread only into preallocated rings. Always
        # constructed (the stats()/timeline surfaces must exist);
        # engine.flight_recorder=False turns appends into one branch.
        self.flight = FlightRecorder(
            ring_size=self.ecfg.flight_ring_size,
            enabled=self.ecfg.flight_recorder)
        self.metrics.flight_stats = self.flight.stats
        # The ledger of every program this engine enqueues on its
        # device (serving/flight.py::ProgramLedger; an OpenAIServer
        # points the encoders beside it at the same one). Stamped only
        # while the recorder is on; drained by the scheduler thread
        # into `program` events (_drain_programs).
        self.programs = ProgramLedger()
        # The host's pauses: this engine's cursor into the process's
        # collections (flight.HOST_PAUSES; None until start()) and the
        # last pauses it wrote, (start, end, cause), which a stalled
        # program's interval is held against (_note_stall).
        self._pause_cursor: Optional[Tuple[int, int, float]] = None
        self._recent_pauses: deque = deque(maxlen=256)
        # One decode step's device time by the ledger's rows of the last
        # landed blocks: what decode_block.choose_k holds a block to its
        # time budget with. None (the rule off) until a block has landed
        # and for as long as the recorder, and so the ledger, is off.
        self._step_time = decode_block.StepTime()
        # Scheduler-thread beat bookkeeping for the recorder: previous
        # beat's host-ready stamp (drives the beat-gap histogram and
        # host-gap attribution) and pager pages moved since the last
        # record (promote in _lookup_prefix / demote in the reclaim
        # hook, both scheduler-side).
        self._last_beat_ready = 0.0
        self._beat_kv_demote = 0
        self._beat_kv_promote = 0
        # SLO-aware multi-tenant QoS (serving/qos.py): None = the FIFO
        # admission path, byte-identical to the pre-QoS scheduler. With
        # engine.qos on, admission order comes from the weighted-fair
        # TierScheduler and latency-tier TTFT phases pause lower-tier
        # long prefills at the beat boundary.
        self.qos = None
        if self.ecfg.qos:
            from generativeaiexamples_tpu.serving.qos import TierScheduler

            self.qos = TierScheduler({
                "latency": self.ecfg.qos_weight_latency,
                "standard": self.ecfg.qos_weight_standard,
                "batch": self.ecfg.qos_weight_batch})
        # Buckets drive prefill_step's page-write reshape, so each must be a
        # positive multiple of page_size within max_seq_len; invalid entries
        # are rounded up / dropped here instead of crashing at first request.
        max_bucket = self.max_pages * ps
        rounded = {min(-(-b // ps) * ps, max_bucket)
                   for b in self.ecfg.prefill_buckets if b > 0}
        self.buckets = sorted(rounded) or [min(-(-512 // ps) * ps, max_bucket)]
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # Control ops (serving/disagg.py KV page transfer): closures
        # queued by other threads via run_control_op() and drained at
        # the top of the scheduler loop, so the radix tree, allocator
        # and pool stay scheduler-thread-owned even when the fleet
        # brokers a cross-replica page transfer. Lock-free append /
        # popleft (the router-report deque idiom); each entry is
        # (fn, result_box, done_event).
        self._control_ops: deque = deque()
        # Chaos slow-replica injection (serving/chaos.py): extra sleep
        # per scheduler iteration. 0.0 (the permanent production value)
        # costs one float compare per beat; written by the chaos thread
        # (GIL-atomic float store, the `_running`/`req.cancelled`
        # cross-thread-flag idiom), read at the loop top.
        self.chaos_beat_delay_s = 0.0
        self._rng = jax.random.PRNGKey(0)
        # Device-resident current token per slot (decode blocks chain
        # through it; the host only reads tokens one block behind).
        self._last_tokens = jnp.zeros((self.ecfg.max_batch_size,), jnp.int32)
        # Speculative decoding state (speculative_k > 0): a device
        # token-history buffer feeds the n-gram drafter, and lengths
        # become DEVICE-authoritative (the host cannot know acceptance
        # before a block lands, so host page bookkeeping tracks upper
        # bounds and reconciles at landing).
        self._spec_k = max(0, self.ecfg.speculative_k)
        # Tree-verify drafts (engine.speculative_tree_branches): <= 1
        # keeps the linear chain (byte-identical). The commit contract
        # is unchanged either way (at most k+1 tokens per verify
        # step), but a tree step WRITES k/v for every packed node, so
        # page allocation floors at _spec_tree_nodes per step while
        # the token/commit bookkeeping stays at _spec_r.
        self._tree_branches = (max(0, self.ecfg.speculative_tree_branches)
                               if self._spec_k else 0)
        self._spec_r = self._spec_k + 1
        self._spec_tree_nodes = 1 + max(1, self._tree_branches) * self._spec_k \
            if self._spec_k else 1
        if self._spec_k:
            self._history = jnp.zeros(
                (self.ecfg.max_batch_size, self.ecfg.max_seq_len), jnp.int32)
            self._dev_lengths = jnp.ones(
                (self.ecfg.max_batch_size,), jnp.int32)
        if self._replicated is not None:
            self._rng = jax.device_put(self._rng, self._replicated)
            self._last_tokens = jax.device_put(self._last_tokens,
                                               self._replicated)
            if self._spec_k:
                self._history = jax.device_put(self._history,
                                               self._replicated)
                self._dev_lengths = jax.device_put(self._dev_lengths,
                                                   self._replicated)
        self._inflight: deque = deque()
        # Prefill-sampled first tokens en route to the host via
        # copy_to_host_async: [(device_toks, [(slot_idx, slot), ...])].
        # Emitted the moment the (tiny) transfer lands — TTFT no longer
        # rides the FIFO queue of full decode-block readbacks.
        self._pending_first: List = []
        # Off-thread blocking fetch: the reader thread runs np.asarray
        # on the oldest in-flight block while the scheduler waits on
        # _fetch_done, admitting arrivals mid-readback (the ~127 ms
        # submit->admit stall in the r3 TTFT stage table).
        self._fetch_req: "queue.Queue" = queue.Queue(maxsize=1)
        self._fetch_done = threading.Event()
        self._fetch_box: Dict[str, Any] = {}
        self._reader: Optional[threading.Thread] = None
        # The waiter thread blocks on each program's output in enqueue
        # order (a block's tokens, a prefill group's first tokens, a
        # chunk's logits, a commit's token) and stamps the ledger where
        # the wait ends, whatever the scheduler is doing meanwhile; the
        # scheduler only ever puts here.
        self._await_q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._waiter: Optional[threading.Thread] = None
        self._long_prefills: List[_LongPrefill] = []
        # Scratch KVCache registry, slot_idx -> KVCache: the device half
        # of a _LongPrefill, created INSIDE the record executors
        # (_exec_plan lazily / _exec_seed) so leader and followers
        # materialize it at the same position in the dispatch stream.
        self._scratch_caches: Dict[int, Any] = {}
        # Last rider-chunk results, slot_idx -> (chunk_logits, tok0):
        # stashed by _exec_plan on every rank, consumed by _exec_commit
        # — the commit record then never has to carry device arrays.
        self._chunk_res: Dict[int, Any] = {}
        # Follower-side per-host cold page store for pager replay,
        # cold_key -> (local codes, local scales|None), plus the
        # sharding/index metadata needed to reassemble global arrays
        # (leader-side state lives in KVPager; followers never run the
        # pager's eviction policy, they replay its launches).
        self._mh_cold: Dict[int, Any] = {}
        self._mh_cold_meta: Optional[dict] = None
        # Reader beat: landed-decode-block counter; paces chunked
        # prefills to one chunk per block while streams are live.
        self._beat = 0
        # Each in-progress long prefill holds a full-length scratch
        # KVCache on device; cap how many coexist (old synchronous path
        # peak = exactly 1).
        self._max_long_prefills = 1
        # Fused prefill+decode dispatch (engine.fused_prefill): the
        # rider's chunk width — largest power of two within both the
        # biggest bucket and the per-step token budget. 0 = fusing
        # unavailable (knob off, a non-positive budget, or a
        # speculative engine WITHOUT engine.step_plans — composable
        # plans are what give the spec engine a fused lattice point);
        # the interleaved lane then carries all chunks.
        self._fused_width = 0
        if (self.ecfg.fused_prefill
                and (self._spec_k == 0 or self.ecfg.step_plans)
                and self.ecfg.fused_token_budget > 0):
            w = 1
            while w * 2 <= min(self.buckets[-1],
                               self.ecfg.fused_token_budget):
                w *= 2
            self._fused_width = w
        # (S_total, K) fused variants precompiled by warmup(); empty
        # means any shape may dispatch and compile on demand (CPU
        # tests). Same contract as _warm_ks. _warm_spec_fused is the
        # speculative twin (fused_spec_prefill_step variants);
        # _warm_plans records every warmed StepPlan lattice point
        # (plan_variants_compiled in /metrics).
        self._warm_fused: set = set()
        self._warm_spec_fused: set = set()
        self._warm_plans: set = set()
        # (S_total, width) chunked-prefill variants warmed for the
        # interleaved lane — the tail chunk buckets to the smallest
        # warmed power-of-two width instead of padding to full chunk.
        self._warm_chunk_widths: set = set()
        # Fused first-token sampling (engine.fused_sampling): the
        # prompt-completing chunk samples + scatters its first token
        # inside the same dispatch (prefill_chunk_sample_step), and
        # other finishes merge sample_token + set_last_token into one
        # program. _warm_sample_chunks mirrors _warm_chunk_widths for
        # the sample-tail variant — a warmed engine never compiles it
        # mid-traffic; unwarmed (CPU tests) compiles on demand.
        self._fused_sampling = bool(getattr(self.ecfg, "fused_sampling",
                                            True))
        self._warm_sample_chunks: set = set()
        # Reusable host staging buffers for chunk dispatches, keyed by
        # width (one np array per width for the engine's lifetime —
        # the old path allocated a fresh (1, chunk) buffer per chunk).
        self._chunk_staging: Dict[int, np.ndarray] = {}
        self.pipeline_depth = max(1, self.ecfg.pipeline_depth)
        # K variants precompiled by warmup(); empty (no warmup, e.g.
        # CPU tests) means any K may dispatch and compile on demand.
        self._warm_ks: set = set()
        # Minimum request age before a mid-fetch admission (see
        # _fetch_block_host). 8 ms batches burst arrivals without
        # moving the staggered-load TTFT needle.
        self._admit_debounce_s = float(
            os.environ.get("ENGINE_ADMIT_DEBOUNCE_MS", "8")) / 1e3
        # Overlap block readbacks with compute (copy_to_host_async at
        # dispatch). Off by default: never measured on the chip; what
        # it could save is a decode program's `a` less its `b` less
        # its queue in the ledger (the fetch after the block is done).
        self._async_block_copy = (
            os.environ.get("ENGINE_ASYNC_BLOCK_COPY", "0") == "1")
        # Emission pacer: re-spaces block-granular token bursts for
        # interactive streams (few live streams) without delaying
        # completion. Entries keyed by id(slot):
        # {"slot", "buf" (deque), "next_t", "spacing"}; scheduler adds/
        # flushes under _pace_lock, the pacer thread drains due items.
        self._pace_lock = threading.Lock()
        self._pace_entries: Dict[int, Dict[str, Any]] = {}
        self._pace_wake = threading.Event()
        self._pace_thread: Optional[threading.Thread] = None
        # True only while _process_block_host/_process_spec_block run
        # with pacing engaged (scheduler thread; _stream_put reads it).
        self._pace_engaged = False

    # -- lifecycle ---------------------------------------------------------

    def warmup(self, buckets=None, group_sizes=None, ks=None,
               sampled: Optional[bool] = None,
               long_prompts: bool = False,
               long_prompt_lengths=None) -> "LLMEngine":
        """Precompile the prefill/decode graph variants BEFORE serving.

        Admission pads prefill groups to powers of two and decode blocks
        bucket K the same way — each (bucket, N) / K pair is its own XLA
        graph. Without warmup the first 2-request burst in live traffic
        stalls every stream behind a 20-40 s compile (measured: staggered
        16-way TTFT p50 6.5 s vs ~0.3 s single-request). Call before
        start(); the persistent compile cache makes later boots cheap.
        All dummy page-table rows point at the page-0 garbage sink, so
        warmup never touches real KV state."""
        assert not self._running, "warmup() must run before start()"
        if sampled is None:
            # Speculative engines warm the sampled-request fallback by
            # DEFAULT: since the submit-time 422 was lifted, any
            # temperature > 0 request can demote a dispatch to the
            # plain spec-state plan, and that variant compiling cold on
            # the scheduler thread freezes every live stream. Plain
            # engines keep the old opt-in (their sampled variants were
            # always reachable; callers that serve sampled traffic
            # pass sampled=True, as serving/__main__.py does).
            sampled = self._spec_k > 0
        ps = self.pool.page_size
        if group_sizes is None:
            group_sizes = []
            bound = min(self.ecfg.max_batch_size, self._prefill_cap)
            n = 1
            while n < bound:
                group_sizes.append(n)
                n *= 2
            # _prefill_group pads to the NEXT power of two, so a
            # non-power-of-two bound still produces this variant in
            # live traffic; groups never exceed max_prefill_group.
            group_sizes.append(n)
        if ks is None:
            # _dispatch_decode rounds K DOWN to a power of two; warm the
            # variant that will actually dispatch.
            k_live = max(1, self.ecfg.decode_steps_per_dispatch)
            while k_live & (k_live - 1):
                k_live &= k_live - 1
            # the short block of low occupancy and of the time budget
            ks = sorted({1, decode_block.SHORT_K, k_live})
        # The dispatcher will never pick a K outside this set while it
        # is non-empty — a cold decode variant compiling mid-traffic
        # freezes every live stream for 20-40 s. K=1 is forced in so a
        # warmed variant exists under ANY hard bound (page capacity).
        ks = sorted(set(ks) | {1})
        self._warm_ks = set(ks)
        flag_sets = [(True, False, False)]
        if sampled:
            flag_sets.append((False, True, True))
        # Every live dispatch draws from _next_key() — jax.random.split
        # has its own tiny jit graphs (split/_unstack) that would
        # otherwise compile on the scheduler thread at the first real
        # request (caught by the zero-compile subprocess test).
        key = self._next_key()
        for bucket in (buckets or self.buckets):
            for n in group_sizes:
                for flags in flag_sets:
                    toks, self.pool = engine_model.prefill_batch_step(
                        self.params, self.cfg, self.pool,
                        self._put(np.zeros((n, bucket), np.int32)),
                        self._put(np.ones((n,), np.int32)),
                        self._tables(np.zeros((n, bucket // ps), np.int32),
                                     np.zeros((n, bucket // ps), np.int32)
                                     if self.window_allocator else None),
                        self._put(np.zeros((n,), np.float32)),
                        self._put(np.ones((n,), np.float32)),
                        self._put(np.zeros((n,), np.int32)),
                        key, self.use_pallas, sampling_flags=flags,
                        mesh=self.mesh,
                        state_slots=self._state_slots(
                            np.full((n,), len(self.slots), np.int32)))
                    # The admission scatter compiles per group size;
                    # out-of-bounds indices drop, so this writes nothing.
                    self._last_tokens = engine_model.set_last_tokens(
                        self._last_tokens,
                        self._put(np.full((n,), len(self.slots), np.int32)),
                        toks)
        B = self.ecfg.max_batch_size
        if self._spec_k:
            # Spec engines dispatch verify blocks (linear or tree) per
            # outer-steps bucket instead of the plain K variants.
            for steps in ks:
                (_, _, self._last_tokens, self._dev_lengths,
                 self._history, self.pool) = engine_model.decode_spec_multi_step(
                    self.params, self.cfg, self.pool, self._history,
                    self._last_tokens, self._dev_lengths,
                    self._put(np.zeros((B, self.max_pages), np.int32)),
                    self._put(np.zeros((B,), bool)),
                    n_steps=steps, k=self._spec_k,
                    n_branches=self._tree_branches,
                    use_pallas=self.use_pallas, mesh=self.mesh)
                self._warm_plans.add(engine_model.StepPlan(
                    decode_k=steps, spec_k=self._spec_k,
                    tree_branches=self._tree_branches))
            if sampled:
                # The sampled-request fallback plan: plain decode over
                # the spec engine's device state. Fallback dispatches
                # always launch the general-sampling variant (even when
                # the demoting slot dropped out of the batch), so it is
                # the only one to warm.
                for steps in ks:
                    (_, self._last_tokens, self._dev_lengths,
                     self._history, self.pool) = \
                        engine_model.decode_plain_spec_state_multi_step(
                            self.params, self.cfg, self.pool,
                            self._history, self._last_tokens,
                            self._dev_lengths,
                            self._put(np.zeros((B, self.max_pages),
                                               np.int32)),
                            self._put(np.zeros((B,), bool)),
                            self._put(np.zeros((B,), np.float32)),
                            self._put(np.ones((B,), np.float32)),
                            self._put(np.zeros((B,), np.int32)),
                            key, steps, self.use_pallas,
                            sampling_flags=(False, True, True),
                            mesh=self.mesh)
                    self._warm_plans.add(engine_model.StepPlan(
                        decode_k=steps, spec_state=True))
            # Admission history-write variants: every (group-size,
            # bucket) shape _prefill_group can produce, plus the
            # full-width chunked-prefill row — cold scatter compiles on
            # the scheduler thread would stall live streams.
            widths = list(buckets or self.buckets)
            if long_prompts:
                widths.append(self.ecfg.max_seq_len)
            for bucket in widths:
                for n in ([1] if bucket == self.ecfg.max_seq_len
                          else group_sizes):
                    self._history, self._dev_lengths = \
                        engine_model.set_history_rows(
                            self._history, self._dev_lengths,
                            self._put(np.full((n,), B, np.int32)),
                            self._put(np.zeros((n, bucket), np.int32)),
                            self._put(np.ones((n,), np.int32)),
                            self._put(np.zeros((n,), np.int32)))
        for k in ks:
            if self._spec_k:
                break
            self._warm_plans.add(engine_model.StepPlan(decode_k=k))
            for flags in flag_sets:
                _, self._last_tokens, self.pool =                     engine_model.decode_multi_step(
                        self.params, self.cfg, self.pool,
                        self._last_tokens,
                        self._tables(np.zeros((B, self.max_pages), np.int32)),
                        self._put(np.ones((B,), np.int32)),
                        self._put(np.zeros((B,), bool)),
                        self._put(np.zeros((B,), np.float32)),
                        self._put(np.ones((B,), np.float32)),
                        self._put(np.zeros((B,), np.int32)),
                        key, k, self.use_pallas, sampling_flags=flags,
                        mesh=self.mesh)
        if long_prompts:
            # Chunked-prefill variants: one scratch-cache shape per
            # chunk multiple up to page capacity (a cold S_total would
            # otherwise compile on the scheduler thread mid-traffic,
            # freezing live streams). `long_prompt_lengths` restricts
            # warming to known serving lengths — each variant is its
            # own 20-40 s compile on a cold cache.
            from generativeaiexamples_tpu.models.llama import KVCache

            chunk = self.buckets[-1]
            if long_prompt_lengths is not None:
                s_tots = sorted({min(-(-int(s) // chunk) * chunk,
                                     self.max_pages * ps)
                                 for s in long_prompt_lengths})
            else:
                s_tots = list(range(chunk, self.max_pages * ps + 1, chunk))

            def pow2_at_least(n: int) -> int:
                w = 1
                while w < n:
                    w *= 2
                return w

            # Tail-chunk widths per scratch shape: the final partial
            # chunk buckets to the smallest warmed power-of-two width
            # instead of padding to the full chunk. With known serving
            # lengths only the widths those tails need are compiled;
            # otherwise warm the whole power-of-two ladder from
            # page_size up (each is its own XLA variant).
            tail_widths: Dict[int, set] = {s: set() for s in s_tots}
            if long_prompt_lengths is not None:
                for s in long_prompt_lengths:
                    p = min(int(s), self.max_pages * ps)
                    s_tot = min(-(-p // chunk) * chunk, self.max_pages * ps)
                    r = p % chunk
                    if r and pow2_at_least(r) < chunk:
                        tail_widths[s_tot].add(pow2_at_least(r))
            else:
                ladder = set()
                w = pow2_at_least(min(ps, chunk))
                while w < chunk:
                    ladder.add(w)
                    w *= 2
                for s_tot in s_tots:
                    tail_widths[s_tot] = set(ladder)
            logits = None
            for s_tot in s_tots:
                if self.prefix_cache is not None:
                    # Long-prompt prefix HITS seed their scratch from
                    # the pool at these same shapes; compile the gather
                    # now (result discarded — pool is not donated).
                    engine_model.pool_to_cache(
                        self.pool, self.cfg,
                        self._put(np.zeros((s_tot // ps,), np.int32)),
                        self._put(np.int32(1)))
                cache = KVCache.zeros(self.cfg, 1, max_len=s_tot)
                cache = self._place_scratch_cache(cache)
                logits, cache = engine_model.prefill_chunk_step(
                    self.params, self.cfg, cache,
                    self._put(np.zeros((1, chunk), np.int32)),
                    self._put(np.int32(1)), self.use_pallas,
                    mesh=self.mesh)
                self._warm_chunk_widths.add((s_tot, chunk))
                cache = self._warm_sample_chunk(s_tot, chunk, cache,
                                                flag_sets, key)
                for w in sorted(tail_widths[s_tot]):
                    logits, cache = engine_model.prefill_chunk_step(
                        self.params, self.cfg, cache,
                        self._put(np.zeros((1, w), np.int32)),
                        self._put(np.int32(1)), self.use_pallas,
                        mesh=self.mesh)
                    self._warm_chunk_widths.add((s_tot, w))
                    cache = self._warm_sample_chunk(s_tot, w, cache,
                                                    flag_sets, key)
                self.pool = engine_model.cache_to_pool(
                    self.pool, cache, self.cfg,
                    self._put(np.zeros((s_tot // ps,), np.int32)))
                if self._fused_width and s_tot >= self._fused_width:
                    # Fused prefill+decode variants this scratch shape
                    # can reach in live traffic: K is capped by
                    # prefill_decode_k_cap whenever a long prefill is
                    # in progress, so only those (and the always-
                    # dispatchable K=1) need compiling. Speculative
                    # engines (reachable only with engine.step_plans)
                    # warm the composed spec+rider program instead.
                    B = self.ecfg.max_batch_size
                    cap = self.ecfg.prefill_decode_k_cap
                    fks = sorted({k for k in ks if cap <= 0 or k <= cap}
                                 | {1})
                    for kf in fks:
                        if self._spec_k:
                            (_, _, self._last_tokens, self._dev_lengths,
                             self._history, self.pool, logits, cache) = \
                                engine_model.fused_spec_prefill_step(
                                    self.params, self.cfg, self.pool,
                                    self._history, self._last_tokens,
                                    self._dev_lengths,
                                    self._put(np.zeros(
                                        (B, self.max_pages), np.int32)),
                                    self._put(np.zeros((B,), bool)),
                                    cache,
                                    self._put(np.zeros(
                                        (1, self._fused_width), np.int32)),
                                    self._put(np.int32(1)),
                                    n_steps=kf, k=self._spec_k,
                                    n_branches=self._tree_branches,
                                    use_pallas=self.use_pallas,
                                    mesh=self.mesh)
                            self._warm_spec_fused.add((s_tot, kf))
                            self._warm_plans.add(engine_model.StepPlan(
                                decode_k=kf, spec_k=self._spec_k,
                                tree_branches=self._tree_branches,
                                rider_width=self._fused_width,
                                rider_s_total=s_tot))
                            continue
                        for flags in flag_sets:
                            (_, self._last_tokens, self.pool, logits,
                             cache) = engine_model.fused_decode_prefill_step(
                                self.params, self.cfg, self.pool,
                                self._last_tokens,
                                self._put(np.zeros((B, self.max_pages),
                                                   np.int32)),
                                self._put(np.ones((B,), np.int32)),
                                self._put(np.zeros((B,), bool)),
                                self._put(np.zeros((B,), np.float32)),
                                self._put(np.ones((B,), np.float32)),
                                self._put(np.zeros((B,), np.int32)),
                                key, cache,
                                self._put(np.zeros((1, self._fused_width),
                                                   np.int32)),
                                self._put(np.int32(1)), kf,
                                self.use_pallas, sampling_flags=flags,
                                mesh=self.mesh)
                            self._warm_fused.add((s_tot, kf))
                            self._warm_plans.add(engine_model.StepPlan(
                                decode_k=kf,
                                rider_width=self._fused_width,
                                rider_s_total=s_tot))
            if logits is not None:
                # The chunked-prefill FINISH path samples through its
                # own jit variants (sample_token / set_last_token),
                # distinct from the batched-prefill graph. Cold, they
                # compile on the scheduler thread mid-request — the r4
                # 2k-TTFT run-to-run instability (361 vs 1289 ms) was
                # this, visible only when the persistent compile cache
                # didn't already hold them.
                tok0 = None
                for flags in flag_sets:
                    tok0 = engine_model.sample_token(
                        logits, 0.0, 1.0, 0, key, *flags)
                self._last_tokens = engine_model.set_last_token(
                    self._last_tokens, self._put(np.int32(0)), tok0)
                self._warm_sample_into(logits, flag_sets, key)
        if self.prefix_cache is not None:
            # Prefix-cache hit variants for SHORT prompts: a hit
            # gathers into a bucket-sized scratch (pool_to_cache per
            # S_total), feeds the suffix at its own bucket width
            # (prefill_chunk_step per (S_total, chunk) pair), then
            # finishes through cache_to_pool and the chunked-prefill
            # sampler. Cold, any of these compiles on the scheduler
            # thread at the FIRST live hit — the stall warmup exists
            # to prevent.
            bset = sorted(buckets or self.buckets)
            logits = None
            for s_tot in bset:
                cache = engine_model.pool_to_cache(
                    self.pool, self.cfg,
                    self._put(np.zeros((s_tot // ps,), np.int32)),
                    self._put(np.int32(1)))
                # Same gather -> place -> chunk chain as the live hit
                # path (jit specializes on input sharding).
                cache = self._place_scratch_cache(cache)
                for chunk in [b for b in bset if b <= s_tot]:
                    logits, cache = engine_model.prefill_chunk_step(
                        self.params, self.cfg, cache,
                        self._put(np.zeros((1, chunk), np.int32)),
                        self._put(np.int32(1)), self.use_pallas,
                        mesh=self.mesh)
                    self._warm_chunk_widths.add((s_tot, chunk))
                    cache = self._warm_sample_chunk(s_tot, chunk, cache,
                                                    flag_sets, key)
                self.pool = engine_model.cache_to_pool(
                    self.pool, cache, self.cfg,
                    self._put(np.zeros((s_tot // ps,), np.int32)))
            tok0 = None
            for flags in flag_sets:
                tok0 = engine_model.sample_token(logits, 0.0, 1.0, 0,
                                                 key, *flags)
            self._last_tokens = engine_model.set_last_token(
                self._last_tokens, self._put(np.int32(0)), tok0)
            self._warm_sample_into(logits, flag_sets, key)
            if self._spec_k:
                # Hit finishes write history through the full-width
                # single-row variant (long_prompts warmup only covers
                # it when that flag is on).
                self._history, self._dev_lengths = \
                    engine_model.set_history_rows(
                        self._history, self._dev_lengths,
                        self._put(np.full((1,), B, np.int32)),
                        self._put(np.zeros((1, self.ecfg.max_seq_len),
                                           np.int32)),
                        self._put(np.ones((1,), np.int32)),
                        self._put(np.zeros((1,), np.int32)))
        if self.kv_pager is not None:
            # KV-pager promote/demote twins compile per power-of-two
            # batch width (demotion chunks and promotions both pad to
            # one): a cold gather/scatter compiling on the scheduler
            # thread mid-reclaim would freeze live streams exactly
            # when the pool is tightest. All rows point at the page-0
            # sink, so warmup never touches real KV.
            kp = self.kv_pager
            w = 1
            while True:
                row = self._put(np.zeros((w,), np.int32))
                engine_model.pool_to_pages(self.pool, row)
                codes = self._put(np.zeros((w,) + kp.codes_shape,
                                           kp.codes_dtype))
                scales = (self._put(np.zeros((w,) + kp.scales_shape,
                                             np.float32))
                          if kp.scales_shape else None)
                self.pool = engine_model.pages_to_pool(self.pool, codes,
                                                       scales, row)
                if w >= self.max_pages:
                    break
                w *= 2
        # Rider-only plans (the idle interleaved lane's chunk
        # dispatches) are warmed via the chunk-width loops above; the
        # lattice size is the observability gauge for "how many jitted
        # step programs can this engine dispatch without compiling".
        for s_tot, w in self._warm_chunk_widths:
            self._warm_plans.add(engine_model.StepPlan(
                rider_width=w, rider_s_total=s_tot))
        self.metrics.plan_variants_compiled = len(self._warm_plans)
        jax.block_until_ready(self._last_tokens)
        _LOG.info("engine warmup: %d prefill + %d decode variants compiled",
                  len(self.buckets if buckets is None else buckets)
                  * len(group_sizes) * len(flag_sets),
                  len(ks) * len(flag_sets))
        return self

    def _warm_sample_into(self, logits, flag_sets, key) -> None:
        """Compile the merged sample_token_into finish
        (engine.fused_sampling) against warmup logits for every
        sampling-flag set — shared by the long-prompts and
        prefix-cache warmup finishes so the two sites can't drift."""
        if not self._fused_sampling:
            return
        for flags in flag_sets:
            _, self._last_tokens = engine_model.sample_token_into(
                self._last_tokens, self._put(np.int32(0)), logits,
                0.0, 1.0, 0, key, *flags)

    def _warm_sample_chunk(self, s_tot: int, width: int, cache,
                           flag_sets, key):
        """Compile the fused first-token tail for one chunk shape
        (engine.fused_sampling): prefill_chunk_sample_step per
        sampling-flag set, registered in _warm_sample_chunks so the
        prompt-completing chunk may dispatch it without a mid-traffic
        compile. Chains and returns the donated scratch cache; the
        dummy slot index / sampling params mirror the neighboring
        warmup calls (garbage state, page-0 sink)."""
        if not self._fused_sampling:
            return cache
        for flags in flag_sets:
            _, self._last_tokens, cache = \
                engine_model.prefill_chunk_sample_step(
                    self.params, self.cfg, cache,
                    self._put(np.zeros((1, width), np.int32)),
                    self._put(np.int32(1)), self._last_tokens,
                    self._put(np.int32(0)), 0.0, 1.0, 0, key,
                    self.use_pallas, sampling_flags=flags, mesh=self.mesh)
        self._warm_sample_chunks.add((s_tot, width))
        self._warm_plans.add(engine_model.StepPlan(
            rider_width=width, rider_s_total=s_tot, rider_sample=True))
        return cache

    def start(self) -> "LLMEngine":
        self._running = True
        self._pause_cursor = flight_mod.HOST_PAUSES.acquire(self.flight)
        self._reader = threading.Thread(target=self._reader_loop,
                                        daemon=True, name="llm-engine-read")
        self._reader.start()
        self._waiter = threading.Thread(target=self._waiter_loop,
                                        daemon=True, name="llm-engine-wait")
        self._waiter.start()
        if self.ecfg.pace_emission_max_streams > 0:
            self._pace_thread = threading.Thread(
                target=self._pacer_loop, daemon=True, name="llm-engine-pace")
            self._pace_thread.start()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()
        return self

    def stop(self) -> None:
        # Leader tells followers to exit their replay loop BEFORE the
        # scheduler joins: a follower blocked in next_record() would
        # otherwise wait out its timeout. Exactly-once across repeated
        # stop() calls (chaos kills race health-probe eviction).
        if (self._mh_log is not None and self._mh_leader
                and not self._mh_stop_sent):
            self._mh_stop_sent = True
            try:
                self._mh_log.publish("stop")
            except Exception:
                _LOG.warning("multihost: stop record publish failed",
                             exc_info=True)
        self._running = False
        flight_mod.HOST_PAUSES.release(self.flight)
        self._wake.set()
        self._pace_wake.set()
        self._await_q.put(None)
        # A join that times out with the thread STILL ALIVE (wedged on
        # a device op / lock) must not pass silently: log once per
        # stop and count into the always-present stuck_thread_joins
        # counter so zombie accumulation is observable in /metrics
        # (and summed fleet-wide).
        # Snapshot the thread refs ONCE: concurrent stop() callers (a
        # chaos kill racing the health-probe eviction) otherwise race
        # each other nulling _reader/_pace_thread mid-check. join() on
        # an already-joined thread is a no-op, so both callers joining
        # the same locals is safe.
        stuck = []
        threads = [self._thread, self._reader, self._waiter,
                   self._pace_thread]
        self._reader = None
        self._waiter = None
        self._pace_thread = None
        for t in threads:
            if t is None:
                continue
            t.join(timeout=10)
            if t.is_alive():
                stuck.append(t.name)
        if stuck:
            _LOG.warning("engine stop: %d thread(s) still alive after "
                         "join timeout: %s", len(stuck), stuck)
            self.metrics.stuck_thread_joins += len(stuck)
        # Paced tokens still in flight at shutdown must reach their
        # consumers — a blocked stream.get would otherwise hang.
        with self._pace_lock:
            for entry in self._pace_entries.values():
                for ev in entry["buf"]:
                    entry["slot"].req.stream.put(ev)
            self._pace_entries.clear()
        if self.kv_pager is not None:
            # Drain the single-flight spill worker and drop the mmap
            # (a daemon worker mid-write at interpreter exit would
            # race the spill-dir cleanup).
            self.kv_pager.close()
        # Pending control ops (disagg page transfers) must not strand
        # their waiters once the scheduler is gone: fail them so the
        # fleet's transfer path falls back to colocated serving.
        while self._control_ops:
            _, box, done = self._control_ops.popleft()
            box["err"] = RuntimeError("engine stopped")
            done.set()

    # -- public API --------------------------------------------------------

    def submit(self, req: GenRequest) -> GenRequest:
        # Sampled requests (temperature > 0) on a speculative engine
        # are NOT rejected: greedy verification cannot honor them, so
        # dispatches with a live sampled slot run the non-speculative
        # plan over the engine's device-authoritative state instead
        # (decode_plain_spec_state_multi_step; counted by
        # metrics.spec_fallback_steps). The request serves — it just
        # doesn't speculate — and greedy traffic resumes verify plans
        # the moment no sampled slot is dispatchable.
        # Prompts beyond the largest bucket go through CHUNKED prefill
        # (bucket-size pieces into a contiguous scratch cache, then one
        # scatter into the page pool), so the real ceiling is the page
        # capacity minus one generated token.
        max_prompt = self.max_pages * self.ecfg.page_size - 1
        if not self.served.long_prompts:
            # the chunked long-prompt lane (a contiguous scratch cache of
            # K and V per head) has no latent form, carries no recurrent
            # state from chunk to chunk, holds no index rows and writes
            # one page table
            max_prompt = min(max_prompt, self.buckets[-1])
        if len(req.prompt_ids) > max_prompt:
            if not req.truncate_prompt:
                raise PromptTooLongError(
                    f"prompt is {len(req.prompt_ids)} tokens; engine max is "
                    f"{max_prompt} (page capacity minus one generated "
                    f"token)")
            req.prompt_ids = req.prompt_ids[-max_prompt:]
        with self._lock:
            self.waiting.append(req)
            self._tier_depth(req, +1)
        self._wake.set()
        return req

    def generate_stream(self, prompt_ids: Sequence[int], **kw) -> Iterator[Dict]:
        """Blocking iterator of {text, token_id, finished, ...} events."""
        req = GenRequest(prompt_ids=list(prompt_ids), **kw)
        self.submit(req)
        while True:
            ev = req.stream.get()
            yield ev
            if ev["finished"]:
                return

    def generate(self, prompt_ids: Sequence[int], **kw) -> str:
        return "".join(ev["text"] for ev in self.generate_stream(prompt_ids, **kw))

    # -- control ops / disagg KV page transfer (serving/disagg.py) ---------

    def run_control_op(self, fn, timeout_s: float = 60.0):
        """Run `fn()` on the scheduler thread — the single owner of
        slot, page, allocator and radix-tree state — and return its
        result. The fleet's KV page transfer rides this seam so a
        cross-replica export/import never races the scheduler's own
        tree mutations. Falls back to running inline when the
        scheduler is not live (tests, warm/parked engines) or when the
        caller already IS the scheduler thread."""
        t = self._thread
        if (not self._running or t is None or not t.is_alive()
                or threading.current_thread() is t):
            return fn()
        box: Dict[str, Any] = {}
        done = threading.Event()
        self._control_ops.append((fn, box, done))
        self._wake.set()
        if not done.wait(timeout_s):
            raise TimeoutError("engine control op timed out "
                               f"after {timeout_s}s")
        if "err" in box:
            raise box["err"]
        return box.get("out")

    def _drain_control_ops(self) -> None:
        """Scheduler thread, loop top: run queued control closures.
        Errors are boxed back to the waiter, never kill the loop."""
        while self._control_ops:
            fn, box, done = self._control_ops.popleft()
            try:
                box["out"] = fn()
            except BaseException as e:  # waiter re-raises
                box["err"] = e
            finally:
                done.set()

    def _cached_page_runs(self, ids: Sequence[int]):
        """Longest exportable cached prefix of `ids` as two node runs:
        the device-resident lead (the resident set is ancestor-closed)
        and — with engine.kv_pager — the demoted tail readable straight
        from its cold tier. A TIER_PENDING node ends the run (its bytes
        are mid-flight to the host)."""
        from generativeaiexamples_tpu.serving.prefix_cache import (
            TIER_DEVICE, TIER_DISK, TIER_HOST)

        nodes = self.prefix_cache.match_nodes(list(ids))
        dev: List = []
        for n in nodes:
            if n.tier != TIER_DEVICE:
                break
            dev.append(n)
        cold: List = []
        if self.kv_pager is not None:
            for n in nodes[len(dev):]:
                if n.tier not in (TIER_HOST, TIER_DISK):
                    break
                cold.append(n)
        return dev, cold

    def export_prefix_pages(self, ids: Sequence[int],
                            start_page: int = 0, max_pages: int = 0):
        """Longest cached full-page prefix of `ids` as HOST bytes —
        the disagg transfer's source half (serving/disagg.py): batched
        pool_to_pages gathers for the device-resident run — chunked at
        the pager granularity (self.max_pages), the PR-11 demotion
        idiom, so a large transfer never holds the scheduler's
        control-op slot for one monolithic gather — plus (with
        engine.kv_pager) a tier-lock read of any demoted tail, codes +
        int8 scales VERBATIM so a transfer round trip is bit-identical
        to never having left this pool. `start_page`/`max_pages`
        select a page window of the cached prefix (defaults: all of
        it) for chunked/pipelined transfers. Returns
        (codes [n,2,L,KH,ps,Hd], scales [n,2,L,KH,ps]|None, n_tokens)
        where n_tokens covers the prefix through the END of the
        window — so ids[:n_tokens] plus first_page=start_page is the
        matching import call — or None when the window is empty.
        Scheduler thread only — the fleet calls in via run_control_op.
        The blocking device->host fetch is by design: it IS the
        transfer cost the bench meters."""
        from generativeaiexamples_tpu.serving.disagg import page_geometry
        from generativeaiexamples_tpu.serving.kv_pager import gather_spans

        if self.prefix_cache is None:
            return None
        dev, cold = self._cached_page_runs(ids)
        n_total = len(dev) + len(cold)
        lo = max(0, int(start_page))
        hi = n_total if max_pages <= 0 else min(n_total,
                                                lo + int(max_pages))
        n_pages = hi - lo
        if n_pages <= 0:
            return None
        codes_shape, codes_dtype, scales_shape = page_geometry(self.pool)
        codes = np.zeros((n_pages,) + codes_shape, codes_dtype)
        scales = (np.zeros((n_pages,) + scales_shape, np.float32)
                  if scales_shape else None)
        dev_w = dev[lo:hi]
        for s_lo, s_hi in gather_spans(len(dev_w), self.max_pages):
            batch = dev_w[s_lo:s_hi]
            w = 1
            while w < len(batch):
                w *= 2
            row = np.zeros((w,), np.int32)  # padding -> sink page 0
            row[: len(batch)] = [n.page for n in batch]
            got, got_s = self._exec_pages_out(dict(row=row))
            # Pool pages are sharded on kv-heads (tensor axis): under a
            # multi-host mesh this host only owns its shard, so the
            # gather must assemble addressable shards (and fail with
            # the seam name, never a raw XLA transfer error).
            codes[s_lo:s_hi] = mh_fetch_addressable(
                got, "kv-page export gather (pool_to_pages)")[: len(batch)]
            if scales is not None:
                scales[s_lo:s_hi] = mh_fetch_addressable(
                    got_s, "kv-page export gather (pool_to_pages "
                    "scales)")[: len(batch)]
        cold_w = cold[max(lo - len(dev), 0): max(hi - len(dev), 0)]
        if cold_w:
            self.kv_pager.read_pages(
                cold_w, codes[len(dev_w):],
                None if scales is None else scales[len(dev_w):])
        return codes, scales, hi * self.pool.page_size

    # graftlint: hot-path
    def export_prefix_pages_device(self, ids: Sequence[int],
                                   start_page: int = 0,
                                   max_pages: int = 0):
        """Device-path export half (the ICI fast path): the window's
        device-RESIDENT pages as jax.Arrays straight off one batched
        pool_to_pages gather — no np.asarray, no host sync, zero
        serialization; the caller hands the arrays to the target
        engine's import_prefix_pages where device_put moves them
        chip-to-chip over ICI (int8 codes + f32 scales verbatim, so
        the route is bit-identical to the GKVT host bounce). Only the
        leading TIER_DEVICE run participates — a pager-demoted cold
        tail must take the host path. Each call caps its window at
        self.max_pages so every gather width is a warmed power-of-two
        variant; callers loop on the returned n_tokens. Returns
        (codes, scales|None, n_tokens) like export_prefix_pages, or
        None when the window holds no device-resident pages.
        Scheduler thread only — run_control_op."""
        if self.prefix_cache is None:
            return None
        dev, _ = self._cached_page_runs(ids)
        lo = max(0, int(start_page))
        hi = len(dev) if max_pages <= 0 else min(len(dev),
                                                 lo + int(max_pages))
        hi = min(hi, lo + self.max_pages)
        n_pages = hi - lo
        if n_pages <= 0:
            return None
        w = 1
        while w < n_pages:
            w *= 2
        row = np.zeros((w,), np.int32)  # padding -> sink page 0
        row[:n_pages] = [n.page for n in dev[lo:hi]]
        got, got_s = self._exec_pages_out(dict(row=row))
        return (got[:n_pages],
                None if got_s is None else got_s[:n_pages],
                hi * self.pool.page_size)

    def publish_prefill_pages(self, ids: Sequence[int]) -> int:
        """Make the COMPLETED chunks of an in-flight chunked prefill
        for `ids` exportable now — the pipelined-disagg seam: scatter
        the newly covered full pages from the scratch cache into the
        pool (same cache_to_pool variant the finish scatter compiles —
        per-page quantization makes incremental scatters bit-identical
        to the one-shot) and insert the covered prefix into the radix
        tree, so export_prefix_pages can ship those pages while later
        chunks are still computing. Idempotent and monotone: each call
        publishes only pages newly completed since the last; the
        finish scatter sinks already-published rows so every page is
        written exactly once. With no matching in-flight prefill
        (finished, or never chunked) returns the exportable coverage
        already in the tree. Returns covered full pages. Scheduler
        thread only — run_control_op."""
        if self.prefix_cache is None:
            return 0
        ids = list(ids)
        ps = self.pool.page_size
        n_full = len(ids) // ps
        if n_full <= 0:
            return 0
        for lp in self._long_prefills:
            if (lp.ids != ids or self.slots[lp.slot_idx] is not lp.slot
                    or lp.req.cancelled):
                continue
            covered = min(lp.pos // ps, n_full)
            done = max(lp.published, lp.seq.n_shared)
            if covered > done:
                row = np.zeros((lp.s_total // ps,), np.int32)  # sink 0
                row[done:covered] = lp.seq.pages[done:covered]
                self._exec_publish_pages(
                    dict(slot=np.int32(lp.slot_idx), row=row))
            if covered > lp.published:
                self.prefix_cache.insert(ids[: covered * ps],
                                         lp.seq.pages[:covered])
                freed = self.prefix_cache.trim()
                if freed:
                    self.metrics.prefix_evictions += freed
                lp.published = covered
            return lp.published
        dev, cold = self._cached_page_runs(ids)
        return min(len(dev) + len(cold), n_full)

    def import_prefix_pages(self, ids: Sequence[int], codes,
                            scales, first_page: int = 0) -> int:
        """Seat transferred page bytes into this engine's pool and
        radix tree — the disagg transfer's target half: allocate pool
        pages (reclaim may demote cold sessions, exactly like a
        promote), ONE pages_to_pool scatter, then insert the prefix
        into the tree so the very next admission takes the normal
        prefix-cache hit path (zero re-prefill of the transferred
        prefix). `codes` is either host np.ndarrays (the GKVT wire) or
        device jax.Arrays (the ICI fast path — staged on device,
        device_put to this engine's placement, never touching the
        host); `first_page` says which page of ids' prefix codes[0]
        covers, so a chunked/pipelined transfer imports window by
        window and each import dedups against what already landed.
        Returns pages imported (0 when the prefix is already
        resident); raises MemoryError when the allocator cannot cover
        the pages even after reclaim, ValueError when the window
        starts past the resident prefix (a gap — the fleet falls back
        to colocated serving either way). Scheduler thread only —
        run_control_op."""
        from generativeaiexamples_tpu.serving.prefix_cache import (
            TIER_DEVICE)

        if self.prefix_cache is None:
            raise RuntimeError("KV import needs engine.prefix_cache")
        ps = self.pool.page_size
        first = max(0, int(first_page))
        n = min(first + int(codes.shape[0]), len(ids) // ps)
        if n <= first:
            return 0

        def resident_run(upto_pages: int) -> List:
            out = []
            for node in self.prefix_cache.match_nodes(
                    list(ids[: upto_pages * ps])):
                if node.tier != TIER_DEVICE:
                    break
                out.append(node)
            return out

        # Import only the NON-resident suffix: a growing multi-turn
        # prefix re-ships every turn, and allocating pages for chunks
        # the tree already holds can reclaim-evict hot cache (or fail
        # a transfer that only needed the tail).
        have = len(resident_run(n))
        if have >= n:
            return 0  # already resident: the hit path serves as-is
        if have < first:
            raise ValueError(
                f"import window starts at page {first} but only "
                f"{have} pages of the prefix are resident — a chunk "
                "gap (an earlier window failed or was evicted)")
        if self._mh_log is not None and not isinstance(codes, np.ndarray):
            # Device-path import under multihost would stage through a
            # device-side scatter (a collective launch followers can't
            # replay) and its bytes couldn't ride the dispatch record;
            # bounce through the host so the record is self-contained.
            codes = np.asarray(codes)
            if scales is not None:
                scales = np.asarray(scales)
        device = not isinstance(codes, np.ndarray)
        t0 = time.perf_counter()
        m = n - have
        pages = self.allocator.alloc(m)
        try:
            if have and len(resident_run(have)) < have:
                # The alloc's reclaim evicted part of the resident
                # prefix out from under us: the suffix would link
                # under missing ancestors. Rare (hard pool pressure);
                # the fleet falls back to colocated serving.
                raise MemoryError(
                    "resident prefix evicted during import alloc")
            w = 1
            while w < m:
                w *= 2
            row = np.zeros((w,), np.int32)  # padding -> sink page 0
            row[:m] = pages
            if device:
                # Stage the pad on device and move straight to this
                # engine's placement — no host round trip, the whole
                # point of the fast path (single-process only; the
                # multihost bounce above forced the host path).
                buf = jnp.zeros((w,) + tuple(codes.shape[1:]),
                                codes.dtype).at[:m].set(
                                    codes[have - first: n - first])
                sbuf = None
                if scales is not None:
                    sbuf = jnp.zeros((w,) + tuple(scales.shape[1:]),
                                     jnp.float32).at[:m].set(
                                         scales[have - first: n - first])
                if self._replicated is not None:
                    buf = jax.device_put(buf, self._replicated)
                    if sbuf is not None:
                        sbuf = jax.device_put(sbuf, self._replicated)
                self._exec_pages_in(dict(row=row), buf=buf, sbuf=sbuf)
            else:
                hbuf = np.zeros((w,) + codes.shape[1:], codes.dtype)
                hbuf[:m] = codes[have - first: n - first]
                rec = dict(row=row, codes=hbuf)
                if scales is not None:
                    hs = np.zeros((w,) + scales.shape[1:], np.float32)
                    hs[:m] = scales[have - first: n - first]
                    rec["scales"] = hs
                self._exec_pages_in(rec)
            # The leading `have` chunks are guaranteed present (just
            # re-verified, nothing evicts between here and insert on
            # this thread), so insert dedups them — their payloads
            # are never adopted, only the fresh suffix pages are.
            lead = [nd.page for nd in resident_run(have)]
            self.prefix_cache.insert(list(ids[: n * ps]),
                                     lead + list(pages))
            freed = self.prefix_cache.trim()
            if freed:
                self.metrics.prefix_evictions += freed
        finally:
            # The tree retained its own references at insert; suffix
            # chunks that raced into the cache keep their existing
            # node and this release frees the duplicate page.
            self.allocator.release(pages)
        dt_ms = (time.perf_counter() - t0) * 1e3
        self.metrics.kv_transfer_pages += m
        self.metrics.kv_transfer_ms += dt_ms
        self.metrics.kv_transfer_chunks += 1
        if device:
            self.metrics.kv_transfer_device_pages += m
        self.metrics.hists["kv_transfer_ms_per_page"].observe(dt_ms / m)
        if self.flight.enabled:
            self.flight.record_event(EV_KV_TRANSFER, t0, a=float(m),
                                     b=dt_ms)
        return m

    # -- scheduler ---------------------------------------------------------

    def _free_slot_index(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _put(self, x):
        """Host array -> device. Under a mesh, explicitly replicated so
        jit never sees an input committed to a single device of a
        multi-device computation."""
        if self._replicated is not None:
            x = np.asarray(x)
            if jax.process_count() > 1:
                # device_put to a cross-process sharding launches a
                # broadcast collective (multihost assert_equal) — every
                # rank would have to mirror every host put in lockstep.
                # Replicate locally instead: each process already holds
                # the full value (leader from its scheduler, followers
                # from the dispatch record), so assembling from
                # single-device buffers is collective-free.
                bufs = [jax.device_put(x, d)
                        for d in self._replicated.addressable_devices]
                return jax.make_array_from_single_device_arrays(
                    x.shape, self._replicated, bufs)
            return jax.device_put(x, self._replicated)
        return jnp.asarray(x)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _next_key(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _loop(self) -> None:
        """Pipelined scheduler: admissions and decode dispatches are
        async (device-side sampling, device-chained tokens); the only
        blocking operation is fetching the OLDEST in-flight block, which
        overlaps the device computing the newer ones. What that costs
        a new arrival is the program ledger's t_start - t_enqueue of
        its prefill (`hist_device_queue_ms`): the rest of the block
        that is running plus the blocks enqueued behind it."""
        while self._running:
            if self.chaos_beat_delay_s > 0.0:
                # Injected slow-replica latency (chaos harness only;
                # 0.0 in production, one compare per iteration).
                time.sleep(self.chaos_beat_delay_s)
            self._drain_control_ops()
            with _phase("sched.admit"):
                did_work = self._admit_waiting()
            # Chunk forwards interleave with decode dispatches (paced
            # by the landed-block beat) instead of monopolizing the
            # device queue.
            with _phase("sched.prefill_dispatch"):
                did_work = self._advance_long_prefills() or did_work
            self._emit_ready_first_tokens()
            # Keep the dispatch pipeline full.
            while (len(self._inflight) < self.pipeline_depth
                   and any(s is not None for s in self.slots)):
                try:
                    with _phase("sched.plan"):
                        dispatched = self._dispatch_decode()
                    if not dispatched:
                        break
                    did_work = True
                except Exception:
                    # Device-side decode failure poisons the whole batch
                    # (cache state unknown): fail all active slots, keep
                    # the engine alive for new requests.
                    _LOG.exception("decode dispatch failed; failing batch")
                    self._fail_active()
                    break
            if self._inflight:
                self._land_next_block()
                did_work = True
            elif self._pending_first:
                # No blocks in flight but first tokens still en route
                # (e.g. every active request finished at its first
                # token): poll rather than sleep the full timeout.
                with _phase("sched.idle"):
                    self._timed_wait(self._wake, 0.002, "idle")
                self._wake.clear()
                continue
            if not did_work:
                # Idle boundary: the beat-gap histogram measures the
                # inter-block cadence WITHIN an active period — one
                # 10-minute idle stretch must not inject a giant
                # sample that drowns the stall signal the histogram
                # exists to expose.
                self._last_beat_ready = 0.0
                with _phase("sched.idle"):
                    self._timed_wait(self._wake, 0.02, "idle")
                self._wake.clear()

    # graftlint: hot-path
    def _land_next_block(self) -> None:
        """Land the oldest in-flight block: fetch (reader thread),
        process/emit, release parked pages, advance the beat, and
        write the beat's flight record. One scheduling beat end to
        end — inline test drivers call this instead of replicating
        the loop body."""
        fl = self._inflight.popleft()
        tokens_before = self.metrics.tokens_out
        try:
            with _phase("sched.fetch"):
                host = self._fetch_block_host(fl)
            if self._load_rows and not isinstance(host, tuple):
                host = self._note_expert_load(fl, host, time.perf_counter())
            if fl.noted is not None:
                event, a, b = fl.noted
                self.flight.record_event(event, time.perf_counter(), a=a, b=b)
            if fl.window is not None:
                self._land_window_cache(fl.window)
            # The landed block proves every program enqueued before it
            # complete: their rows resolve now, the block's own with
            # them, before the beat row that reads it.
            self._drain_programs(fl.prog.seq if fl.prog is not None else -1)
            if fl.prog is not None and fl.prog.t_start:
                # the ledger's interval of this block: what a step
                # costs whoever waits out the next one (decode_block)
                self._step_time.note(fl.prog.ran_ms, fl.K)
            with _phase("sched.emit"):
                self._process_block_host(fl, host)
        except Exception:
            _LOG.exception("decode block failed; failing batch")
            self._fail_active()
        finally:
            # Pages parked on this block are released even on
            # failure — they back retired slots this very block
            # may still have written to.
            for seq in fl.releases:
                seq.release()
            fl.releases = []
            self._gauge_window_pages()
        with _phase("sched.retire"):
            self._reap_starved()
            self._beat += 1
            self._note_prefill_stalls()
            self._record_beat(fl, fl.t_ready,
                              self.metrics.tokens_out - tokens_before)

    def _attn_fold(self, table_width: int) -> int:
        """`fold_pages` of a decode step's int8 attention calls over
        tables of `table_width` pages: the score tile one chip sees."""
        tensor = 1 if self.mesh is None else self.mesh.shape.get("tensor", 1)
        return fold_pages(self.cfg.n_kv_heads // tensor,
                          self.cfg.n_heads // self.cfg.n_kv_heads,
                          min(PAGES_PER_BLOCK, table_width))

    def _note_window_cache(self, lengths, active, win_base, K: int):
        """A decode block of a model with window layers, from the lengths
        and tables the host dispatches it with: in every step a live slot
        attends its whole context in each global layer and the last
        `window` tokens of it in each window layer, whose kernel call
        walks the slot's window table from its first page. Counts the
        window pages walked and returns what `_land_window_cache` needs
        when the block lands: the block's `window_cache` event (a =
        cached tokens its attention calls see over layers x context, what
        one kind of row would have seen; b = pages x rows the live slots
        hold in both pools over what one table for every row would hold;
        aux = the window pages walked, the calls that walked them, a call
        being one window layer's of one step, the softmax updates they
        were folded into, and the pages the global rows' calls walked, a
        live slot's whole context a call, with those calls) and, a live
        slot, its sequence and the position no later step reads behind;
        None for every other model."""
        if self.window_allocator is None:
            return None
        wr = self.cfg.window_rows
        ps = self.pool.page_size
        live = np.asarray(lengths, np.int64)[active]
        ctx = live[None, :] + np.arange(K)[:, None]          # [K, n_live]
        seen = wr.n_global * ctx + wr.n_window * np.minimum(ctx, wr.window)
        maxw = self._window_table_pages
        pages, _, updates, _ = page_counts(ctx - win_base[active], ps, maxw,
                                           fold=self._attn_fold(maxw))
        walked, updates = pages * wr.n_window, updates * wr.n_window
        self.metrics.decode_attn_window_pages_walked += walked
        glob = page_counts(ctx, ps, self.max_pages)[0] * wr.n_global
        self.metrics.decode_attn_global_pages_walked += glob
        seqs = [self.slots[i].seq for i in active]
        held = sum(wr.n_global * len(q.pages)
                   + wr.n_window * len(q.window_pages) for q in seqs)
        one = (wr.n_global + wr.n_window) * sum(len(q.pages) for q in seqs)
        event = (float(seen.sum()) / float(self.cfg.n_layers * ctx.sum()),
                 held / one, f"window_pages={walked} calls={K * wr.n_window} "
                 f"updates={updates} global_pages={glob} "
                 f"global_calls={K * wr.n_global}")
        # the block's last step has length `live + K - 1`; the next
        # block's first is one longer, and its window starts there
        return event, [(q, int(n) + K - wr.window)
                       for q, n in zip(seqs, live)]

    def _land_window_cache(self, window) -> None:
        """A landed decode block of a model with window layers: every
        step that read the pages behind its slots' windows has run, so
        they go back to the window allocator; then the block's event."""
        (a, b, aux), slides = window
        for seq, start in slides:
            self.metrics.window_pages_released += seq.slide(start)
        self.flight.record_event(EV_WINDOW_CACHE, time.perf_counter(),
                                 a=a, b=b, aux=aux)

    def _note_expert_load(self, fl: _InFlight, host, t_ready: float):
        """A landed decode block of a model with experts carries, below
        its token rows, the pairs each held expert of each expert block
        took in each step (engine_model.expert_load_rows): count them,
        write the block's `moe_load` event (aux: the (expert layer, held
        expert, step) triples in which the expert took a pair, of all the
        block's), hand back the token rows."""
        load = host[-self._load_rows:, 1:]        # [Lm * E, K]
        pairs = int(load.sum())
        hit = int(np.count_nonzero(load))
        self.metrics.moe_pairs_local += pairs
        self.metrics.moe_experts_hit += hit
        self.metrics.moe_expert_steps += load.size
        busiest = float(load.sum(axis=1).max())   # one expert of one block
        self.flight.record_event(
            EV_MOE_LOAD, t_ready, a=pairs / (fl.K * self.cfg.n_moe_layers),
            b=busiest * self._load_rows / pairs if pairs else 0.0,
            aux=f"hit={hit} of={load.size}")
        return host[:-self._load_rows]

    # graftlint: hot-path
    def _record_beat(self, fl: _InFlight, t_ready: float,
                     emitted: int) -> None:
        """Write one beat record (and the beat-gap histogram sample)
        for a just-landed block. The histogram is always live; the
        ring append is one branch when the recorder is off."""
        prev = self._last_beat_ready
        if t_ready:
            if prev:
                self.metrics.hists["beat_gap_ms"].observe(
                    (t_ready - prev) * 1e3)
            self._last_beat_ready = t_ready
        if fl.prog is not None and fl.prog.t_start:
            # One source, two sinks: the row's interval ends are the
            # ledger's, so `t_prev_ready` is the previous PROGRAM's
            # ready (a prefill's or an encoder forward's too) and the
            # beat's slice is this block's own device time.
            prev = fl.prog.t_prev_ready
        if not self.flight.enabled:
            self._beat_kv_demote = self._beat_kv_promote = 0
            return
        busy = [0, 0, 0]
        for s in self.slots:
            if s is not None and not s.req.cancelled:
                busy[tier_id(s.req)] += 1
        d = self.metrics.qos_queue_depth
        plan = fl.plan
        self.flight.record_beat(
            t_dispatch=fl.t_dispatch, t_ready=t_ready or fl.t_dispatch,
            t_prev_ready=prev,
            decode_k=plan.decode_k if plan is not None else fl.K,
            spec_k=plan.spec_k if plan is not None else 0,
            tree_branches=plan.tree_branches if plan is not None else 0,
            rider_width=plan.rider_width if plan is not None else 0,
            spec_state=bool(plan.spec_state) if plan is not None
            else fl.plain_spec,
            fused_rider=bool(plan is not None and plan.rider_width),
            qos_paused=any(lp.paused for lp in self._long_prefills),
            busy=(busy[0], busy[1], busy[2]),
            wait=(d["latency"], d["standard"], d["batch"]),
            tokens_emitted=emitted,
            kv_demote_pages=self._beat_kv_demote,
            kv_promote_pages=self._beat_kv_promote)
        self._beat_kv_demote = self._beat_kv_promote = 0

    def _reader_loop(self) -> None:
        """Blocking host readbacks, off the scheduler thread. Engaged
        only when the scheduler hands over a block (one at a time), so
        steady state is identical to the measured-fastest blocking
        design (ENGINEERING_NOTES r3 scheduler study) — the GIL cost of
        a free-running reader never materializes — while the scheduler
        stays responsive to admissions while the block runs (the
        ledger's `a` of a decode program: its enqueue -> its ready).
        The fetch ends HERE, so this thread's clock reading, not the
        scheduler's after the hand-back, is the block's t_ready where
        the waiter thread has not stamped it already."""
        while self._running:
            try:
                blk = self._fetch_req.get(timeout=0.1)
            except queue.Empty:
                continue
            box: Dict[str, Any] = {}
            try:
                box["host"] = _to_host(blk)
            except Exception as e:  # surfaced on the scheduler thread
                box["err"] = e
            box["t_ready"] = time.perf_counter()
            self._fetch_box = box
            self._fetch_done.set()

    def _waiter_loop(self) -> None:
        """Every program's completion, off the scheduler thread: block
        on each program's output in enqueue order and stamp the ledger
        where the wait ends. Blocks are still fetched by the reader
        thread and first tokens emitted by the scheduler's own poll
        (_emit_ready_first_tokens); this thread touches the ledger and
        nothing else, and wakes nobody."""
        while self._running:
            try:
                item = self._await_q.get(timeout=0.1)
            except queue.Empty:
                continue
            if item is None:
                break
            prog, out = item
            try:
                jax.block_until_ready(out)
            except Exception:
                # Deleted or failed: left unstamped, the row takes the
                # bound the next landed block proves (ledger.drain).
                _LOG.debug("program %d: wait failed", prog.seq,
                           exc_info=True)
                continue
            self.programs.ready(prog)

    def _pacer_loop(self) -> None:
        """Drain paced token events at their scheduled times. Runs only
        when pace_emission_max_streams > 0; sleeps on an event when no
        entries are pending, so bulk workloads (pacing disengaged at
        high stream counts) pay nothing."""
        while self._running:
            timeout = None  # empty schedule: sleep until a commit wakes us
            with self._pace_lock:
                if self._pace_entries:
                    now = time.perf_counter()
                    nxt = None
                    for key in list(self._pace_entries):
                        entry = self._pace_entries[key]
                        while entry["buf"] and entry["next_t"] <= now:
                            entry["slot"].req.stream.put(
                                entry["buf"].popleft())
                            entry["next_t"] += entry["spacing"]
                        if not entry["buf"]:
                            del self._pace_entries[key]
                        elif nxt is None or entry["next_t"] < nxt:
                            nxt = entry["next_t"]
                    if nxt is not None:
                        timeout = max(0.001, nxt - now)
            self._pace_wake.wait(timeout=timeout)
            self._pace_wake.clear()

    def _fetch_block_host(self, fl: _InFlight) -> np.ndarray:
        """Fetch one in-flight block to the host. The wait happens on
        the reader thread; while it runs, the scheduler admits newly
        arrived requests (their prefill dispatches overlap the
        readback) and emits first tokens whose async copies landed —
        the two latency paths that used to wait out the fetch."""
        if self._reader is None or not self._reader.is_alive():
            return self._fetch_inline(fl)  # tests may drive _loop inline
        self._fetch_done.clear()
        self._fetch_req.put(fl.block)
        while not self._timed_wait(self._fetch_done, 0.005, "fetch"):
            if not self._running or not self._reader.is_alive():
                # stop() raced the handoff. If the reader exited without
                # consuming the block, reclaim it and fetch inline;
                # if it did consume, give it a bounded grace period.
                try:
                    self._fetch_req.get_nowait()
                except queue.Empty:
                    if self._fetch_done.wait(timeout=10):
                        break
                return self._fetch_inline(fl)
            self._emit_ready_first_tokens()
            # Mid-fetch admissions: only once the oldest arrival has
            # aged past a short debounce, so a burst batches into few
            # large prefill groups (one weight read per group) instead
            # of one group per 5 ms poll. Costs at most the debounce in
            # TTFT under load; idle-path admission stays immediate.
            with self._lock:
                oldest = (self.waiting[0].submit_time if self.waiting
                          else None)
            if oldest is not None and \
                    time.perf_counter() - oldest >= self._admit_debounce_s:
                with _phase("sched.admit"):
                    self._admit_waiting()
        box, self._fetch_box = self._fetch_box, {}
        self._note_block_ready(fl, box.get("t_ready") or time.perf_counter())
        if "err" in box:
            raise box["err"]
        return box["host"]

    # graftlint: hot-path
    def _timed_wait(self, event: threading.Event, timeout: float,
                    where: str) -> bool:
        """`event.wait(timeout)` of the scheduler's own polls. Where it
        comes back UNSET and flight.LATE_WAKE_MS or more past its
        timeout, the thread was held off (another thread kept the
        interpreter's lock, or the OS the core): one `host_pause` row
        of cause `late_wake`, its length the lateness. Two clock reads
        a poll; with the recorder off, none."""
        if not self.flight.enabled:
            return event.wait(timeout)
        t0 = time.perf_counter()
        if event.wait(timeout):
            return True
        t1 = time.perf_counter()
        if (t1 - t0 - timeout) * 1e3 >= flight_mod.LATE_WAKE_MS:
            self.metrics.host_late_wakes += 1
            self._record_pause(flight_mod.PAUSE_LATE_WAKE, t0 + timeout, t1,
                               0, "where=" + where)
        return False

    def _record_pause(self, cause: int, t0: float, t1: float, gen: int,
                      aux: str) -> None:
        """One `host_pause` event (scheduler thread), its histogram
        sample, and the row a later stall is held against."""
        ms = (t1 - t0) * 1e3
        self.metrics.hists["host_pause_ms"].observe(ms)
        self._recent_pauses.append(
            (t0, t1, flight_mod.PAUSE_CAUSES[cause]))
        self.flight.record_event(EV_HOST_PAUSE, t1, code=cause, a=ms,
                                 b=float(gen), aux=aux)

    # graftlint: hot-path
    def _drain_pauses(self) -> None:
        """The process's collections since this engine last looked
        (flight.HOST_PAUSES, by this engine's cursor): the two sums,
        and a `host_pause` event a listed one."""
        cursor = self._pause_cursor
        if cursor is None or not self.flight.enabled:
            return
        rows, after = flight_mod.HOST_PAUSES.read(cursor)
        if after is cursor:     # nothing was collected meanwhile
            return
        self._pause_cursor = after
        m = self.metrics
        m.host_gc_collections += after[1] - cursor[1]
        m.host_gc_pause_ms += after[2] - cursor[2]
        for t0, t1, gen, collected, thread in rows:
            m.host_gc_pauses += 1
            self._record_pause(
                flight_mod.PAUSE_GC, t0, t1, gen,
                f"gen={gen} collected={collected} "
                f"thread={thread.replace(' ', '_')}")

    def _note_stall(self, prog: Program) -> None:
        """A stalled program names its cause: how much of its start ->
        ready the union of the host's known pauses covers (collections
        and late wake-ups this engine wrote, and the dispatch call of
        every program enqueued meanwhile), counted as the HOST's where
        that is half or more of what it ran over its class's median,
        and ONE line logged."""
        pauses = list(self._recent_pauses)
        pauses += [(t0, t1, flight_mod.CAUSE_DISPATCH_CALL)
                   for t0, t1 in self.programs.calls()]
        prog.host_ms, by = flight_mod.host_cover(
            prog.t_start, prog.t_ready, pauses)
        self.metrics.program_stalls += 1
        if prog.host_ms >= 0.5 * (prog.ran_ms - prog.median_ms):
            self.metrics.program_stalls_host += 1
        _LOG.warning(
            "device program stalled: class=%s shape=%s seq=%d "
            "waited a=%.1f ms, ran b=%.1f ms (over %g x the "
            "running median of its class and shape); host=%.1f ms of "
            "%.1f ms (gc %.1f ms, dispatch_call %.1f ms, late_wake "
            "%.1f ms)",
            PROGRAM_CLASSES[prog.cls], prog.shape, prog.seq,
            prog.waited_ms, prog.ran_ms, flight_mod.STALL_FACTOR,
            prog.host_ms, prog.ran_ms, by.get("gc", 0.0),
            by.get(flight_mod.CAUSE_DISPATCH_CALL, 0.0),
            by.get("late_wake", 0.0))

    def _fetch_inline(self, fl: _InFlight) -> np.ndarray:
        host = _to_host(fl.block)
        self._note_block_ready(fl, time.perf_counter())
        return host

    def _note_block_ready(self, fl: _InFlight, t_ready: float) -> None:
        """The fetch of a block ended at `t_ready` (the fetching
        thread's clock). The block's ledger row takes it unless the
        waiter thread, which has waited on the block since its
        dispatch, stamped first: the scheduler hands a block to the
        reader only when it gets to it, and a block that completed
        meanwhile would otherwise read as long as the scheduler was
        busy. The beat row and the beat-gap histogram read the row's."""
        if fl.prog is not None:
            self.programs.ready(fl.prog, t_ready)
            t_ready = fl.prog.t_ready
        fl.t_ready = t_ready

    def _emit_ready_first_tokens(self) -> None:
        """Emit first tokens whose prefill-sampled values have reached
        the host (async copy issued at prefill dispatch). Slots whose
        first decode block was processed first (first_emitted set there)
        are simply dropped — the token values are identical because
        decode blocks chain from the same device buffer."""
        for item in list(self._pending_first):
            toks, metas, prog = item
            if all(slot.first_emitted or self.slots[i] is not slot
                   for i, slot in metas):
                self._pending_first.remove(item)
                continue
            try:
                if not toks.is_ready():
                    continue
            except AttributeError:
                pass  # non-jax array (tests): treat as ready
            self._pending_first.remove(item)
            if prog is not None:
                # Seen complete: stands only where the waiter thread
                # has not stamped first (or there is none: inline).
                self.programs.ready(prog)
            with _phase("sched.emit"):
                self._emit_first_values(
                    mh_fetch_replicated(
                        toks, "prefill first-token readback").reshape(-1),
                    metas)
        self._drain_programs()

    @contextlib.contextmanager
    def _enqueue(self, phase: str, cls: int, rows: int, n: int,
                 shape: str):
        """THE stamp every program this engine puts on the device
        passes: a ledger row (sequence number, t_enqueue) taken just
        before the dispatch call, inside the scheduler phase the call
        runs in, whose annotation carries the sequence number as the
        argument `seq` (a stat of the profile's host event: its name
        stays bare, and nothing is formatted while no profile runs).
        Yields None with the recorder off; a dispatch that raises
        takes its row out."""
        prog = (self.programs.enqueue(cls, rows, n, shape)
                if self.flight.enabled else None)
        try:
            with (_phase(phase) if prog is None
                  else _phase(phase, seq=prog.seq)):
                yield prog
                if prog is not None:  # the dispatch call returned
                    self.programs.dispatched(prog)
        except BaseException:
            if prog is not None:
                self.programs.cancel(prog)
            raise

    def _await_program(self, prog: Optional[Program], out) -> None:
        """Hand a program to the waiter thread, which stamps its ready
        where the wait on `out` ends. With no waiter (an inline-driven
        engine) a block's fetch, the scheduler's own sighting of the
        first tokens, or the next landed block, stamps it."""
        waiter = self._waiter
        if prog is not None and out is not None \
                and waiter is not None and waiter.is_alive():
            self._await_q.put((prog, out))

    # graftlint: hot-path
    def _drain_programs(self, proved: int = -1) -> None:
        """Write the `program` event of every ledger row whose
        completion is now known (scheduler thread: the ring's single
        writer; an encoder's rows arrive here the same way), feed the
        prefill histograms, count and log a stall. The host's pauses
        go first: a stall is held against them."""
        self._drain_pauses()
        for prog in self.programs.drain(proved):
            self.metrics.hists["dispatch_call_ms"].observe(prog.call_ms)
            if prog.cls == PROG_PREFILL:
                self.metrics.hists["device_queue_ms"].observe(
                    prog.queued_ms)
                self.metrics.hists["program_ms_prefill"].observe(
                    prog.ran_ms)
            if prog.stalled:
                self._note_stall(prog)
            self.flight.record_event(
                EV_PROGRAM, prog.t_ready, code=prog.cls, slot=prog.rows,
                a=prog.waited_ms, b=prog.ran_ms, aux=prog.aux())

    @property
    def _prefill_cap(self) -> int:
        cap = self.ecfg.max_prefill_group
        return cap if cap > 0 else self.ecfg.max_batch_size

    def _tier_depth(self, req: GenRequest, delta: int) -> None:
        """Move the per-tier waiting-depth gauge (always maintained —
        the edge and router read tier pressure from it whether or not
        engine.qos is on). Called with self._lock held."""
        d = self.metrics.qos_queue_depth
        tier = request_tier(req)
        d[tier] = max(0, d[tier] + delta)

    # -- flight-recorder lifecycle hooks (scheduler thread only) -----------

    # graftlint: hot-path
    def _flight_note_pop(self, req: GenRequest) -> None:
        """Record the request's submit (retroactively, stamped with
        its submit_time — server threads never write the ring) and,
        under engine.qos, the weighted-fair pick that chose it."""
        if not self.flight.enabled:
            return
        tier = tier_id(req)
        if not req.flight_seen:
            req.flight_seen = True
            if not req.request_id:
                # Engine-direct callers (bench, generate_stream) have
                # no server-issued id; synthesize one so their
                # lifecycle events still correlate into timeline spans.
                req.request_id = f"req-{self.flight.stats()['flight_events']}"
            pre_ms = (max(0.0, (req.submit_time - req.received_time) * 1e3)
                      if req.received_time else 0.0)
            self.flight.record_event(EV_SUBMIT, req.submit_time,
                                     rid=req.request_id, tier=tier,
                                     a=float(len(req.prompt_ids)),
                                     b=pre_ms, aux=req.caller_id)
        if self.qos is not None:
            self.flight.record_event(EV_QOS_PICK, time.perf_counter(),
                                     rid=req.request_id, tier=tier)

    # graftlint: hot-path
    def _flight_admit(self, req: GenRequest, slot_idx: int) -> None:
        """Slot reserved: observe the per-tier queue-wait histogram
        (always live) and record the admit event."""
        now = time.perf_counter()
        wait_ms = max(0.0, (now - req.submit_time) * 1e3)
        tier = request_tier(req)
        self.metrics.hists["queue_wait_ms_" + tier].observe(wait_ms)
        if self.flight.enabled:
            self.flight.record_event(EV_ADMIT, now, rid=req.request_id,
                                     tier=tier_id(tier),
                                     slot=slot_idx, a=wait_ms)

    # graftlint: hot-path
    def _flight_first(self, slot: "_Slot", slot_idx: int,
                      ttft_ms: float) -> None:
        self.flight.record_event(
            EV_FIRST_TOKEN, time.perf_counter(),
            rid=slot.req.request_id,
            tier=tier_id(slot.req), slot=slot_idx, a=ttft_ms)

    # graftlint: hot-path
    def _flight_retire(self, slot: "_Slot", slot_idx: int,
                       reason: str) -> None:
        """Slot retired: observe the e2e-latency histogram and record
        the retire event (reason code, token count, e2e ms, and the
        rid <-> trace-id correlation when a span is live)."""
        now = time.perf_counter()
        e2e_ms = max(0.0, (now - slot.req.submit_time) * 1e3)
        self.metrics.hists["e2e_ms"].observe(e2e_ms)
        if not self.flight.enabled:
            return
        self.flight.record_event(
            EV_RETIRE, now, rid=slot.req.request_id,
            tier=tier_id(slot.req), slot=slot_idx,
            code=RETIRE_CODES.get(reason, -1), a=float(slot.generated),
            b=e2e_ms, aux=tracing.span_trace_id(slot.span))

    # graftlint: hot-path
    def _qos_pop_waiting(self) -> GenRequest:
        """Weighted-fair admission pop (engine.qos on; self._lock
        held): the TierScheduler picks the least-served-per-weight
        tier, the least-served tenant within it, FIFO within the
        tenant. O(waiting) per pop — the edge bounds keep the queue
        short; unbounded queues belong to the FIFO path."""
        idx = self.qos.pick(self.waiting)
        req = self.waiting[idx]
        del self.waiting[idx]
        return req

    # graftlint: hot-path
    def _qos_refresh_preemption(self) -> None:
        """Pause/resume in-progress long prefills at the beat boundary
        (engine.qos + qos_preempt_prefill): while any latency-tier slot
        is in its TTFT phase, lower-tier prefills stop dispatching
        chunks AND stop attaching fused riders — the dispatch bandwidth
        goes to the latency request. Resume is byte-identical: a paused
        prefill's pos/scratch-cache snapshot simply waits. Idempotent
        within a scheduler iteration (transitions counted edge-
        triggered), and a latency-tier prefill itself never pauses."""
        if self.qos is None or not self._long_prefills \
                or not self.ecfg.qos_preempt_prefill:
            return
        pressure = self._qos_latency_pressure()
        now = 0.0
        for lp in self._long_prefills:
            should = pressure and lp.tier != "latency"
            if should != lp.paused and self.flight.enabled:
                now = now or time.perf_counter()  # graftlint: ignore[GL703] timestamp feeds flight-recorder events only; the pause decision itself reads queue state, not the clock
                self.flight.record_event(
                    EV_QOS_PAUSE if should else EV_QOS_RESUME, now,
                    rid=lp.req.request_id,
                    tier=tier_id(lp.tier), a=float(lp.pos))
            if should and not lp.paused:
                self.metrics.qos_preemptions += 1
            lp.paused = should

    # graftlint: hot-path
    def _qos_latency_pressure(self) -> bool:
        """True while an ADMITTED latency-tier request is prefilling or
        awaiting its first token. Deliberately not triggered by merely
        WAITING latency requests: a waiting request either gets a slot
        this very pass (admission runs before dispatch) or cannot
        progress regardless — pausing on its behalf could deadlock a
        prefill that holds the only slot."""
        for s in self.slots:
            if s is None or s.req.cancelled:
                continue
            if request_tier(s.req) != "latency":
                continue
            if s.prefilling or not s.first_emitted:
                return True
        return False

    def _admit_waiting(self) -> bool:
        """Admit every waiting request with a free slot, grouped by
        prefill bucket into BATCHED prefill dispatches (capped at
        max_prefill_group per dispatch — prefill transients scale with
        the group): a burst of N admissions reads the
        (bandwidth-dominating) weights once per group, not N times,
        collapsing both TTFT under load and startup cost."""
        groups: Dict[int, List] = {}  # bucket -> [(req, slot_idx, seq, ids)]
        deferred_long: List[GenRequest] = []
        while True:
            with self._lock:
                if not self.waiting:
                    break
                slot_idx = self._free_slot_index()
                if slot_idx is None:
                    break
                # FIFO is the byte-identical default; with engine.qos
                # the weighted-fair scheduler picks the next admission
                # across tiers and tenants instead of queue position.
                req = (self.waiting.popleft() if self.qos is None
                       else self._qos_pop_waiting())
                self._tier_depth(req, -1)
            self._flight_note_pop(req)
            ids = req.prompt_ids or [0]
            long = len(ids) > self.buckets[-1]
            lane_full = len(self._long_prefills) >= self._max_long_prefills
            if long and lane_full:
                # Bound concurrent scratch caches: each long prefill
                # (and each prefix-cache hit — same machinery) holds a
                # device KVCache; admitting a burst of them at once
                # would multiply the old (synchronous) path's peak
                # device memory. Deferred BEFORE the radix lookup: a
                # backlogged long prompt must not pay an O(prompt)
                # match (and skew the LRU) on every admission pass.
                deferred_long.append(req)
                continue
            # With the pager on and the scratch lane full, any hit is
            # about to be discarded below — look up WITHOUT promoting
            # so the doomed hit never costs a device scatter.
            hit = self._lookup_prefix(ids, promote=not lane_full) \
                if self.prefix_cache is not None else None
            demoted = False
            if hit is not None and lane_full:
                # Short prompt, scratch lane busy: fall back to the
                # plain batched prefill rather than queueing behind
                # the lane.
                self._release_hit_pin(hit)
                hit, demoted = None, True
            seq = self._new_sequence()
            try:
                if hit is not None:
                    seq.adopt(hit[0], hit[1])
                seq.ensure(len(ids))
            except MemoryError as e:
                seq.release()
                self._release_hit_pin(hit)
                self.metrics.admission_failures += 1
                if self.flight.enabled:
                    # Args materialized only when recording (the PR-7
                    # reporter idiom: the recorder-less hot path pays
                    # nothing, not even the perf_counter call).
                    self.flight.record_event(
                        EV_ADMIT_RETRY, time.perf_counter(),
                        rid=req.request_id, tier=tier_id(req),
                        a=float(req.admission_attempts))
                # Poison: the prompt (plus one generated token) needs
                # more pages than the pool HAS (page 0 is the sink) —
                # no amount of draining or reclaim ever admits it, and
                # requeued at the head it would block the whole line.
                # Fail it now and keep admitting the rest.
                ps = self.pool.page_size
                never_fits = -(-(len(ids) + 1) // ps) \
                    > self.allocator.n_pages - 1
                # The retry cap only advances while nothing can free
                # pages (no live slots, nothing in flight): a request
                # waiting behind long-running decodes is a queue, not a
                # failure, and retries indefinitely.
                if not never_fits and not any(
                        s is not None for s in self.slots) \
                        and not self._inflight:
                    req.admission_attempts += 1
                if never_fits \
                        or req.admission_attempts >= MAX_ADMISSION_RETRIES:
                    _LOG.warning(
                        "admission failed terminally (%s, attempts=%d, "
                        "never_fits=%s); failing request",
                        e, req.admission_attempts, never_fits)
                    req.stream.put({"text": "", "token_id": -1,
                                    "finished": True,
                                    "finish_reason": "error"})
                    continue
                _LOG.warning("admission failed (%s); requeueing", e)
                with self._lock:
                    self.waiting.appendleft(req)
                    self._tier_depth(req, +1)
                break
            if self.prefix_cache is not None:
                if hit is None:
                    # A demotion (cached prefix, busy scratch lane) is
                    # NOT a miss — miscounting it would show the hit
                    # rate collapsing exactly when the cache is hot
                    # and the engine is busy.
                    if not demoted:
                        self.metrics.prefix_miss += 1
                else:
                    self.metrics.prefix_hits += 1
                    self.metrics.prefix_hit_tokens += hit[1]
            # Reserve the slot now so the next iteration sees it taken;
            # the real _Slot replaces the placeholder at dispatch.
            placeholder = _Slot(req, seq, None)
            self.slots[slot_idx] = placeholder
            self._flight_admit(req, slot_idx)
            if self.qos is not None:
                # Charge the weighted-fair accounting only for REAL
                # admissions (deferred/requeued requests go back to the
                # queue uncharged).
                self.qos.note_admitted(req)
            if hit is not None:
                try:
                    self._begin_prefix_prefill(req, slot_idx, seq, ids,
                                               hit[0], hit[1], placeholder)
                except Exception:
                    _LOG.exception("prefix-hit prefill setup failed")
                    self._fail_request(req, slot_idx, seq)
                continue
            if long:
                try:
                    self._begin_long_prefill(req, slot_idx, seq, ids,
                                             placeholder)
                except Exception:
                    _LOG.exception("chunked prefill setup failed")
                    self._fail_request(req, slot_idx, seq)
                continue
            bucket = self._bucket_for(len(ids))
            groups.setdefault(bucket, []).append((req, slot_idx, seq, ids))
        if deferred_long:
            with self._lock:
                self.waiting.extendleft(reversed(deferred_long))
                for r in deferred_long:
                    self._tier_depth(r, +1)
        did = False
        cap = self._prefill_cap
        for bucket, entries in groups.items():
            for start in range(0, len(entries), cap):
                part = entries[start:start + cap]
                try:
                    with _phase("sched.prefill_dispatch"):
                        self._prefill_group(bucket, part)
                    did = True
                except Exception:
                    # A bad group must not kill the scheduler thread:
                    # fail the requests, free their pages, keep serving
                    # (SURVEY.md §5.3 pattern).
                    _LOG.exception("prefill failed; failing %d requests",
                                   len(part))
                    for req, slot_idx, seq, _ in part:
                        self._fail_request(req, slot_idx, seq)
        return did

    def _new_sequence(self) -> SequencePages:
        """A sequence's page bookkeeping: for a model with window layers,
        over both allocators."""
        ps = self.pool.page_size
        if self.window_allocator is None:
            return SequencePages(self.allocator, ps, self.max_pages)
        return WindowSequencePages(
            self.allocator, self.window_allocator, ps, self.max_pages,
            self.cfg.window_rows.window, self._window_table_pages)

    def _tables(self, glob, win=None, base=None):
        """Page tables as a step program takes them: the one array, or a
        model with window layers' WindowTables (`win` None: all zeros,
        a warm-up's)."""
        if self.window_allocator is None:
            return self._put(glob)
        if win is None:
            win = np.zeros(glob.shape[:-1] + (self._window_table_pages,),
                           np.int32)
            base = np.zeros(glob.shape[:-1], np.int32)
        return WindowTables(self._put(glob), self._put(win),
                            None if base is None else self._put(base))

    def _fail_request(self, req: GenRequest, slot_idx: int,
                      seq: SequencePages) -> None:
        """Fail one request before it reached decodable state: free the
        slot and pages, emit the terminal error event."""
        slot = self.slots[slot_idx]
        if slot is not None:
            self._flight_retire(slot, slot_idx, "error")
        self.slots[slot_idx] = None
        seq.release()
        req.stream.put({"text": "", "token_id": -1, "finished": True,
                        "finish_reason": "error"})

    def _fail_active(self) -> None:
        for fl in self._inflight:
            for seq in fl.releases:
                seq.release()
            if fl.prog is not None:  # nobody will fetch it: no ledger row
                self.programs.cancel(fl.prog)
        self._inflight.clear()
        for i, s in enumerate(self.slots):
            if s is not None:
                self._finish(i, "error")

    def _prefill_group(self, bucket: int, entries: List) -> None:
        """One batched prefill dispatch for a same-bucket admission
        group. Fully async: forward + on-device sampling + scatter into
        the device last-token buffer; the first tokens reach the host
        by their own small copy (_emit_ready_first_tokens), or with
        the slot's first decode block if that lands first."""
        ps = self.pool.page_size
        n = len(entries)
        # Pad N to a power of two so only log2(max_batch) x buckets
        # graph variants ever compile.
        N = 1
        while N < n:
            N *= 2
        tokens = np.zeros((N, bucket), np.int32)
        lengths = np.ones((N,), np.int32)
        rows = np.zeros((N, bucket // ps), np.int32)
        # a model with window layers: their rows' pages, indexed as `rows`
        # (the pages behind the window stay the sink)
        win_rows = None if self.window_allocator is None \
            else np.zeros_like(rows)
        temps = np.zeros((N,), np.float32)
        top_ps = np.ones((N,), np.float32)
        top_ks = np.zeros((N,), np.int32)
        # Padding rows point out of bounds -> dropped by the scatter.
        idxs = np.full((N,), len(self.slots), np.int32)
        for j, (req, slot_idx, seq, ids) in enumerate(entries):
            tokens[j, : len(ids)] = ids
            lengths[j] = len(ids)
            rows[j, : len(seq.pages)] = seq.pages
            if win_rows is not None:
                first = seq.window_first
                win_rows[j, first: first + len(seq.window_pages)] = \
                    seq.window_pages
            temps[j] = req.temperature
            top_ps[j] = req.top_p
            top_ks[j] = req.top_k
            idxs[j] = slot_idx
        all_greedy = bool(all(temps[:n] <= 0.0))
        flags = (True, False, False) if all_greedy else (False, True, True)
        live = bucket  # the rows the program computes of each prompt
        if self.served.live_prefill_rows:
            live = engine_model.prefill_row_counts(bucket, ps, N)[
                int(engine_model.prefill_live_index(lengths, bucket, ps))]
        self.metrics.prefill_rows_live += N * live
        self.metrics.prefill_rows_bucket += N * bucket
        with self._enqueue("sched.prefill_dispatch", PROG_PREFILL, n,
                           int(lengths[:n].sum()),
                           f"{N}x{bucket}") as prog:
            rec = dict(
                tokens=tokens, lengths=lengths, rows=rows, temps=temps,
                top_ps=top_ps, top_ks=top_ks, idxs=idxs,
                flags=np.asarray(flags))
            if win_rows is not None:
                rec["win_rows"] = win_rows
            toks = self._exec_prefill(rec)
        self._await_program(prog, toks)
        seq_no = float(prog.seq) if prog is not None else 0.0
        metas = []
        self.served.note_prefill(self.metrics, self.cfg, len(entries),
                                 int(lengths[:n].sum()))
        for req, slot_idx, seq, ids in entries:
            slot = _Slot(req, seq, StreamDetokenizer(self.tokenizer),
                         span=self._request_span(req, len(ids)))
            self.slots[slot_idx] = slot
            metas.append((slot_idx, slot))
            self.metrics.prefill_tokens += len(ids)
            if self.flight.enabled:
                self.flight.record_event(
                    EV_PREFILL_DISPATCH, time.perf_counter(),
                    rid=req.request_id, tier=tier_id(req),
                    slot=slot_idx, a=float(len(ids)), b=seq_no)
            # Completed prefill: its full prompt pages become reusable
            # by later identical/shared-prefix prompts (the page writes
            # are already dispatched; device ordering sequences any
            # later gather after them).
            self._insert_prefix(ids, seq)
        # Start the (tiny, [N] int32) first-token transfer NOW: it
        # lands beside the in-flight blocks' fetches, so the first
        # token reaches the stream when the prefill completes (the
        # ledger's t_ready of this program) plus the scheduler's poll
        # (the `first_token` event less that t_ready: the lag), not
        # behind every older block fetch.
        try:
            toks.copy_to_host_async()
        except AttributeError:
            pass
        self._pending_first.append((toks, metas, prog))

    @staticmethod
    def _request_span(req: GenRequest, prompt_tokens: int, **attributes):
        """The request's `engine.generate` span, a child of the context
        its caller sent; None while tracing is off, so that retirement
        does no span work at all."""
        if not tracing.enabled():
            return None
        return tracing.ManualSpan(
            "engine.generate", context=req.trace_context,
            attributes=dict(attributes, prompt_tokens=prompt_tokens,
                            request_id=req.request_id))

    def _begin_long_prefill(self, req: GenRequest, slot_idx: int,
                            seq: SequencePages, ids: List[int],
                            placeholder: "_Slot") -> None:
        """Start chunked prefill for a prompt beyond the largest bucket
        (SURVEY.md §5.7 — the reference has no long-context story at
        all): bucket-size chunks run through a contiguous scratch
        KVCache with offset queries (the flash kernel's shifted causal
        diagonal). Chunks are dispatched INCREMENTALLY by
        _advance_long_prefills — one per scheduler iteration — so
        concurrent streams keep their token cadence; when the last chunk
        lands, ONE scatter moves the cache into this sequence's pages
        and the first token samples on device.

        NOTE: a COLD S_total shape compiles on the scheduler thread —
        warm the variants at boot via warmup(long_prompts=True) when
        long prompts are expected in live traffic."""
        chunk = self.buckets[-1]
        S_total = -(-len(ids) // chunk) * chunk
        # No device allocation here: the scratch cache materializes
        # inside _exec_plan when the first chunk record executes (its
        # `fresh` flag), so leader and followers build it at the same
        # position in the dispatch stream.
        placeholder.prefilling = True
        self._long_prefills.append(
            _LongPrefill(req, slot_idx, seq, ids, S_total, placeholder,
                         chunk))

    # -- prefix cache ------------------------------------------------------

    def _reclaim_cached_pages(self, n: int) -> None:
        """Allocator shortfall hook: LRU-evict cold cached prefixes so
        live traffic always wins over the cache."""
        freed = self.prefix_cache.evict(n)
        if freed:
            self.metrics.prefix_evictions += freed
            if self.kv_pager is not None:
                # With the pager, eviction DEMOTES instead of
                # destroying — a page-move record for the timeline.
                self._beat_kv_demote += freed
                if self.flight.enabled:
                    self.flight.record_event(EV_KV_DEMOTE,
                                             time.perf_counter(),
                                             a=float(freed))

    # graftlint: hot-path
    def _lookup_prefix(self, ids: List[int], promote: bool = True):
        """Longest cached page-granular prefix of this prompt, capped
        at len(ids) - 1 so at least one suffix token always runs
        through the model (its logits sample the first output token).
        Returns (pages, n_tokens) or None; when the cap lands mid-page
        the last page is gather-only (SequencePages.adopt turns it into
        a copy-on-write private tail) and is PINNED here — the adopt/
        ensure allocations between lookup and the gather can trigger
        reclaim eviction of refcount-1 tree pages, and the sequence
        holds no reference of its own to this one. Every consumer of a
        hit must release the pin (_release_hit_pin).

        With engine.kv_pager, the match may land on DEMOTED nodes
        (host RAM / disk spill): the whole matched path is promoted
        back into the pool with one batched scatter before the pages
        are returned — a warm session resume costs a page gather, not
        a re-prefill. If the allocator cannot cover the cold pages
        even after reclaim, the hit falls back to the device-resident
        prefix (the resident set is ancestor-closed, so that is always
        the leading run)."""
        from generativeaiexamples_tpu.serving.prefix_cache import (
            TIER_DEVICE)

        if self.kv_pager is None:
            pages = self.prefix_cache.match(ids)
            if not pages:
                return None
            nodes = None
        else:
            nodes = self.prefix_cache.match_nodes(ids)
            if not nodes:
                return None
            pages = nodes  # length drives the cap below
        ps = self.pool.page_size
        m = min(len(pages) * ps, len(ids) - 1)
        if m <= 0:
            return None
        if nodes is not None:
            nodes = nodes[: -(-m // ps)]
            if any(n.tier != TIER_DEVICE for n in nodes):
                promoted = False
                if promote:
                    n_cold = sum(1 for n in nodes
                                 if n.tier != TIER_DEVICE)
                    t0 = time.perf_counter()  # graftlint: ignore[GL703] times the host-side promote for kv_promote_ms_per_page; the prefix-hit decision is made from tree state above
                    try:
                        self.pool = self.prefix_cache.promote(self.pool,
                                                              nodes)
                        promoted = True
                    except MemoryError:
                        pass  # resident-prefix fallback below
                    if promoted:
                        # Page-move record: host-side promote cost per
                        # page (the gather/scatter dispatch is async;
                        # this times the host work — tier reads plus
                        # staging — which is what stalls the beat).
                        dt_ms = (time.perf_counter() - t0) * 1e3  # graftlint: ignore[GL703] metrics-only read (see t0 above)
                        self.metrics.hists[
                            "kv_promote_ms_per_page"].observe(
                            dt_ms / max(1, n_cold))
                        self._beat_kv_promote += n_cold
                        if self.flight.enabled:
                            self.flight.record_event(
                                EV_KV_PROMOTE, t0, a=float(n_cold),
                                b=dt_ms)
                if not promoted:
                    # Not promoting (caller will discard the hit —
                    # scratch lane full — so a device scatter that may
                    # reclaim-demote OTHER parked sessions would be
                    # pure waste) or the allocator could not cover the
                    # cold pages: keep the leading device-resident run
                    # — always the path's prefix, the resident set is
                    # ancestor-closed — and let the cold suffix
                    # re-prefill.
                    keep = []
                    for n in nodes:
                        if n.tier != TIER_DEVICE:
                            break
                        keep.append(n)
                    nodes = keep
                    m = min(len(nodes) * ps, len(ids) - 1)
                    if m <= 0:
                        return None
            pages = [n.page for n in nodes]
        pages = pages[: -(-m // ps)]
        if m % ps:
            self.allocator.retain([pages[-1]])
        return pages, m

    def _release_hit_pin(self, hit) -> None:
        """Drop _lookup_prefix's pin on the gather-only tail page (a
        no-op for page-aligned matches)."""
        if hit is not None and hit[1] % self.pool.page_size:
            self.allocator.release([hit[0][-1]])

    def _insert_prefix(self, ids: List[int], seq: SequencePages) -> None:
        """Register a completed prefill's FULL prompt pages in the
        radix tree (partial tail pages stay private — decode writes
        into them). The tree retains its own references; on chunk
        collisions the existing page wins and the duplicate stays with
        the sequence."""
        if self.prefix_cache is None:
            return
        n_full = len(ids) // self.pool.page_size
        if n_full <= 0:
            return
        self.prefix_cache.insert(list(ids), seq.pages[:n_full])
        freed = self.prefix_cache.trim()
        if freed:
            self.metrics.prefix_evictions += freed

    def _begin_prefix_prefill(self, req: GenRequest, slot_idx: int,
                              seq: SequencePages, ids: List[int],
                              pages: List[int], m: int,
                              placeholder: "_Slot") -> None:
        """Admission for a prefix-cache hit: seed a scratch KVCache with
        the matched pages' KV (one gather — the exact bytes decode
        attention reads for those pages) and run ONLY the uncached
        suffix ids[m:] through the chunked-prefill lane, its queries
        offset by m. The finish scatter points the adopted read-only
        rows at the page-0 sink, so shared pages are never rewritten;
        a CoW tail page is rewritten whole (gathered head + computed
        tail) from the scratch cache. Owns _lookup_prefix's pin on the
        gather-only tail page: released once the gather is dispatched
        (or on any failure)."""
        try:
            ps = self.pool.page_size
            plen = len(ids)
            if plen <= self.buckets[-1]:
                chunk = self._bucket_for(plen - m)
                s_total = self._bucket_for(plen)
            else:
                chunk = self.buckets[-1]
                s_total = -(-plen // chunk) * chunk
            row = np.zeros((s_total // ps,), np.int32)
            row[: len(pages)] = pages
            # The gather AND the warmup-matched placement happen inside
            # the seed executor, so followers replay them at the same
            # stream position (the page-index row rides the record —
            # followers never see the radix tree that produced it).
            self._exec_seed(dict(slot=np.int32(slot_idx), row=row,
                                 m=np.int32(m), s_total=np.int32(s_total)))
        finally:
            self._release_hit_pin((pages, m))
        placeholder.prefilling = True
        lp = _LongPrefill(req, slot_idx, seq, ids, s_total, placeholder,
                          chunk)
        lp.pos = m
        self._long_prefills.append(lp)

    def _advance_long_prefills(self) -> bool:
        """Dispatch at most ONE chunk for each in-progress long prefill
        (paced by the reader beat while decode traffic is live); finish
        those whose prompt is fully fed. Returns True if any advanced.

        With engine.fused_prefill on, this is only the FALLBACK lane:
        while decode traffic can carry the chunk as a rider inside the
        next decode dispatch (_fuse_ready), dispatching a standalone
        batch-of-1 chunk here would reintroduce the device-queue stall
        the fused step removes. The lane still runs when the engine is
        idle (chunks at full dispatch speed), when the engine is
        speculative, when fusing is off, or when the fused variant for
        this scratch shape isn't warmed."""
        did = False
        self._qos_refresh_preemption()
        decoding = any(s is not None and not s.prefilling
                       for s in self.slots)
        for lp in list(self._long_prefills):
            if self.slots[lp.slot_idx] is not lp.slot:
                # Slot was failed/retired (e.g. _fail_active) while
                # prefilling; the seq was released by _finish.
                self._long_prefills.remove(lp)
                self._drop_scratch(lp.slot_idx)
                continue
            if lp.req.cancelled:
                self._long_prefills.remove(lp)
                self._drop_scratch(lp.slot_idx)
                self._finish(lp.slot_idx, "cancelled")
                continue
            if lp.paused:
                # QoS preemption: a latency-tier TTFT phase owns the
                # dispatch bandwidth; this prefill resumes from its
                # snapshot (pos + scratch cache) once pressure clears.
                continue
            if decoding and self._fuse_ready(lp):
                continue  # the next decode dispatch carries the chunk
            if decoding and lp.beat == self._beat:
                # At most prefill_chunks_per_block chunks per LANDED
                # decode block while other streams are live — the
                # interleave invariant stated explicitly rather than
                # via the loop's block-per-iteration shape.
                continue
            lp.beat = self._beat
            chunk = lp.chunk
            s_total = lp.s_total
            n_chunks = max(1, self.ecfg.prefill_chunks_per_block) \
                if decoding else 1
            try:
                for _ in range(n_chunks):
                    part = lp.ids[lp.pos:lp.pos + chunk]
                    if not part:
                        break
                    width = self._pick_chunk_width(len(part), chunk,
                                                   s_total)
                    tok = self._chunk_buf(width)
                    tok[0, :len(part)] = part
                    final = lp.pos + len(part) >= len(lp.ids)
                    # The prompt-completing chunk samples + scatters
                    # its first token INSIDE the dispatch when the
                    # fused-sampling tail is warmed for this shape
                    # (engine.fused_sampling; never a cold compile on
                    # a warmed engine).
                    fuse_sample = (final and self._fused_sampling
                                   and (not self._warm_ks
                                        or (s_total, width)
                                        in self._warm_sample_chunks))
                    # A rider-only plan (decode_k=0): the idle/fallback
                    # lane's chunk dispatch goes through the same
                    # plan-record executor as every other device step.
                    rec = engine_model.plan_to_record(
                        engine_model.StepPlan(rider_width=width,
                                              rider_s_total=s_total,
                                              rider_sample=fuse_sample))
                    rec.update(slot=np.int32(lp.slot_idx),
                               chunk_tokens=tok,
                               chunk_valid=np.int32(len(part)),
                               fresh=np.bool_(lp.pos == 0))
                    if fuse_sample:
                        req = lp.req
                        greedy = req.temperature <= 0.0
                        rec.update(
                            r_temp=np.float32(req.temperature),
                            r_top_p=np.float32(req.top_p),
                            r_top_k=np.int32(req.top_k),
                            r_flags=np.asarray(
                                (True, False, False) if greedy
                                else (False, True, True)))
                    with self._enqueue(
                            "sched.prefill_dispatch", PROG_CHUNK, 1,
                            len(part), f"W{width}/S{s_total}") as prog:
                        res = self._exec_plan(rec)
                    self._await_program(
                        prog, res.get("tok0", res.get("chunk_logits")))
                    if fuse_sample:
                        self.metrics.fused_sample_dispatches += 1
                    lp.pos += len(part)
                    self.metrics.prefill_tokens += len(part)
                    if self.flight.enabled:
                        self.flight.record_event(
                            EV_PREFILL_CHUNK, time.perf_counter(),
                            rid=lp.req.request_id,
                            tier=tier_id(lp.tier), a=float(len(part)))
                    if lp.pos >= len(lp.ids):
                        self._long_prefills.remove(lp)
                        self._finish_long_prefill(lp)
                        break
            except Exception:
                _LOG.exception("chunked prefill failed")
                self._long_prefills.remove(lp)
                self._drop_scratch(lp.slot_idx)
                self._fail_request(lp.req, lp.slot_idx, lp.seq)
            did = True
        return did

    def _drop_scratch(self, slot_idx: int) -> None:
        """Leader-side registry cleanup for a long prefill that ends
        WITHOUT a commit record (cancel / slot failure). Followers keep
        their stale entry until the slot's next `fresh` plan record
        recreates the cache — the stale bytes are never read."""
        self._scratch_caches.pop(slot_idx, None)
        self._chunk_res.pop(slot_idx, None)

    def _pick_chunk_width(self, n: int, chunk: int, s_total: int) -> int:
        """Dispatch width for a chunk of n valid tokens: the smallest
        power of two >= n, capped at the full chunk. When ANY warmup
        ran (_warm_ks non-empty), restricted to the widths precompiled
        for this scratch shape, falling back to the full chunk — the
        prompt's earlier chunks already compiled that variant, so the
        tail never adds a cold compile that the old pad-to-full-chunk
        path didn't have. Only a never-warmed engine (CPU tests) may
        compile a fresh tail width on demand."""
        w = 1
        while w < n:
            w *= 2
        if w >= chunk:
            return chunk
        if self._warm_ks or self._warm_chunk_widths:
            fits = sorted(x for (s, x) in self._warm_chunk_widths
                          if s == s_total and n <= x < chunk)
            return fits[0] if fits else chunk
        return w

    def _chunk_buf(self, width: int) -> np.ndarray:
        """Zeroed (1, width) int32 staging buffer, reused across chunk
        dispatches (_exec_plan puts a COPY on the device, so the buffer
        is free again by the time the call returns)."""
        buf = self._chunk_staging.get(width)
        if buf is None:
            buf = np.zeros((1, width), np.int32)
            self._chunk_staging[width] = buf
        else:
            buf.fill(0)
        return buf

    # graftlint: hot-path
    def _fuse_ready(self, lp: "_LongPrefill") -> bool:
        """True when the next decode dispatch can carry this prefill's
        chunk as a fused rider: fusing is available, the scratch cache
        fits the rider width, the fused variant is warmed (or no warmup
        constrains shapes), and at least one decode slot can actually
        dispatch — without that last check, deferring would stall the
        prefill behind traffic that never launches a block."""
        if not self._fused_width or lp.pos >= len(lp.ids):
            return False
        s_total = lp.s_total
        if s_total < self._fused_width:
            return False
        warm = self._warm_spec_fused if self._spec_k else self._warm_fused
        if self._warm_ks and not any(
                (s_total, k) in warm for k in self._warm_ks):
            # A warmup ran but didn't cover this fused shape (e.g.
            # long_prompts=False): never compile it mid-traffic — the
            # interleaved lane carries the chunks instead.
            return False
        if self._spec_k and self._sampled_live():
            # The sampled-request fallback plan has no rider variant;
            # the interleaved lane carries chunks while it runs.
            return False
        for s in self.slots:
            if (s is not None and not s.prefilling
                    and not s.req.cancelled and not s.no_capacity
                    and s.req.max_new_tokens - s.scheduled > 0):
                return True
        return False

    # graftlint: hot-path
    def _note_prefill_stalls(self) -> None:
        """One landed decode block = one scheduling beat; an in-progress
        chunked prefill that advanced zero prompt tokens over the beat
        counts one prefill_stall_beats — the generation-stall signal
        the fused lane exists to close (and the honest residual when
        the fallback lane is carrying the chunks)."""
        for lp in self._long_prefills:
            if lp.stall_pos == lp.pos:
                self.metrics.prefill_stall_beats += 1
            lp.stall_pos = lp.pos

    def _finish_long_prefill(self, lp: "_LongPrefill") -> None:
        """Last chunk fed: ONE commit record finishes the prefill —
        scatter the scratch cache into the page pool, sample the first
        token on device (unless the finishing chunk already rode the
        fused-sampling tail — _exec_plan stashed its tok0 in
        _chunk_res), seed the speculative history row — then open the
        slot for decode. All device work lives in _exec_commit so
        followers replay it from the record alone; only the host-side
        slot/tree bookkeeping stays here."""
        ps = self.pool.page_size
        S_total = lp.s_total
        row = np.zeros((S_total // ps,), np.int32)  # padding -> sink 0
        row[:len(lp.seq.pages)] = lp.seq.pages
        # Pages adopted read-only from the prefix cache must never be
        # rewritten: their rows scatter into the page-0 sink. (A CoW
        # tail page is NOT shared — it is rewritten whole from the
        # scratch cache: gathered head + computed tail.) Pages already
        # scattered by publish_prefill_pages sink too: each page is
        # written exactly once.
        sunk = max(lp.seq.n_shared, lp.published)
        if sunk:
            row[:sunk] = 0
        req = lp.req
        greedy = req.temperature <= 0.0
        flags = (True, False, False) if greedy else (False, True, True)
        # Peek (don't pop — _exec_commit owns the pop) whether the
        # final chunk already sampled tok0 on device.
        _, tok0_prev = self._chunk_res.get(lp.slot_idx, (None, None))
        rec = dict(slot=np.int32(lp.slot_idx), row=row,
                   sampled=np.bool_(tok0_prev is not None),
                   temp=np.float32(req.temperature),
                   top_p=np.float32(req.top_p),
                   top_k=np.int32(req.top_k), flags=np.asarray(flags))
        if self._spec_k:
            rec["h_ids"] = np.asarray(lp.ids, np.int32)
        with self._enqueue("sched.prefill_dispatch", PROG_CHUNK, 1, 0,
                           "commit") as prog:
            tok0 = self._exec_commit(rec)
        if tok0_prev is None:
            self._await_program(prog, tok0)
        # (a commit whose chunk already sampled has no output of its
        # own to wait on: its row takes the next landed block's bound)
        self._insert_prefix(lp.ids, lp.seq)
        slot = _Slot(req, lp.seq, StreamDetokenizer(self.tokenizer),
                     span=self._request_span(req, len(lp.ids),
                                             chunked_prefill=True))
        self.slots[lp.slot_idx] = slot
        # Same early first-token path as bucketed prefill.
        try:
            tok0.copy_to_host_async()
        except AttributeError:
            pass
        self._pending_first.append((tok0, [(lp.slot_idx, slot)], prog))

    def _place_scratch_cache(self, cache):
        """Shard a chunked-prefill scratch cache like the KV pool (kv
        heads on tensor). warmup and the live path MUST place
        identically — jit specializes on input sharding, so a
        differently-placed warmup variant would never be reused."""
        if self.mesh is None:
            return cache
        from jax.sharding import NamedSharding, PartitionSpec as P

        from generativeaiexamples_tpu.models.llama import KVCache

        kv_sh = NamedSharding(self.mesh, P(None, None, "tensor", None, None))
        return KVCache(jax.device_put(cache.k, kv_sh),
                       jax.device_put(cache.v, kv_sh),
                       jax.device_put(cache.lengths, self._replicated))

    def _slot_used(self, slot: "_Slot") -> int:
        """Tokens this slot's pages must already cover: the host-exact
        sequence length on a plain engine; the reconciled-plus-in-
        flight worst case on a speculative one (lengths are device-
        authoritative there — the host cannot know acceptance before a
        block lands)."""
        return (slot.kv_len + slot.kv_worst) if self._spec_k \
            else slot.seq.length

    def _sampled_live(self) -> bool:
        """True when a live, dispatchable slot wants sampling
        (temperature > 0). On a speculative engine this demotes the
        next dispatch to the plain spec-state plan — greedy
        verification cannot honor sampling, so the request serves
        without speculating (the documented per-request fallback;
        verify plans resume the moment no sampled slot is
        dispatchable). A sampled slot with no page capacity for even
        one token does NOT demote: the live filter will starve it out
        of this batch anyway (for the plain plan too), so demoting
        would cost every greedy stream its speculation while the
        stuck slot waits on the reaper."""
        for s in self.slots:
            if (s is not None and not s.prefilling
                    and not s.req.cancelled
                    and s.req.temperature > 0.0
                    and s.req.max_new_tokens - s.scheduled > 0
                    and self._advance_capacity(s, self._slot_used(s))[0]
                    >= 1):
                return True
        return False

    # graftlint: hot-path
    def _dispatch_decode(self) -> bool:
        """Dispatch (async) ONE composed step over the slot batch:
        build the batch state, select the widest warmed StepPlan
        (decode block + optional spec-verify width + optional prefill
        rider — _select_plan) and lower it through ONE
        engine_model.plan_step dispatch (the `plan` record executor,
        _exec_plan — published to the multihost log first). Sampling /
        verification happens on device and tokens chain device-side,
        so this returns without any host<->device sync; results are
        consumed later by _process_block.

        This is the single dispatch path the old partially-exclusive
        lanes (_dispatch_decode / _dispatch_decode_spec /
        _dispatch_fused_rider) collapsed into: with engine.step_plans
        off the selected plans reproduce the lane-exclusive decisions
        exactly (speculative engines never fuse), with it on the
        lattice composes."""
        B = len(self.slots)
        spec_mode = self._spec_k > 0
        if spec_mode and self._sampled_live():
            spec_mode = False  # per-request fallback: plain plan
        # Per-step commit worst case r (tokens the budget/bookkeeping
        # reserve) vs page-write worst case r_nodes (a tree verify
        # step scatters k/v for EVERY packed node, accepted or not).
        # Linear/plain engines: r_nodes == r, byte-identical sizing.
        r = self._spec_r if spec_mode else 1
        r_nodes = self._spec_tree_nodes if spec_mode else 1
        lengths = np.ones((B,), np.int32)
        tables = np.zeros((B, self.max_pages), np.int32)
        win_tables = win_base = None
        if self.window_allocator is not None:
            win_tables = np.zeros((B, self._window_table_pages), np.int32)
            win_base = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        top_ps = np.ones((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        active_mask = np.zeros((B,), bool)
        live: List[int] = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            if s.prefilling:
                continue  # chunked prefill in progress; not decodable yet
            if s.req.cancelled:
                self._finish(i, "cancelled")
                continue
            cap, _ = self._advance_capacity(s, self._slot_used(s))
            if cap < r_nodes:
                self._starve(i)
                continue
            if s.req.max_new_tokens - s.scheduled <= 0:
                # Every token this request asked for is already emitted
                # or in flight — another block would be pure overshoot
                # (a block of device time and a fetch nobody consumes).
                continue
            live.append(i)
        if not live:
            return False
        # The block's length is ONE function's choice (decode_block):
        # the configured K is its ceiling, and while an arrival can be
        # waiting for this block (an empty slot, a queued request, a
        # slot whose prefill is enqueued and rides its first block
        # here) the block is held to a time budget by the step time
        # the landed blocks read. Asked twice: what the budget took
        # away is `decode_blocks_short_for_arrival`.
        before_step_time = (
            self.ecfg.decode_steps_per_dispatch, self._warm_ks, len(live), B,
            bool(self.waiting) or any(
                s is None or s.prefilling or s.awaiting_first
                for s in self.slots),
            self.ecfg.prefill_decode_k_cap if self._long_prefills else 0)
        K, unhurried = (
            decode_block.choose_k(*before_step_time, step_ms,
                                  decode_block.BLOCK_BUDGET_MS)
            for step_ms in (self._step_time.ms, None))
        short_for_arrival = K < unhurried
        # Two caps with different semantics: page capacity is HARD
        # (steps past it write out of bounds) — round DOWN; the token
        # budget is SOFT (steps past the last requested token are
        # dropped at emission) — round UP to the nearest precompiled K
        # rather than shrink onto a cold variant.
        cap_min = min(self._advance_capacity(
            self.slots[i], self._slot_used(self.slots[i]))[0] for i in live)
        max_rem = max(self.slots[i].req.max_new_tokens
                      - self.slots[i].scheduled for i in live)
        K = self._pick_k(min(K, max(1, (cap_min - (r_nodes - r)) // r)))
        if max_rem < K:
            if self._warm_ks:
                fits = sorted(k for k in self._warm_ks
                              if max_rem <= k <= K)
                K = fits[0] if fits else K
            else:
                K = self._pick_k(max(1, max_rem))
        while K & (K - 1):
            K &= K - 1
        worst = K * r                    # commit / token-budget bound
        alloc = (K - 1) * r + r_nodes    # page-write bound
        # ensure() pre-advances seq.length, so capture base usage once —
        # a shrink-retry pass must re-ensure from the same starting
        # point.
        base_lens = {i: self._slot_used(self.slots[i]) for i in live}
        metas: List = []
        active: List[int] = []
        while True:
            shrink_to = None
            active = []
            metas = []
            active_mask[:] = False
            for i in live:
                s = self.slots[i]
                if s is None:
                    continue
                base = base_lens[i]
                try:
                    s.seq.ensure(base + alloc)
                except MemoryError:
                    # Pool can't cover K steps. Shrink K to what the
                    # slot's allocated pages PLUS the remaining free
                    # pages can hold; starve only when even one step
                    # cannot be stored anywhere.
                    _, avail = self._advance_capacity(s, base)
                    if avail >= r_nodes and K > 1:
                        shrink_to = max(1, (avail - (r_nodes - r)) // r)
                        break
                    if avail < r_nodes:
                        self._starve(i)
                    continue
                active.append(i)
                active_mask[i] = True
                s.no_capacity = False  # capacity proven; undo stale starve
                tables[i] = s.seq.table_row()
                if win_tables is not None:
                    win_tables[i], win_base[i] = s.seq.window_row()
                if spec_mode:
                    metas.append((i, s, base))
                else:
                    lengths[i] = base + 1  # incl. the incoming token
                    temps[i] = s.req.temperature
                    top_ps[i] = s.req.top_p
                    top_ks[i] = s.req.top_k
            if shrink_to is None:
                break
            K = self._pick_k(shrink_to)
            worst = K * r
            alloc = (K - 1) * r + r_nodes
        if not active:
            return False
        # Static sampling flags from host-known params: a fully greedy
        # batch (the default) skips all [B, vocab] sort work on device.
        # Exactly TWO variants per K bucket (all-greedy vs general).
        # A spec_state fallback dispatch always takes the GENERAL
        # variant — the only one warmup compiles for it, and the
        # sampled slot that demoted spec_mode can drop out of `active`
        # after _sampled_live() (starved on pages, ensure failure),
        # which would otherwise launch an all-greedy variant cold.
        # Greedy rows still take exact argmax inside sample().
        spec_state_fb = self._spec_k > 0 and not spec_mode
        all_greedy = spec_mode or (
            not spec_state_fb
            and bool(all(temps[i] <= 0.0 for i in active)))
        flags = (True, False, False) if all_greedy else (False, True, True)
        plan, lp = self._select_plan(K, spec_mode)
        # The record carries the WHOLE plan lattice point plus every
        # host scalar the launch consumes: followers rebuild the exact
        # StepPlan from it (engine_model.plan_from_record) instead of
        # re-deriving it from scheduler state they don't have — only
        # the scheduler's OUTPUTS cross the wire (the GL703 invariant).
        rec = engine_model.plan_to_record(plan)
        rec.update(tables=tables, lengths=lengths,
                   active_mask=active_mask, temps=temps, top_ps=top_ps,
                   top_ks=top_ks, flags=np.asarray(flags))
        if win_tables is not None:
            rec.update(win_tables=win_tables, win_base=win_base)
        n_part = 0
        if plan.rider_width:
            part = lp.ids[lp.pos:lp.pos + plan.rider_width]
            n_part = len(part)
            # Publishing the reused staging buffer is safe: the record
            # serializes (np.savez) at publish time, before any reuse.
            tok = self._chunk_buf(plan.rider_width)
            tok[0, :n_part] = part
            rec.update(slot=np.int32(lp.slot_idx), chunk_tokens=tok,
                       chunk_valid=np.int32(n_part),
                       fresh=np.bool_(lp.pos == 0))
        with self._enqueue(
                "sched.decode_dispatch", PROG_DECODE, len(active), K,
                f"K{K}+W{plan.rider_width}" if plan.rider_width
                else f"K{K}") as prog:
            res = self._exec_plan(rec)
        if prog is not None:
            for i in active:
                s = self.slots[i]
                if s.awaiting_first:
                    # the first decode block this occupant rides in
                    self.flight.record_event(
                        EV_DECODE_JOIN, prog.t_enqueue,
                        rid=s.req.request_id, tier=tier_id(s.req),
                        slot=i, b=float(prog.seq))
        if plan.rider_width:
            self._rider_bookkeeping(lp, n_part)
        self.metrics.decode_steps += K
        self.metrics.decode_blocks_short_for_arrival += short_for_arrival
        self.metrics.layer_passes += K * self.cfg.cache_rows
        # the plain and the fused decode programs choose their form so;
        # a speculative engine's programs keep the staged one
        if not (plan.spec_k or plan.spec_state) \
                and self.served.direct_qkv \
                and engine_model.direct_qkv(self.cfg, K):
            self.metrics.decode_steps_direct_qkv += K
        if self._load_rows:  # every live slot's token, in every expert block
            self.metrics.moe_pairs_routed += (
                len(active) * K * self.cfg.n_moe_layers
                * self.cfg.n_experts_per_tok)
        # the architecture's own counts, and its flight event or None
        noted = self.served.note_decode(
            self.metrics, self.cfg, lengths, active_mask, K, self.pool,
            self.use_pallas, self.max_pages)
        window = self._note_window_cache(lengths, active, win_base, K)
        # every decode program but the verifies writes one row a slot
        if not plan.spec_k and kernel_append(self.pool, self.use_pallas):
            if engine_model.fuses_append(self.cfg, self.pool,
                                         self.use_pallas):
                self.metrics.decode_steps_fused_append += K
            else:
                self.metrics.decode_steps_kernel_append += K
            # ... and attends through paged_attention_int8: a live
            # row is one token longer every step of the block, and
            # decode_multi_step's kernels walk the live rows alone (the
            # fused and the spec-state lanes hand them no mask)
            masked = engine_model.masks_pool_kernels(plan)
            live_pages, walked, updates, grid_steps = page_counts(
                lengths + np.arange(K)[:, None] * active_mask,
                self.pool.page_size, self.max_pages,
                mask=active_mask if masked else None,
                fold=self._attn_fold(self.max_pages))
            self.metrics.decode_attn_pages_live += live_pages
            self.metrics.decode_attn_pages_walked += walked
            self.metrics.decode_attn_updates += updates
            self.metrics.decode_attn_grid_steps += grid_steps
            if masked:
                self.metrics.decode_attn_rows_skipped += (
                    B - len(active)) * K
        self.metrics.busy_slots_acc += len(active) * K
        if spec_mode:
            for i in active:
                s = self.slots[i]
                s.awaiting_first = False
                s.scheduled += worst
                s.kv_worst += worst
            block = (res["targets"], res["counts"])
            if self._async_block_copy:
                for b in block:
                    try:
                        b.copy_to_host_async()
                    except AttributeError:
                        pass
            fl = _InFlight(block, metas, K, spec_worst=worst)
            # plan_step's dispatch-return stamp (engine_model hook).
            fl.t_dispatch = res.get("t_dispatch") or time.perf_counter()
            fl.plan = plan
            fl.prog = prog
            self._await_program(prog, block)
            self._inflight.append(fl)
        else:
            block = res["block"]
            for i in active:
                s = self.slots[i]
                metas.append((i, s, 0 if s.awaiting_first else 1))
                s.awaiting_first = False
                s.scheduled += K
                if plan.spec_state:
                    # On a speculative engine _slot_used reads
                    # kv_len + kv_worst, and kv_len only moves at
                    # landing — reserve this block's K writes now so a
                    # sibling dispatch (pipeline_depth > 1) ensures
                    # pages past the in-flight block instead of
                    # scattering K tokens beyond what ensure() covered.
                    s.kv_worst += K
            if plan.spec_state:
                self.metrics.spec_fallback_steps += 1
            if self._async_block_copy:
                try:
                    block.copy_to_host_async()
                except AttributeError:
                    pass
            fl = _InFlight(block, metas, K, plain_spec=plan.spec_state)
            fl.t_dispatch = res.get("t_dispatch") or time.perf_counter()
            fl.plan = plan
            fl.prog = prog
            fl.noted = noted
            fl.window = window
            self._await_program(prog, block)
            self._inflight.append(fl)
        return True

    # graftlint: hot-path
    def _rider_candidate(self) -> Optional["_LongPrefill"]:
        """The in-progress long prefill whose next chunk can ride the
        next dispatch (fusing available, prompt tokens remaining,
        scratch wide enough), or None."""
        if not self._fused_width:
            return None
        self._qos_refresh_preemption()
        for cand in self._long_prefills:
            if (self.slots[cand.slot_idx] is cand.slot
                    and not cand.req.cancelled
                    and not cand.paused
                    and cand.pos < len(cand.ids)
                    and cand.s_total >= self._fused_width):
                return cand
        return None

    # graftlint: hot-path
    def _select_plan(self, K: int, spec_mode: bool):
        """Choose the widest WARMED StepPlan for this dispatch: the
        decode block always runs; the spec-verify width rides on a
        speculative engine unless a live sampled request forced the
        plain fallback; a prefill rider attaches when an in-progress
        chunked prefill's fused variant is warmed for this
        (S_total, K). Fallback is always toward a NARROWER plan (drop
        the rider — the interleaved lane carries the chunk this beat)
        rather than compiling a cold lattice point mid-traffic, which
        would freeze every live stream for a 20-40 s compile. Returns
        (plan, rider _LongPrefill or None)."""
        spec_k = self._spec_k if spec_mode else 0
        spec_state = bool(self._spec_k) and not spec_mode
        rider_w = rider_s = 0
        lp = None
        if not spec_state:  # the fallback plan has no rider variant
            cand = self._rider_candidate()
            if cand is not None:
                s_total = cand.s_total
                warm = self._warm_spec_fused if spec_k else self._warm_fused
                # Keyed on _warm_ks (did ANY warmup run), so a warmup
                # without long_prompts=True — which leaves the fused
                # sets empty — also refuses, instead of reading
                # "empty = anything goes".
                if not self._warm_ks or (s_total, K) in warm:
                    rider_w, rider_s = self._fused_width, s_total
                    lp = cand
        return engine_model.StepPlan(
            decode_k=K, spec_k=spec_k,
            tree_branches=self._tree_branches if spec_k else 0,
            rider_width=rider_w, rider_s_total=rider_s,
            spec_state=spec_state), lp

    # graftlint: hot-path
    def _rider_bookkeeping(self, lp: "_LongPrefill",
                           n_part: int) -> None:
        """Leader-side bookkeeping after a fused-rider plan record
        executed: advance the prefill cursor, meter the chunk, and
        commit the prefill when the prompt is fully fed. Device state
        was already folded by _exec_plan."""
        lp.pos += n_part
        lp.beat = self._beat  # the rider consumed this beat's chunk
        self.metrics.fused_steps += 1
        self.metrics.fused_prefill_tokens += n_part
        # Real (unpadded) prompt tokens only — the rider's fixed-
        # width padding must not inflate the prefill meter.
        self.metrics.prefill_tokens += n_part
        if self.flight.enabled:
            self.flight.record_event(
                EV_PREFILL_CHUNK, time.perf_counter(),
                rid=lp.req.request_id, tier=tier_id(lp.tier),
                a=float(n_part), b=1.0)  # b=1: fused rider
        if lp.pos >= len(lp.ids):
            self._long_prefills.remove(lp)
            self._finish_long_prefill(lp)

    # -- dispatch-record executors (multihost replay vocabulary) -----------
    #
    # Every scheduler-reachable collective launch lives in one of the
    # _exec_* methods below. Each builds its device inputs FROM THE
    # RECORD alone, publishes the record right before launching (leader
    # only — followers run the same executor via _mh_replay_table with
    # _mh_leader False), and folds the returned device state back into
    # the engine. Leader-only state (slots, radix tree, allocator, QoS)
    # never enters an executor: only its outputs — launch order and
    # host scalars — cross the wire (the GL703 invariant).

    def _state_slots(self, idxs):
        """A prefill's `state_slots`, for an architecture whose prefill
        writes per-slot state: the decode slots its rows were admitted to
        (a padding row's is past the last slot and dropped); else None."""
        return self._put(idxs) if self.served.state_slots else None

    def _exec_prefill(self, rec: Dict[str, Any]):
        """Execute one `prefill` record: the batched prefill forward +
        on-device sampling, the first-token scatter, and (speculative
        engines) the history-row seed. The RNG stream stays in lockstep
        because every rank draws exactly one key here."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            # Publish BEFORE launching: cross-process collectives pair
            # by launch order, so followers must enter this same jitted
            # prefill as their very next dispatch.
            log.publish("prefill", **rec)
        flags = tuple(bool(f) for f in rec["flags"])
        toks, self.pool = engine_model.prefill_batch_step(
            self.params, self.cfg, self.pool, self._put(rec["tokens"]),
            self._put(rec["lengths"]),
            self._tables(rec["rows"], rec.get("win_rows")),
            self._put(rec["temps"]), self._put(rec["top_ps"]),
            self._put(rec["top_ks"]), self._next_key(), self.use_pallas,
            sampling_flags=flags, mesh=self.mesh,
            state_slots=self._state_slots(rec["idxs"]))
        # Scatter the first-tokens into the device buffer (padding rows'
        # out-of-bounds indices are dropped on device).
        self._last_tokens = engine_model.set_last_tokens(
            self._last_tokens, self._put(rec["idxs"]), toks)
        if self._spec_k:
            self._history, self._dev_lengths = \
                engine_model.set_history_rows(
                    self._history, self._dev_lengths,
                    self._put(rec["idxs"]), self._put(rec["tokens"]),
                    self._put(rec["lengths"]), toks)
        return toks

    def _exec_plan(self, rec: Dict[str, Any]):
        """Execute one `plan` record — EVERY plan_step lattice point
        (decode / spec verify / tree / fused rider / fused-sample
        chunk) lowers through here as ONE jitted dispatch. The record
        is self-describing: the full StepPlan plus every host scalar
        the launch consumes (page tables, sampling params, the rider's
        chunk tokens), so a follower rebuilds the identical program
        without any scheduler state."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            # Publish BEFORE launching (collectives pair by launch
            # order).
            log.publish("plan", **rec)
        plan = engine_model.plan_from_record(rec)
        kw = dict(use_pallas=self.use_pallas, mesh=self.mesh)
        if plan.decode_k:
            kw.update(pool=self.pool, last_tokens=self._last_tokens,
                      page_tables=self._tables(rec["tables"],
                                               rec.get("win_tables"),
                                               rec.get("win_base")),
                      active=self._put(rec["active_mask"]))
            if plan.spec_k or plan.spec_state:
                kw.update(history=self._history,
                          dev_lengths=self._dev_lengths)
            if not plan.spec_k:
                kw.update(lengths=self._put(rec["lengths"]),
                          temperature=self._put(rec["temps"]),
                          top_p=self._put(rec["top_ps"]),
                          top_k=self._put(rec["top_ks"]),
                          rng=self._next_key(),
                          sampling_flags=tuple(bool(f)
                                               for f in rec["flags"]))
        if plan.rider_width:
            slot = int(rec["slot"])
            cache = self._scratch_caches.get(slot)
            if cache is None or bool(rec["fresh"]):
                # First chunk of this prefill (or the slot's previous
                # occupant was dropped leader-side without a commit):
                # materialize the scratch cache HERE, at the record's
                # stream position, so every rank builds it from the
                # same zeros at the same point in the launch order.
                # Model dtype, NOT kv dtype: llama.forward's scatter
                # writes model-dtype k/v; cache_to_pool casts once at
                # the page write.
                from generativeaiexamples_tpu.models.llama import KVCache

                cache = self._place_scratch_cache(
                    KVCache.zeros(self.cfg, 1,
                                  max_len=plan.rider_s_total))
                self._chunk_res.pop(slot, None)
            # A COPY of the staging buffer goes to the device: the
            # host->device put may alias host memory (on the CPU
            # jnp.asarray shares a 64-byte-aligned numpy buffer
            # outright) and the program reads it after this call
            # returns, while _chunk_buf zeroes and refills the same
            # buffer for the next chunk (ROADMAP D7's wrong tokens
            # under load).
            kw.update(cache=cache,
                      chunk_tokens=self._put(np.array(rec["chunk_tokens"])),
                      chunk_valid=self._put(
                          np.int32(int(rec["chunk_valid"]))))
        if plan.rider_sample:
            kw.update(last_tokens=self._last_tokens,
                      slot_idx=self._put(np.int32(int(rec["slot"]))),
                      temperature=float(rec["r_temp"]),
                      top_p=float(rec["r_top_p"]),
                      top_k=int(rec["r_top_k"]),
                      rng=self._next_key(),
                      sampling_flags=tuple(bool(f)
                                           for f in rec["r_flags"]))
        res = engine_model.plan_step(self.params, self.cfg, plan, **kw)
        if "pool" in res:
            self.pool = res["pool"]
        if plan.decode_k or plan.rider_sample:
            self._last_tokens = res["last_tokens"]
        if plan.spec_k or plan.spec_state:
            self._dev_lengths = res["dev_lengths"]
            self._history = res["history"]
        if plan.rider_width:
            slot = int(rec["slot"])
            self._scratch_caches[slot] = res["cache"]
            # The finishing chunk's logits/tok0 feed the commit record's
            # sample — stashed per-slot on BOTH ranks so the commit
            # never has to carry device arrays over the wire.
            self._chunk_res[slot] = (res.get("chunk_logits"),
                                     res.get("tok0"))
        return res

    def _exec_seed(self, rec: Dict[str, Any]) -> None:
        """Execute one `seed` record — a prefix-cache hit's scratch
        seeding: ONE pool_to_cache gather of the matched pages into a
        fresh scratch cache, registered under the slot. The page-index
        row rides the record, so followers launch the identical gather
        without reproducing the leader's radix-tree match."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            log.publish("seed", **rec)
        slot = int(rec["slot"])
        cache = engine_model.pool_to_cache(
            self.pool, self.cfg, self._put(rec["row"]),
            self._put(np.int32(int(rec["m"]))))
        # Same placement as warmup's scratch caches — jit specializes
        # on input sharding, so a differently-placed live cache would
        # recompile prefill_chunk_step on the scheduler thread.
        self._scratch_caches[slot] = self._place_scratch_cache(cache)
        self._chunk_res.pop(slot, None)

    def _exec_commit(self, rec: Dict[str, Any]):
        """Execute one `commit` record — the chunked-prefill finish:
        ONE cache_to_pool scatter of the scratch cache (already-
        published and adopted rows sunk to page 0 by the leader-built
        row), the first-token sample (sample_token_into under
        engine.fused_sampling, the legacy pair otherwise; skipped when
        the finishing chunk already rode the fused-sampling tail), and
        the speculative history-row seed. Consumes the slot's registry
        entries on every rank. Returns the first token's device
        array."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            log.publish("commit", **rec)
        slot = int(rec["slot"])
        cache = self._scratch_caches.pop(slot)
        logits, tok0 = self._chunk_res.pop(slot, (None, None))
        self.pool = engine_model.cache_to_pool(
            self.pool, cache, self.cfg, self._put(rec["row"]))
        if not bool(rec["sampled"]):
            flags = tuple(bool(f) for f in rec["flags"])
            temp = float(rec["temp"])
            top_p = float(rec["top_p"])
            top_k = int(rec["top_k"])
            if self._fused_sampling:
                tok0, self._last_tokens = engine_model.sample_token_into(
                    self._last_tokens, self._put(np.int32(slot)),
                    logits, temp, top_p, top_k, self._next_key(),
                    *flags)
                self.metrics.fused_sample_dispatches += 1
            else:
                tok0 = engine_model.sample_token(
                    logits, temp, top_p, top_k, self._next_key(),
                    *flags)
                self._last_tokens = engine_model.set_last_token(
                    self._last_tokens, self._put(np.int32(slot)), tok0)
        if self._spec_k:
            ids = np.asarray(rec["h_ids"], np.int32)
            row = np.zeros((1, self.ecfg.max_seq_len), np.int32)
            row[0, : ids.shape[0]] = ids
            self._history, self._dev_lengths = \
                engine_model.set_history_rows(
                    self._history, self._dev_lengths,
                    self._put(np.asarray([slot], np.int32)),
                    self._put(row),
                    self._put(np.asarray([ids.shape[0]], np.int32)),
                    tok0[None])
        return tok0

    def _exec_pages_out(self, rec: Dict[str, Any]):
        """Execute one `pages_out` record — a batched pool_to_pages
        gather (disagg export / pager staging). Launch only: the HOST
        fetch of the gathered bytes is the caller's business (the
        leader reads them; a follower discards the device arrays —
        the launch alone keeps the collective streams paired)."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            log.publish("pages_out", **rec)
        return engine_model.pool_to_pages(self.pool,
                                          self._put(rec["row"]))

    def _exec_pages_in(self, rec: Dict[str, Any], buf=None,
                       sbuf=None) -> None:
        """Execute one `pages_in` record — ONE pages_to_pool scatter of
        transferred page bytes (disagg import). The host path carries
        the padded codes/scales in the record itself so followers
        rebuild identical device inputs; the device (ICI) path passes
        prebuilt buffers and only runs single-process
        (import_prefix_pages bounces device arrays through the host
        under multihost)."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            log.publish("pages_in", **rec)
        if buf is None:
            buf = self._put(rec["codes"])
            if rec.get("scales") is not None:
                sbuf = self._put(rec["scales"])
        self.pool = engine_model.pages_to_pool(self.pool, buf, sbuf,
                                               self._put(rec["row"]))

    def _exec_publish_pages(self, rec: Dict[str, Any]) -> None:
        """Execute one `publish_pages` record — the pipelined-disagg
        seam's partial cache_to_pool scatter: newly completed chunks of
        an in-flight chunked prefill move into the pool ahead of the
        finish commit. The scratch cache stays registered (later chunks
        keep writing it)."""
        log = self._mh_log
        if log is not None and self._mh_leader:
            log.publish("publish_pages", **rec)
        cache = self._scratch_caches[int(rec["slot"])]
        self.pool = engine_model.cache_to_pool(
            self.pool, cache, self.cfg, self._put(rec["row"]))

    def _exec_pager_out(self, rec: Dict[str, Any]) -> None:
        """Follower half of KVPager.demote (`pager_out` — the leader's
        publish lives in the pager, right before ITS launch): enter the
        same pool_to_pages gather, then park THIS RANK's addressable
        shard slice of the gathered pages in the per-host cold store,
        keyed by the record's cold keys. Followers never run the
        pager's eviction policy — they mirror its launches and park
        their own bytes (each rank's host tier holds only its shard
        slice)."""
        from generativeaiexamples_tpu.serving import multihost as mh

        got, got_s = engine_model.pool_to_pages(self.pool,
                                                self._put(rec["row"]))
        codes, c_idx = mh.fetch_addressable_slice(
            got, "pager demote gather (codes)")
        scales = s_idx = None
        if got_s is not None:
            scales, s_idx = mh.fetch_addressable_slice(
                got_s, "pager demote gather (scales)")
        if self._mh_cold_meta is None:
            # Page-batch dim 0 is replicated (only kv-heads shard), so
            # the per-page local index is the fetch index minus dim 0.
            self._mh_cold_meta = {
                "codes_sharding": getattr(got, "sharding", None),
                "codes_index": c_idx[1:],
                "scales_sharding": (None if got_s is None else
                                    getattr(got_s, "sharding", None)),
                "scales_index": None if s_idx is None else s_idx[1:],
            }
        for j in range(int(rec["n"])):
            self._mh_cold[int(rec["keys"][j])] = (
                np.ascontiguousarray(codes[j]),
                None if scales is None
                else np.ascontiguousarray(scales[j]))

    def _exec_pager_in(self, rec: Dict[str, Any]) -> None:
        """Follower half of KVPager.promote_into (`pager_in`): rebuild
        the promoted pages' global device arrays from this rank's cold
        store (put_local_slice — collective-free, each rank supplies
        its own shard slice) and enter the same pages_to_pool scatter
        the leader launched. A missing cold key means the streams
        diverged — raise by name instead of scattering garbage."""
        from generativeaiexamples_tpu.serving import multihost as mh
        from generativeaiexamples_tpu.serving.disagg import page_geometry

        meta = self._mh_cold_meta
        if meta is None:
            raise mh.MultihostError(
                "pager_in record before any pager_out — the follower "
                "cold store is empty; leader and follower replay "
                "streams have diverged")
        row = np.asarray(rec["row"])
        w = int(row.shape[0])
        entries = []
        for j in range(int(rec["n"])):
            key = int(rec["keys"][j])
            got = self._mh_cold.get(key)
            if got is None:
                raise mh.MultihostError(
                    f"pager_in references cold key {key} this rank "
                    "never parked (pager_out) — leader and follower "
                    "replay streams have diverged")
            entries.append(got)
        codes_shape, codes_dtype, scales_shape = page_geometry(self.pool)
        c_idx = meta["codes_index"]
        staged = np.zeros(
            (w,) + tuple(sl.stop - sl.start for sl in c_idx),
            codes_dtype)
        for j, (c, _) in enumerate(entries):
            staged[j] = c
        buf = mh.put_local_slice(staged, (slice(0, w),) + tuple(c_idx),
                                 (w,) + codes_shape,
                                 meta["codes_sharding"])
        sbuf = None
        if scales_shape and meta["scales_index"] is not None:
            s_idx = meta["scales_index"]
            s_staged = np.zeros(
                (w,) + tuple(sl.stop - sl.start for sl in s_idx),
                np.float32)
            for j, (_, s) in enumerate(entries):
                s_staged[j] = s
            sbuf = mh.put_local_slice(
                s_staged, (slice(0, w),) + tuple(s_idx),
                (w,) + scales_shape, meta["scales_sharding"])
        self.pool = engine_model.pages_to_pool(self.pool, buf, sbuf,
                                               self._put(row))

    def _mh_replay_table(self) -> Dict[str, Any]:
        """kind -> executor for multihost.run_follower: the full launch
        vocabulary a leader can publish. Followers call the same
        executors the leader's scheduler calls (with _mh_leader False,
        so the publish inside each is skipped)."""
        return {"prefill": self._exec_prefill,
                "plan": self._exec_plan,
                "seed": self._exec_seed,
                "commit": self._exec_commit,
                "pages_out": self._exec_pages_out,
                "pages_in": self._exec_pages_in,
                "publish_pages": self._exec_publish_pages,
                "pager_out": self._exec_pager_out,
                "pager_in": self._exec_pager_in}

    def _pick_k(self, bound: int) -> int:
        """Largest dispatchable K <= bound (decode_block.round_to_warm):
        the invariant "no cold K mid-traffic" holds even when the bound
        is below every warmed variant, K=1 being in every warm set."""
        return decode_block.round_to_warm(bound, self._warm_ks)

    def _advance_capacity(self, slot: "_Slot", used: int):
        """(table_cap, avail): tokens this slot can still store against
        the page-table limit, and against its allocated pages PLUS the
        pool's current free pages. One definition shared by both
        dispatch paths and _reap_starved — three hand-rolled copies of
        this arithmetic is how starve/finish divergence happens."""
        ps = self.pool.page_size
        table_cap = self.max_pages * ps - used
        in_page = len(slot.seq.pages) * ps - used
        avail = in_page + self.allocator.n_free * ps
        if self.window_allocator is not None:  # the shorter of the two
            seq = slot.seq
            held = (seq.window_first + len(seq.window_pages)) * ps - used
            avail = min(avail, held + self.window_allocator.n_free * ps)
        return table_cap, avail

    def _starve(self, slot_idx: int) -> None:
        """The dispatcher can't advance this slot. If blocks are still in
        flight for it, its remaining tokens (possibly incl. a legitimate
        eos/max-tokens finish) haven't been processed yet — finishing now
        would drop them. Defer; _reap_starved finishes it if it survives
        the drain."""
        slot = self.slots[slot_idx]
        if slot is None:
            return
        in_flight = any(s is slot for fl in self._inflight
                        for _, s, _ in fl.metas)
        if in_flight:
            slot.no_capacity = True
        else:
            self._finish(slot_idx, "length")

    def _reap_starved(self) -> None:
        """Finish slots that were starved of page capacity AND still
        cannot advance now that their in-flight blocks have drained.
        Capacity can come back between the starve and the drain — a
        speculative landing refunds its worst-case reservation
        (kv_worst -= spec_worst in _process_spec_block) and retiring
        slots free pool pages — so finishing unconditionally here would
        truncate streams with reason "length" while pages are free."""
        # A verify step writes k/v for every packed tree node, so the
        # revival floor is the full node count (== k+1 on linear/plain
        # engines — byte-identical to the pre-tree reap rule).
        r = self._spec_tree_nodes if self._spec_k else 1
        reclaimable_pages = None  # computed at most once per pass: the
        # tree cannot change between iterations of this scheduler loop
        for i, slot in enumerate(self.slots):
            if slot is None or not slot.no_capacity:
                continue
            if any(s is slot for fl in self._inflight
                   for _, s, _ in fl.metas):
                continue
            table_cap, avail = self._advance_capacity(
                slot, self._slot_used(slot))
            if self.prefix_cache is not None and avail < r:
                # Cold cached pages are reclaimable on demand (the
                # allocator's reclaim hook evicts inside alloc); a slot
                # must not be cut with 'length' while they could back
                # it. Slow path only — reclaimable() walks the tree.
                if reclaimable_pages is None:
                    reclaimable_pages = self.prefix_cache.reclaimable()
                avail += reclaimable_pages * self.pool.page_size
            if table_cap >= r and avail >= r:
                slot.no_capacity = False
                continue
            self._finish(i, "length")

    def _process_block_host(self, fl: _InFlight, block) -> None:
        """Emit/finish slots from a block already fetched to the host
        ([B, K+1], or (targets, counts) for speculative blocks;
        scheduler thread)."""
        now = time.perf_counter()
        if fl.spec_worst:
            # Records its own token count (the first-token flush inside
            # it already self-records; a wrapper delta would double-
            # count those).
            self._process_spec_block(fl, block)
            return
        self._pace_engaged = self._pace_decide(fl.K)
        tokens_before = self.metrics.tokens_out
        for i, slot, first_col in fl.metas:
            if self.slots[i] is not slot:
                continue  # retired while this block was in flight
            if first_col == 0:
                if slot.first_emitted:
                    # The early async-fetch path already emitted col 0's
                    # value (same device buffer); skip the duplicate.
                    first_col = 1
                else:
                    # The slot's very first token (sampled at prefill)
                    # lands with this fetch — this is the honest TTFT.
                    slot.first_emitted = True
                    ttft_ms = (now - slot.req.submit_time) * 1e3
                    self.metrics.record_ttft(ttft_ms)
                    self._flight_first(slot, i, ttft_ms)
                    if slot.span is not None:
                        slot.span.add_event("first_token",
                                            {"ttft_ms": round(ttft_ms, 2)})
            for j in range(first_col, fl.K + 1):
                tok = int(block[i, j])
                slot.last_token = tok
                self._emit(slot, tok, slot_idx=i)
                if self.slots[i] is not slot:
                    break  # finished mid-block; rest is overshoot
            if fl.plain_spec:
                # Plain block on a speculative engine (sampled-request
                # fallback): all K tokens always advance, so the
                # host's reconciled length moves exactly K and the
                # dispatch-time reservation is released in full.
                slot.kv_len += fl.K
                slot.kv_worst -= fl.K
        paced = self._pace_engaged
        self._pace_engaged = False
        end = time.perf_counter()
        for i, slot, _ in fl.metas:
            if self.slots[i] is slot:
                if paced:
                    self._pace_commit(slot, end)
                else:
                    slot.pace_last_land = end  # keep the estimate fresh
        self.metrics.record_tokens(self.metrics.tokens_out - tokens_before)

    def _process_spec_block(self, fl: _InFlight, block) -> None:
        """Emit a landed speculative block: per slot and outer step,
        the first counts[i, s] entries of targets[i, s] are committed
        greedy tokens. Reconciles the host's worst-case page/budget
        bookkeeping with the actual acceptance."""
        targets, counts = block
        block_emitted = 0
        self._pace_engaged = self._pace_decide(fl.K * (self._spec_k + 1))
        for i, slot, base_len in fl.metas:
            if self.slots[i] is not slot:
                continue  # retired while in flight
            if not slot.first_emitted:
                # The first token (async prefill copy) must hit the
                # stream before any decode tokens; force it now.
                self._flush_first_for(slot)
            emitted = 0
            for s_ in range(fl.K):
                for j in range(int(counts[i, s_])):
                    tok = int(targets[i, s_, j])
                    slot.last_token = tok
                    self._emit(slot, tok, slot_idx=i)
                    emitted += 1
                    if self.slots[i] is not slot:
                        break
                if self.slots[i] is not slot:
                    break
            if self.slots[i] is slot:
                # Refund the unaccepted worst-case tokens so the budget
                # cap doesn't strand the request; kv_len/kv_worst move
                # the page bookkeeping to the actual acceptance while
                # still covering any sibling block in flight.
                slot.scheduled -= fl.spec_worst - emitted
                slot.kv_len += emitted
                slot.kv_worst -= fl.spec_worst
            block_emitted += emitted
            self.metrics.spec_slot_steps += fl.K
        paced = self._pace_engaged
        self._pace_engaged = False
        end = time.perf_counter()
        for i, slot, _ in fl.metas:
            if self.slots[i] is slot:
                if paced:
                    self._pace_commit(slot, end)
                else:
                    slot.pace_last_land = end
        self.metrics.spec_committed += block_emitted
        self.metrics.record_tokens(block_emitted)

    def _flush_first_for(self, slot: "_Slot") -> None:
        """Blocking emission of one slot's pending first token (its
        transfer started at prefill dispatch, so this is near-free by
        the time a decode block for the same slot has landed)."""
        for item in list(self._pending_first):
            toks, metas, _ = item
            if not any(s is slot for _, s in metas):
                continue
            self._pending_first.remove(item)
            self._emit_first_values(
                mh_fetch_replicated(
                    toks, "prefill first-token readback").reshape(-1),
                metas)
            return

    def _emit_first_values(self, vals: np.ndarray, metas) -> None:
        now = time.perf_counter()
        for j, (slot_idx, slot) in enumerate(metas):
            if self.slots[slot_idx] is not slot or slot.first_emitted:
                continue
            slot.first_emitted = True
            ttft_ms = (now - slot.req.submit_time) * 1e3
            self.metrics.record_ttft(ttft_ms)
            self._flight_first(slot, slot_idx, ttft_ms)
            if slot.span is not None:
                slot.span.add_event("first_token",
                                    {"ttft_ms": round(ttft_ms, 2)})
            tok = int(vals[j])
            slot.last_token = tok
            self._emit(slot, tok, slot_idx=slot_idx)
            self.metrics.record_tokens(1)

    def _emit(self, slot: _Slot, tok: int, slot_idx: int) -> None:
        self.metrics.tokens_out += 1
        slot.generated += 1
        eos_ids = getattr(self.tokenizer, "eos_ids", None) or \
            {getattr(self.tokenizer, "eos_id", None)}
        eos = tok in eos_ids or tok in slot.req.stop_ids
        text = "" if eos else slot.detok.push(tok)
        finished = eos or slot.generated >= slot.req.max_new_tokens
        reason = ("stop" if eos else
                  "length" if slot.generated >= slot.req.max_new_tokens else None)
        self._stream_put(slot, {
            "text": text, "token_id": tok, "finished": finished,
            "finish_reason": reason,
        })
        if finished:
            self._finish(slot_idx, reason or "stop", emit=False)

    def _pace_decide(self, burst: int) -> bool:
        """Pacing engages only for interactive regimes: multi-token
        bursts with few live streams. Above the stream threshold (bulk
        throughput workloads) emission stays burst-granular with zero
        pacing overhead."""
        lim = self.ecfg.pace_emission_max_streams
        if lim <= 0 or burst <= 1:
            return False
        live = sum(1 for s in self.slots
                   if s is not None and not s.prefilling)
        return 0 < live <= lim

    def _stream_put(self, slot: _Slot, ev: Dict) -> None:
        """Deliver a stream event, buffering non-terminal tokens for the
        pacer while a block is being processed with pacing engaged.
        Terminal events always flush everything buffered first, so
        completion latency and event order are never affected."""
        # slot.generated > 1: a slot's FIRST token is never paced (it
        # is the TTFT the async-prefill-copy path fought for).
        if self._pace_engaged and not ev["finished"] and slot.generated > 1:
            slot.pace_buf.append(ev)
            return
        # Fast path: nothing buffered anywhere for anyone -> no lock.
        # Both containers are only ever populated by this scheduler
        # thread, so the check is race-free; bulk workloads (pacing
        # disengaged) emit every token through here.
        if not slot.pace_buf and not self._pace_entries:
            slot.req.stream.put(ev)
            return
        self._pace_flush(slot)
        slot.req.stream.put(ev)

    def _pace_flush(self, slot: _Slot) -> None:
        """Instantly deliver everything the pacer still holds for this
        slot (older block first, then the current buffer), in order."""
        entry = None
        with self._pace_lock:
            entry = self._pace_entries.pop(id(slot), None)
        if entry is not None:
            for ev in entry["buf"]:
                slot.req.stream.put(ev)
        if slot.pace_buf:
            for ev in slot.pace_buf:
                slot.req.stream.put(ev)
            slot.pace_buf = []

    def _pace_commit(self, slot: _Slot, now: float) -> None:
        """End of a block's processing: hand this slot's buffered burst
        to the pacer, spaced over the observed block interval (capped
        at 100 ms/token). If the previous block's tokens are still
        queued (pacer fell behind), they flush instantly first — the
        pacer is never more than one block behind real delivery."""
        if not slot.pace_buf:
            slot.pace_last_land = now
            return
        n = len(slot.pace_buf)
        interval = (now - slot.pace_last_land) if slot.pace_last_land else 0.0
        slot.pace_last_land = now
        spacing = min(interval / n, 0.1)
        if spacing < 0.004:
            # First block, or blocks landing fast enough that bursts
            # are already smooth — pacing would only add wakeup churn.
            # What the pacer still holds of a SLOWER block before this
            # one goes first: putting this burst past it handed a
            # stream its tokens out of order (ROADMAP D7).
            self._pace_flush(slot)
            return
        with self._pace_lock:
            prev = self._pace_entries.pop(id(slot), None)
            if prev is not None:
                for ev in prev["buf"]:
                    slot.req.stream.put(ev)
            self._pace_entries[id(slot)] = {
                "slot": slot, "buf": deque(slot.pace_buf),
                "next_t": now + spacing, "spacing": spacing,
            }
        slot.pace_buf = []
        self._pace_wake.set()

    def _release_seq(self, seq: SequencePages) -> None:
        """Free a retired sequence's pages — deferred until the newest
        in-flight decode block (which may still write into them for the
        retired slot) has landed, so a re-allocation can't race it."""
        if self._inflight:
            self._inflight[-1].releases.append(seq)
        else:
            seq.release()
            self._gauge_window_pages()

    def _gauge_window_pages(self) -> None:
        """`window_pages_held`: after a landed block's slides and
        releases, and after a release that waited for none."""
        if self.window_allocator is not None:
            wa = self.window_allocator
            self.metrics.window_pages_held = wa.n_pages - 1 - wa.n_free

    def _finish(self, slot_idx: int, reason: str, emit: bool = True) -> None:
        slot = self.slots[slot_idx]
        if slot is None:
            return
        self._flight_retire(slot, slot_idx, reason)
        self._pace_flush(slot)
        if emit:
            slot.req.stream.put({"text": "", "token_id": -1, "finished": True,
                                 "finish_reason": reason})
        self._release_seq(slot.seq)
        self.slots[slot_idx] = None
        self._mark_done(slot)
        self._wake.set()

    def _mark_done(self, slot: _Slot) -> None:
        if slot.span is not None:
            slot.span.set_attribute("tokens_generated", slot.generated)
            slot.span.end()
