"""Engine flight recorder: per-beat scheduler timeline, exponential
latency histograms, Chrome-trace export, and stall attribution.

The engine's aggregate counters (EngineMetrics) answer "how much"; the
flight recorder answers "where did the time go". One compact record per
scheduling beat (a landed decode block) and one per request lifecycle
event, written by the SCHEDULER THREAD ONLY into preallocated numpy
ring buffers — O(1) append, no locks, no allocation per beat — cheap
enough to stay ON in production (the overhead is pinned by
scripts/smoke_flight.py and reported as a bench extra). On top of it:

- `ExpHistogram` — exponential-bucket latency histograms (TTFT, e2e,
  queue wait per tier, beat gap, promote ms/page) replacing the old
  sliding p50/p95 window: mergeable across a fleet, exportable in
  native Prometheus histogram form, always present in `snapshot()`.
- `ProgramLedger` — every program the process enqueues on the device
  (decode block, prefill group, chunk / commit, encoder forward) gets
  a sequence number and three instants on one clock: enqueue, ready
  (stamped by the thread on which the wait for its result ends) and
  start = max(enqueue, the previous program's ready): one device
  queue runs in order; a fourth, where its dispatch call returned.
  The scheduler drains it into `program` events.
- `HostPauses` — what the HOST was doing while a program "ran long":
  the interpreter's collections (one process-wide `gc.callbacks` hook),
  beside the dispatch calls' own lengths (the ledger's) and the
  scheduler's late wake-ups (the engine's), all on the ledger's clock.
  The scheduler drains them into `host_pause` events, and a stalled
  program says how much of its interval they cover (`host_cover`).
- `chrome_trace()` — the recorder rings rendered as Chrome trace-event
  JSON (Perfetto loads it directly): one process lane per replica, one
  slice per beat and per prefill / encoder program on the device lane
  (start -> ready, as the ledger infers it), request spans correlated
  to beats via rid, instant markers for the known gap causes
  (admission retry, qos pause, pager promote/demote, prefill chunks).
- `scripts/analyze_timeline.py` consumes that JSON and splits wall
  time into device-busy (by program class) / host-gap / idle with
  named gap causes.

Thread model (deliberately lock-free): every `record_*` call happens on
the engine scheduler thread (submit-time events are recorded
RETROACTIVELY at admission pop, stamped with `req.submit_time`, so no
server thread ever writes; an encoder's threads and the engine's
completion waiters stamp `ProgramLedger` rows, which the scheduler
drains into the ring the same way). Readers (`/metrics`, `/debug/timeline`)
copy the rings without a lock; each row carries a double sequence
stamp (`seq` written first, `seq2` last) and snapshot() drops rows
whose stamps disagree or fall outside the live window — a torn row is
skipped, never mis-read. `ExpHistogram` is single-writer the same way
(observe() on the scheduler thread, snapshot() copies).
"""

from __future__ import annotations

import bisect
import gc
import heapq
import math
import statistics
import threading
import time
from collections import deque
from typing import (
    Any, Callable, Deque, Dict, Iterable, List, Optional, Tuple)

import numpy as np

# -- lifecycle event kinds ---------------------------------------------------

EV_SUBMIT = 1          # request entered the waiting queue (ts=submit_time)
EV_QOS_PICK = 2        # weighted-fair scheduler picked it (engine.qos)
EV_ADMIT = 3           # slot reserved; a = queue wait ms, slot set
EV_PREFILL_DISPATCH = 4  # bucketed prefill group dispatched; a = prompt len
EV_PREFILL_CHUNK = 5   # one chunk fed (a = tokens; b = 1 on a fused rider)
EV_FIRST_TOKEN = 6     # first token emitted; a = ttft ms
EV_RETIRE = 7          # slot retired; code = reason, a = tokens, b = e2e ms
EV_ADMIT_RETRY = 8     # admission failed on page exhaustion (requeued)
EV_QOS_PAUSE = 9       # long prefill paused for a latency-tier TTFT phase
EV_QOS_RESUME = 10     # ... and resumed
EV_KV_PROMOTE = 11     # pager promote (a = pages, b = ms)
EV_KV_DEMOTE = 12      # pager/cache reclaim demotion (a = pages)
# Elastic-fleet control events (serving/autoscaler.py / chaos.py /
# EngineFleet.rolling_upgrade). These are NOT written into an engine's
# recorder — each controller owns its own single-writer recorder lane
# (fleet.extra_flight_lanes), so the scheduler-thread-only invariant
# above holds per ring. aux carries the replica id; a = active-replica
# count after the action.
EV_SCALE_UP = 13       # autoscaler activated/spawned a replica
EV_SCALE_DOWN = 14     # autoscaler parked a replica (warm/cold)
EV_SCALE_WAKE = 15     # submit-time wake of a parked fleet (a = 1)
EV_UPGRADE = 16        # one replica rolled (b = drain+swap ms)
EV_CHAOS = 17          # chaos injection (aux = "<kind>:<rid>")
# Disaggregated prefill/decode (serving/disagg.py): KV pages imported
# from a prefill-role replica (a = pages, b = import ms). Written by
# the IMPORTING engine's scheduler thread (the transfer runs as a
# control op), so the single-writer ring invariant holds and the
# analyzer attributes the beat gap it causes to "disagg".
EV_KV_TRANSFER = 18
# Sparse experts: one a landed decode block of a model that has them
# (scheduler thread). a = token-expert pairs computed on the experts
# held here, per step and expert layer; b = the pairs of the busiest
# held expert of any one layer over the mean (1.0 = even load); aux =
# "hit=<n> of=<m>", the (expert layer, held expert, step) triples of the
# block in which the expert took a pair, and all of them.
EV_MOE_LOAD = 19
# One a program the device finished (`ProgramLedger`, below), written
# by the scheduler thread when the completion is known. ts = t_ready;
# code = class (PROGRAM_CLASSES); a = t_ready - t_enqueue ms (what the
# host waited); b = t_ready - t_start ms (what the device ran); slot =
# rows that were live (decode: slots; prefill: prompts; encoder:
# texts); aux = "seq=<n> n=<steps or real tokens> shape=<shape>".
EV_PROGRAM = 20
# The first decode block in which a request's slot is live was
# enqueued: ts = that block's t_enqueue, b = its sequence number.
EV_DECODE_JOIN = 21
# Learned sparse attention: one a landed decode block of a model with an
# indexer (scheduler thread), from the lengths the host dispatched the
# block with. a = index keys scored a live slot, step and layer (the
# block's mean context); b = rows attended over keys scored
# (min(length, topk) / length, summed: 1.0 while every context is
# within topk).
EV_SPARSE_SELECT = 22
# Window and global layers in one model: one a landed decode block of a
# model with window rows (scheduler thread), from the lengths and tables
# the host dispatched the block with. a = cached tokens the block's
# attention calls see over layers x context of its live slots (what one
# kind of row would have seen); b = pages x rows the live slots hold in
# both pools over what one table for every row would hold; aux =
# "window_pages=<n> calls=<m> updates=<u> global_pages=<n>
# global_calls=<m>", the pages (a page a live slot and call) the window
# rows' kernel calls walked in the block, those calls, the softmax
# updates the pages were folded into, and the pages the GLOBAL rows'
# calls walked (a live slot's whole context a call) with those calls.
EV_WINDOW_CACHE = 23
# Recurrent state beside a latent row: one a landed decode block of a
# model with both (scheduler thread), from the lengths the host
# dispatched the block with. a = the live slots' mean context over the
# block's steps; b = a live sequence's latent-row bytes over its state +
# tail + latent-row bytes (the share of a sequence that GROWS).
EV_STATE_CACHE = 24
# The host stood still (scheduler thread, from `HostPauses` and its own
# timed waits). ts = the pause's end; a = its length in ms; code = cause
# (PAUSE_CAUSES: 0 a collection of the interpreter's, 1 a timed wait of
# the scheduler's that came back late); b = the generation (gc) or 0;
# aux = "gen=<g> collected=<n> thread=<name>" or "where=<fetch|idle>".
EV_HOST_PAUSE = 25
# Several residual streams mixed around every branch
# (models/hyper_connections.py): one a landed decode block of a model
# with hc_mult > 1 (scheduler thread), from the mask the host dispatched
# the block with. a = branches mixed a step of the block (live slots x 2
# x layers); b = the stream's bytes a token (hc_mult x dim x itemsize).
EV_RESIDUAL_MIX = 26

# Program classes (EV_PROGRAM.code).
PROG_DECODE = 0    # a decode block (n = steps K)
PROG_PREFILL = 1   # a bucketed prefill group (n = real prompt tokens)
PROG_CHUNK = 2     # a prefill chunk (n = real tokens) or a commit (n = 0)
PROG_ENCODER = 3   # an encoder forward (n = real tokens)
PROGRAM_CLASSES = ("decode", "prefill", "chunk", "encoder")

# A program whose device time exceeds this many times the running
# median of its class and shape is a stall: counted (`program_stalls`)
# and logged once by the engine.
STALL_FACTOR = 8.0
STALL_MEDIAN_WINDOW = 32   # the median's last samples, a class and shape
STALL_MIN_SAMPLES = 4      # fewer than these say nothing yet

# Host pauses (EV_HOST_PAUSE.code). A dispatch call is the third cause a
# stall can name; it has no event of its own, it is `call=` on its
# program's.
PAUSE_GC = 0
PAUSE_LATE_WAKE = 1
PAUSE_CAUSES = ("gc", "late_wake")
CAUSE_DISPATCH_CALL = "dispatch_call"
# A collection shorter than this is summed and not listed, unless it is
# of generation 2.
PAUSE_MIN_MS = 1.0
# A timed wait of the scheduler's that returns unset this long past its
# timeout was held off: another thread kept the interpreter's lock, or
# the OS the core.
LATE_WAKE_MS = 20.0

EVENT_NAMES = {
    EV_SUBMIT: "submit", EV_QOS_PICK: "qos_pick", EV_ADMIT: "admit",
    EV_PREFILL_DISPATCH: "prefill_dispatch",
    EV_PREFILL_CHUNK: "prefill_chunk", EV_FIRST_TOKEN: "first_token",
    EV_RETIRE: "retire", EV_ADMIT_RETRY: "admission_retry",
    EV_QOS_PAUSE: "qos_pause", EV_QOS_RESUME: "qos_resume",
    EV_KV_PROMOTE: "kv_promote", EV_KV_DEMOTE: "kv_demote",
    EV_SCALE_UP: "scale_up", EV_SCALE_DOWN: "scale_down",
    EV_SCALE_WAKE: "scale_wake", EV_UPGRADE: "upgrade",
    EV_CHAOS: "chaos", EV_KV_TRANSFER: "kv_transfer",
    EV_MOE_LOAD: "moe_load", EV_PROGRAM: "program",
    EV_DECODE_JOIN: "decode_join", EV_SPARSE_SELECT: "sparse_select",
    EV_WINDOW_CACHE: "window_cache", EV_STATE_CACHE: "state_cache",
    EV_HOST_PAUSE: "host_pause", EV_RESIDUAL_MIX: "residual_mix",
}

# Retire reason codes (EV_RETIRE.code); anything unknown maps to -1.
RETIRE_CODES = {"stop": 0, "length": 1, "error": 2, "cancelled": 3}
RETIRE_NAMES = {v: k for k, v in RETIRE_CODES.items()}

# Gap-cause instants the analyzer attributes host gaps to, in priority
# order (a gap containing several causes is charged to the first).
GAP_CAUSE_KINDS = (EV_QOS_PAUSE, EV_KV_PROMOTE, EV_KV_TRANSFER,
                   EV_ADMIT_RETRY, EV_PREFILL_CHUNK, EV_KV_DEMOTE)

# Fleet control-plane instants: rendered on the timeline (cat "fleet",
# so a TTFT spike can be eyeballed against the scale/upgrade/chaos
# event that caused it) but deliberately NOT gap causes — a replica's
# host gap is never *explained* by another replica being scaled.
FLEET_INSTANT_KINDS = (EV_SCALE_UP, EV_SCALE_DOWN, EV_SCALE_WAKE,
                       EV_UPGRADE, EV_CHAOS)

BEAT_DTYPE = np.dtype([
    # seq opens the record, seq2 CLOSES it and sits LAST in memory:
    # snapshot copies read fields in address order, so a row whose
    # stamps agree was fully written before the copy reached it (the
    # per-record seqlock).
    ("seq", "<i8"),
    ("t_dispatch", "<f8"),    # perf_counter when the block's dispatch returned
    # The block's ledger stamps: when its result was complete (the
    # clock of the thread that waited on it) and the t_ready of the
    # PROGRAM enqueued just before it, a prefill or an encoder forward
    # too (0 on the first). max(t_dispatch, t_prev_ready) -> t_ready is what
    # the device ran for this block and nothing else.
    ("t_ready", "<f8"),
    ("t_prev_ready", "<f8"),
    # StepPlan lattice point of the landed dispatch.
    ("decode_k", "<i2"), ("spec_k", "<i2"), ("tree_branches", "<i2"),
    ("rider_width", "<i4"),
    ("spec_state", "?"), ("fused_rider", "?"), ("qos_paused", "?"),
    # Busy slots and waiting-queue depth per QoS tier at landing.
    ("busy_latency", "<i2"), ("busy_standard", "<i2"), ("busy_batch", "<i2"),
    ("wait_latency", "<i2"), ("wait_standard", "<i2"), ("wait_batch", "<i2"),
    ("tokens_emitted", "<i4"),
    # Pager pages moved since the previous beat (scheduler-side moves).
    ("kv_demote_pages", "<i4"), ("kv_promote_pages", "<i4"),
    ("seq2", "<i8"),
])

EVENT_DTYPE = np.dtype([
    ("seq", "<i8"),
    ("ts", "<f8"), ("kind", "<u1"), ("tier", "<u1"),
    ("code", "<i2"), ("slot", "<i2"),
    ("a", "<f8"), ("b", "<f8"),
    ("seq2", "<i8"),
])

# Always-present /metrics keys the recorder contributes (zeros when the
# recorder is off — the repo-wide counter convention).
FLIGHT_KEYS = ("flight_beats", "flight_events", "flight_enabled")

# Always-present histogram keys in EngineMetrics.snapshot() (each maps
# to an ExpHistogram snapshot dict; zero-count dicts when idle).
HIST_KEYS = (
    "hist_ttft_ms", "hist_e2e_ms",
    "hist_queue_wait_ms_latency", "hist_queue_wait_ms_standard",
    "hist_queue_wait_ms_batch",
    "hist_beat_gap_ms", "hist_kv_promote_ms_per_page",
    "hist_kv_transfer_ms_per_page",
    # From the program ledger, a prefill group's t_start - t_enqueue
    # (what the blocks in flight cost a new arrival) and its device time.
    "hist_device_queue_ms", "hist_program_ms_prefill",
    # The host's pauses (collections and late wake-ups, the `host_pause`
    # event's `a`) and every program's dispatch call (its `call=`).
    "hist_host_pause_ms", "hist_dispatch_call_ms",
)


# ---------------------------------------------------------------------------
# Exponential-bucket histogram
# ---------------------------------------------------------------------------


def default_bounds(lo: float = 0.01, hi: float = 6e7,
                   factor: float = math.sqrt(2.0)) -> Tuple[float, ...]:
    """Geometric bucket upper bounds in ms: 10 us .. ~16.6 h by
    sqrt(2) steps (~52 buckets). One FIXED scheme everywhere so fleet
    merges are element-wise sums, never bucket realignment."""
    out = []
    b = lo
    while b < hi:
        out.append(round(b, 6))
        b *= factor
    return tuple(out)


_DEFAULT_BOUNDS = default_bounds()


class ExpHistogram:
    """Exponential-bucket histogram: O(log buckets) observe into a
    preallocated int64 array, no allocation, single-writer lock-free
    (the scheduler thread observes; scrapes copy).

    snapshot() is JSON-ready and Prometheus-shaped: per-bucket counts
    keyed by their string upper bound, plus count/sum and interpolated
    p50/p95/p99 estimates (exact enough for dashboards; the bucket
    scheme bounds the relative error at sqrt(2))."""

    __slots__ = ("bounds", "counts", "count", "total")

    def __init__(self, bounds: Tuple[float, ...] = _DEFAULT_BOUNDS):
        self.bounds = bounds
        self.counts = np.zeros(len(bounds) + 1, np.int64)  # +overflow
        self.count = 0
        self.total = 0.0

    # graftlint: hot-path
    def observe(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.count += 1
        self.total += value

    def snapshot(self) -> Dict[str, Any]:
        # Read count/total BEFORE copying the bucket array: observe()
        # increments the bucket first, so a scrape racing a writer can
        # only see count <= sum(buckets) — the reverse order would let
        # a {count: 1, buckets: {}} snapshot send hist_quantile to the
        # top bound (~12 h) for that scrape.
        count, total = self.count, self.total
        counts = self.counts.copy()
        snap = {
            "count": count,
            "sum": round(total, 3),
            "buckets": {str(b): int(c)
                        for b, c in zip(self.bounds, counts) if c},
            "overflow": int(counts[-1]),
        }
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            snap[key] = hist_quantile(snap, q, bounds=self.bounds)
        return snap


def zero_hist_snapshot() -> Dict[str, Any]:
    """The always-present empty-histogram shape (same keys a live one
    emits), for metrics objects with no histogram backing."""
    return {"count": 0, "sum": 0.0, "buckets": {}, "overflow": 0,
            "p50": None, "p95": None, "p99": None}


def hist_quantile(snap: Dict[str, Any], q: float,
                  bounds: Tuple[float, ...] = _DEFAULT_BOUNDS
                  ) -> Optional[float]:
    """Interpolated quantile estimate from a histogram snapshot dict
    (None when empty). Works on merged/JSON-round-tripped snapshots."""
    total = int(snap.get("count") or 0)
    if total <= 0:
        return None
    # Bucket keys may be a subset (zero buckets omitted); walk the full
    # bound scheme so interpolation has a stable lower edge. Clamp the
    # target to the actual bucket mass: a foreign/merged snapshot whose
    # count outruns its buckets must not walk off the top bound.
    bdict = snap.get("buckets") or {}
    mass = sum(int(v) for v in bdict.values()) \
        + int(snap.get("overflow") or 0)
    if mass <= 0:
        return None
    target = min(q * total, mass)
    seen = 0.0
    prev_bound = 0.0
    for b in bounds:
        c = int(bdict.get(str(b), 0))
        if c and seen + c >= target:
            frac = (target - seen) / c
            return round(prev_bound + (b - prev_bound) * frac, 4)
        seen += c
        prev_bound = b
    return round(prev_bound, 4)  # overflow bucket: clamp to the top bound


def merge_hist_snapshots(snaps: List[Optional[Dict[str, Any]]]
                         ) -> Dict[str, Any]:
    """Element-wise merge of histogram snapshot dicts (missing/None
    entries contribute nothing) — the fleet aggregation primitive. All
    in-repo histograms share one bound scheme, so merge is a sum."""
    out = zero_hist_snapshot()
    buckets: Dict[str, int] = {}
    for s in snaps:
        if not isinstance(s, dict):
            continue
        out["count"] += int(s.get("count") or 0)
        out["sum"] = round(out["sum"] + float(s.get("sum") or 0.0), 3)
        out["overflow"] += int(s.get("overflow") or 0)
        for k, v in (s.get("buckets") or {}).items():
            buckets[k] = buckets.get(k, 0) + int(v)
    out["buckets"] = buckets
    for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
        out[key] = hist_quantile(out, q)
    return out


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Single-writer ring buffers for beat records and request
    lifecycle events. `enabled=False` keeps the object (and its
    always-present stats()) but turns every append into one branch."""

    def __init__(self, ring_size: int = 4096, enabled: bool = True):
        self.ring_size = max(64, int(ring_size))
        self.event_ring = self.ring_size * 4
        self.enabled = bool(enabled)
        self._beats = np.zeros(self.ring_size, BEAT_DTYPE)
        self._beats["seq"] = -1
        self._beats["seq2"] = -2
        self._events = np.zeros(self.event_ring, EVENT_DTYPE)
        self._events["seq"] = -1
        self._events["seq2"] = -2
        # Per-slot rid / aux strings parallel to the event ring
        # (assignment into a preallocated list: no per-event growth).
        self._event_rids: List[str] = [""] * self.event_ring
        self._event_aux: List[str] = [""] * self.event_ring
        self._n_beats = 0
        self._n_events = 0

    def set_enabled(self, enabled: bool) -> None:
        """Runtime toggle (bench uses it for the on-vs-off overhead
        pin). Existing ring contents are kept."""
        self.enabled = bool(enabled)

    # -- writers (engine scheduler thread ONLY) ----------------------------

    # graftlint: hot-path
    def record_beat(self, t_dispatch: float, t_ready: float,
                    t_prev_ready: float, decode_k: int, spec_k: int,
                    tree_branches: int, rider_width: int,
                    spec_state: bool,
                    fused_rider: bool, qos_paused: bool,
                    busy: Tuple[int, int, int],
                    wait: Tuple[int, int, int], tokens_emitted: int,
                    kv_demote_pages: int, kv_promote_pages: int) -> None:
        if not self.enabled:
            return
        seq = self._n_beats
        row = self._beats[seq % self.ring_size]
        row["seq"] = seq          # stamp FIRST ...
        row["t_dispatch"] = t_dispatch
        row["t_ready"] = t_ready
        row["t_prev_ready"] = t_prev_ready
        row["decode_k"] = decode_k
        row["spec_k"] = spec_k
        row["tree_branches"] = tree_branches
        row["rider_width"] = rider_width
        row["spec_state"] = spec_state
        row["fused_rider"] = fused_rider
        row["qos_paused"] = qos_paused
        row["busy_latency"], row["busy_standard"], row["busy_batch"] = busy
        row["wait_latency"], row["wait_standard"], row["wait_batch"] = wait
        row["tokens_emitted"] = tokens_emitted
        row["kv_demote_pages"] = kv_demote_pages
        row["kv_promote_pages"] = kv_promote_pages
        row["seq2"] = seq         # ... and LAST: readers drop torn rows
        self._n_beats = seq + 1

    # graftlint: hot-path
    def record_event(self, kind: int, ts: float, rid: str = "",
                     tier: int = 1, code: int = 0, slot: int = -1,
                     a: float = 0.0, b: float = 0.0,
                     aux: str = "") -> None:
        if not self.enabled:
            return
        seq = self._n_events
        i = seq % self.event_ring
        row = self._events[i]
        row["seq"] = seq
        row["ts"] = ts
        row["kind"] = kind
        row["tier"] = tier
        row["code"] = code
        row["slot"] = slot
        row["a"] = a
        row["b"] = b
        self._event_rids[i] = rid
        self._event_aux[i] = aux
        row["seq2"] = seq
        self._n_events = seq + 1

    # -- readers (any thread; lock-free torn-row-tolerant copies) ----------

    def _snapshot_ring(self, arr: np.ndarray, head: int, size: int
                       ) -> np.ndarray:
        copy = arr.copy()
        lo = max(0, head - size)
        seq = copy["seq"]
        ok = (seq == copy["seq2"]) & (seq >= lo) & (seq < head) \
            & (seq % size == np.arange(size))
        out = copy[ok]
        return out[np.argsort(out["seq"], kind="stable")]

    def snapshot_beats(self) -> np.ndarray:
        """Valid beat records, oldest first (up to ring_size)."""
        return self._snapshot_ring(self._beats, self._n_beats,
                                   self.ring_size)

    def snapshot_events(self) -> List[Dict[str, Any]]:
        """Valid lifecycle events as dicts, oldest first."""
        head = self._n_events
        rows = self._snapshot_ring(self._events, head, self.event_ring)
        out = []
        for r in rows:
            seq = int(r["seq"])
            i = seq % self.event_ring
            rid, aux = self._event_rids[i], self._event_aux[i]
            live = self._events[i]
            if int(live["seq"]) != seq or int(live["seq2"]) != seq:
                # The writer lapped this slot between the array copy
                # and the string reads: rid/aux now belong to a NEWER
                # event (the strings live outside the seqlocked row).
                # The live `seq` check is what catches a lap IN
                # PROGRESS — the writer stamps seq BEFORE the strings,
                # so new strings imply a new live seq even while seq2
                # still holds the old value. Drop the row rather than
                # mis-attribute it.
                continue
            out.append({
                "seq": seq, "ts": float(r["ts"]),
                "kind": int(r["kind"]), "tier": int(r["tier"]),
                "code": int(r["code"]), "slot": int(r["slot"]),
                "a": float(r["a"]), "b": float(r["b"]),
                "rid": rid, "aux": aux,
            })
        return out

    def stats(self) -> Dict[str, int]:
        """Always-present recorder counters (FLIGHT_KEYS)."""
        return {"flight_beats": self._n_beats,
                "flight_events": self._n_events,
                "flight_enabled": int(self.enabled)}


# ---------------------------------------------------------------------------
# The program ledger
# ---------------------------------------------------------------------------


class Program:
    """One program enqueued on the device. `seq` and `t_enqueue` are
    written by `ProgramLedger.enqueue`, `t_dispatched` by the same
    thread where its dispatch call returned, `t_ready` by whichever
    thread the wait for its result ends on, the rest by `drain` (and
    `host_ms`, of a stalled program, by the engine that drained it)."""

    __slots__ = ("seq", "cls", "rows", "n", "shape", "t_enqueue",
                 "t_dispatched", "t_ready", "t_start", "t_prev_ready",
                 "cancelled", "stalled", "median_ms", "host_ms")

    def __init__(self, seq: int, cls: int, rows: int, n: int, shape: str,
                 t_enqueue: float):
        self.seq = seq
        self.cls = cls
        self.rows = rows
        self.n = n
        self.shape = shape
        self.t_enqueue = t_enqueue
        self.t_dispatched = 0.0
        self.t_ready = 0.0
        self.t_start = 0.0
        self.t_prev_ready = 0.0
        self.cancelled = False
        self.stalled = False
        self.median_ms = 0.0  # of its class and shape, when it stalled
        self.host_ms = 0.0    # of a stall: what the host's pauses cover

    @property
    def waited_ms(self) -> float:
        """enqueue -> ready: what the host waited (the event's `a`)."""
        return (self.t_ready - self.t_enqueue) * 1e3

    @property
    def ran_ms(self) -> float:
        """start -> ready: what the device ran (the event's `b`)."""
        return (self.t_ready - self.t_start) * 1e3

    @property
    def queued_ms(self) -> float:
        """enqueue -> start: the wait behind the programs before it."""
        return (self.t_start - self.t_enqueue) * 1e3

    @property
    def call_ms(self) -> float:
        """enqueue -> its dispatch call returned: what the call itself
        held the calling thread (0 where nobody stamped the return)."""
        return max(0.0, (self.t_dispatched - self.t_enqueue) * 1e3)

    def aux(self) -> str:
        aux = (f"seq={self.seq} n={self.n} shape={self.shape} "
               f"call={self.call_ms:.3f}")
        if self.stalled:
            aux += f" stalled=1 host={self.host_ms:.1f}"
        return aux


def parse_program_aux(aux: str) -> Dict[str, str]:
    """`seq=12 n=8 shape=K8 call=0.412` -> {"seq": "12", "n": "8",
    "shape": "K8", "call": "0.412"}."""
    return dict(kv.split("=", 1) for kv in aux.split() if "=" in kv)


class ProgramLedger:
    """Every program one process enqueues on one device, in enqueue
    order. ANY thread stamps: `enqueue` just before its dispatch call
    (the sequence number and `t_enqueue` under one short lock, so both
    run in the same order), `ready` where its wait for the result ends.
    ONE thread, the engine's scheduler, calls `drain`, which hands back
    the programs whose start can be inferred, oldest first:

        t_start = max(t_enqueue, t_ready of the program enqueued before)

    because one device queue runs in order. The open rows are the
    hand-off between the stamping threads and the flight ring's single
    writer; they are bounded, and with nobody draining (an encoder
    served with no engine beside it) the oldest row is dropped.

    Two stamps can be a little out of the device's order: a thread
    that waits on another clock tick, or an encoder thread and the
    scheduler racing between stamp and dispatch. A start is therefore
    clamped to its own program's ready (a device time is never
    negative) and the "previous ready" only moves forward."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 capacity: int = 1024):
        self._clock = clock
        self._capacity = max(2, int(capacity))
        self._lock = threading.Lock()
        self._open: Deque[Program] = deque()
        # The last dispatch calls, (t_enqueue, t_dispatched): what a
        # stall's interval is held against (`calls`), whether or not
        # their programs are complete yet.
        self._calls: Deque[Tuple[float, float]] = deque(maxlen=256)
        self._n = 0
        self._prev_ready = 0.0
        self.dropped = 0
        # (class, shape) -> the last device times, for the stall rule.
        self._recent: Dict[Tuple[int, str], Deque[float]] = {}

    # -- stamps (any thread) -----------------------------------------------

    # graftlint: hot-path
    def enqueue(self, cls: int, rows: int = 0, n: int = 0,
                shape: str = "") -> Program:
        with self._lock:
            prog = Program(self._n, cls, rows, n, shape, self._clock())
            self._n += 1
            self._open.append(prog)
            if len(self._open) > self._capacity:
                self._open.popleft()
                self.dropped += 1
        return prog

    # graftlint: hot-path
    def dispatched(self, prog: Program) -> None:
        """The program's dispatch call returned, on the calling thread."""
        prog.t_dispatched = self._clock()
        self._calls.append((prog.t_enqueue, prog.t_dispatched))

    def calls(self) -> Tuple[Tuple[float, float], ...]:
        """The last dispatch calls as (start, end), any thread's."""
        return tuple(self._calls)

    # graftlint: hot-path
    def ready(self, prog: Program, t: Optional[float] = None) -> None:
        """The program's result is complete; the first stamp stands."""
        if not prog.t_ready:
            prog.t_ready = t if t else self._clock()

    def cancel(self, prog: Program) -> None:
        """Its dispatch raised: nothing was enqueued."""
        prog.cancelled = True

    @property
    def enqueued(self) -> int:
        return self._n

    # -- the drain (the scheduler thread only) ------------------------------

    # graftlint: hot-path
    def drain(self, proved: int = -1) -> List[Program]:
        """The programs at the head of the queue whose completion is
        known, resolved. `proved` is the sequence number of a program
        the caller has SEEN complete (a landed block): the in-order
        queue then proves every earlier program complete as well, and
        one of those that nobody stamped takes the ready of the next
        stamped program (an upper bound: a waiter that has not run
        yet, or a driver with no waiter thread)."""
        out: List[Program] = []
        if not self._open:      # the usual poll: nothing to take a lock for
            return out
        with self._lock:
            while self._open:
                head = self._open[0]
                if not (head.t_ready or head.cancelled
                        or head.seq <= proved):
                    break
                if not head.t_ready and not head.cancelled:
                    nxt = next((p.t_ready for p in self._open
                                if p.t_ready and p.seq <= proved), 0.0)
                    if not nxt:
                        break
                    head.t_ready = nxt
                self._open.popleft()
                if not head.cancelled:
                    out.append(head)
        for prog in out:
            self._resolve(prog)
        return out

    def _resolve(self, prog: Program) -> None:
        prev = self._prev_ready
        prog.t_prev_ready = prev
        prog.t_start = min(max(prog.t_enqueue, prev), prog.t_ready)
        self._prev_ready = max(prev, prog.t_ready)
        recent = self._recent.get((prog.cls, prog.shape))
        if recent is None:
            recent = self._recent[(prog.cls, prog.shape)] = deque(
                maxlen=STALL_MEDIAN_WINDOW)
        ran = prog.ran_ms
        if len(recent) >= STALL_MIN_SAMPLES:
            median = statistics.median(recent)
            if ran > STALL_FACTOR * median:
                prog.stalled = True
                prog.median_ms = median
        recent.append(ran)


# ---------------------------------------------------------------------------
# The host's pauses
# ---------------------------------------------------------------------------


def host_cover(t0: float, t1: float,
               pauses: Iterable[Tuple[float, float, str]]
               ) -> Tuple[float, Dict[str, float]]:
    """How much of [t0, t1] the host's known pauses cover, in ms: the
    length of their UNION (a collection inside a late wake-up counts
    once), and each cause's own union beside it. `pauses` are (start,
    end, cause) on the interval's clock."""
    by_cause: Dict[str, List[Tuple[float, float]]] = {}
    for start, end, cause in pauses:
        lo, hi = max(start, t0), min(end, t1)
        if hi > lo:
            by_cause.setdefault(cause, []).append((lo, hi))

    def union_ms(intervals: List[Tuple[float, float]]) -> float:
        total, cursor = 0.0, t0
        for lo, hi in sorted(intervals):
            lo = max(lo, cursor)
            if hi > lo:
                total += hi - lo
                cursor = hi
        return total * 1e3

    return (union_ms([iv for ivs in by_cause.values() for iv in ivs]),
            {cause: union_ms(ivs) for cause, ivs in by_cause.items()})


class HostPauses:
    """The interpreter's collections, process-wide, on the ledger's
    clock: ONE `gc.callbacks` hook, installed by the first engine that
    starts (`acquire`) and taken out when the last one stops
    (`release`). Every collection adds to two sums (collections seen,
    ms inside them); one of `PAUSE_MIN_MS` or more, or of generation 2,
    also leaves a row (start, end, generation, collected, the thread's
    name) in a bounded hand-off of the ledger's kind: the collecting
    thread appends, the oldest row is overwritten, and each engine's
    scheduler reads with a cursor of its own (`read`), so two engines in
    one process both see a pause that held both.

    The hook takes no lock (a thread that held it could be the one that
    collects), formats nothing and never raises; collections do not
    nest, so it is single-writer by the collector's own rule. While no
    acquired recorder is on it takes no stamp. Around each collection
    it enters and leaves a `TraceAnnotation("host.gc", gen=...)`: in a
    profile the collector then stands on the host's plane under the
    program's own name (a flag test while no profile runs)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 capacity: int = 1024):
        self._clock = clock
        self._capacity = max(2, int(capacity))
        self._ring: List[Optional[tuple]] = [None] * self._capacity
        self._n = 0             # rows ever written
        self.collections = 0    # collections seen while a recorder was on
        self.pause_ms = 0.0     # ... and the ms inside them
        self.errors = 0         # times the hook swallowed an error of its own
        self._t0 = 0.0
        self._annotation = None       # jax's TraceAnnotation, from acquire
        self._open_annotation = None
        # The running engines' recorders, replaced whole (never mutated)
        # so the hook iterates a list nobody changes under it.
        self._recorders: List[Any] = []
        self._lock = threading.Lock()  # acquire / release; never the hook

    # -- the engines' side --------------------------------------------------

    def acquire(self, recorder: Any) -> Tuple[int, int, float]:
        """An engine starts: the hook goes in with the first. Returns
        the engine's cursor: it reads what happens from now on."""
        with self._lock:
            if self._annotation is None:
                import jax  # not at import: the chain server imports this

                self._annotation = jax.profiler.TraceAnnotation
            if not any(r is recorder for r in self._recorders):
                self._recorders = self._recorders + [recorder]
            if self._on_gc not in gc.callbacks:
                # FIRST in the list: the callbacks of others run inside
                # the pause they lengthen. JAX's own (`jax/_src/lib`:
                # `collect_garbage()`, the runtime's `PythonRefManager::
                # CollectGarbage`, on both phases) is then inside ours
                # on `start`, with the backlog it frees; what a later
                # callback does on `stop` comes after our second stamp.
                gc.callbacks.insert(0, self._on_gc)
            return self._n, self.collections, self.pause_ms

    def release(self, recorder: Any) -> None:
        """An engine stops: the hook comes out with the last."""
        with self._lock:
            self._recorders = [r for r in self._recorders
                               if r is not recorder]
            if not self._recorders and self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)

    # graftlint: hot-path
    def read(self, cursor: Tuple[int, int, float]
             ) -> Tuple[List[tuple], Tuple[int, int, float]]:
        """The rows written since `cursor` that the ring still holds,
        oldest first, and the cursor to read on from; the cursor's
        second and third fields are the two sums then."""
        head = self._n
        if head == cursor[0] and self.collections == cursor[1]:
            return [], cursor
        rows = []
        for i in range(max(cursor[0], head - self._capacity), head):
            row = self._ring[i % self._capacity]
            if row is not None and row[0] == i:   # not lapped meanwhile
                rows.append(row[1:])
        return rows, (head, self.collections, self.pause_ms)

    # -- the hook (whichever thread collects) -------------------------------

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        try:
            if phase == "start":
                self._t0 = 0.0
                for rec in self._recorders:
                    if rec.enabled:
                        break
                else:
                    return
                if self._annotation is not None:
                    ann = self._annotation("host.gc",
                                           gen=info["generation"])
                    ann.__enter__()
                    self._open_annotation = ann
                self._t0 = self._clock()
                return
            t0, self._t0 = self._t0, 0.0
            if not t0:
                return
            t1 = self._clock()
            ann, self._open_annotation = self._open_annotation, None
            if ann is not None:
                ann.__exit__(None, None, None)
            self.collections += 1
            self.pause_ms += (t1 - t0) * 1e3
            gen = info["generation"]
            if (t1 - t0) * 1e3 >= PAUSE_MIN_MS or gen >= 2:
                n = self._n
                self._ring[n % self._capacity] = (
                    n, t0, t1, gen, info["collected"],
                    threading.current_thread().name)
                self._n = n + 1
        except Exception:
            # Raised from here it would be printed as unraisable at every
            # collection, from inside the collector: counted, nothing more.
            self.errors += 1


# The process's one: every engine acquires and reads this.
HOST_PAUSES = HostPauses()


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto-loadable)
# ---------------------------------------------------------------------------

# tid layout inside each replica lane: 0 = the device lane (a slice a
# beat, and one a prefill, chunk or encoder program), 1 = scheduler
# instants (gap causes), 16 + slot = request spans (a slot serves one
# request at a time, so spans on one tid never overlap).
TID_BEATS = 0
TID_SCHED = 1
TID_REQ_BASE = 16


def plan_label(decode_k: int, spec_k: int, tree_branches: int,
               rider_width: int, spec_state: bool) -> str:
    """Human label for a StepPlan lattice point (timeline slice names)."""
    if decode_k == 0:
        return f"chunk W={rider_width}"
    parts = [f"decode K={decode_k}"]
    if spec_state:
        parts.append("spec-fallback")
    elif spec_k:
        parts.append(f"spec k={spec_k}"
                     + (f" tree={tree_branches}" if tree_branches > 1
                        else ""))
    if rider_width:
        parts.append(f"rider W={rider_width}")
    return " ".join(parts)


def _beat_events(pid: int, beats: np.ndarray,
                 base: float) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    for b in beats:
        t_d = float(b["t_dispatch"]) - base
        t_r = float(b["t_ready"]) - base
        prev = float(b["t_prev_ready"])
        prev = prev - base if prev else 0.0
        host_gap_ms = max(0.0, (t_d - prev) * 1e3) if prev else 0.0
        # Slice = what the device ran for THIS block: the queue runs
        # in order, so it starts at max(dispatch, the previous
        # PROGRAM's ready) — a prefill or an encoder forward enqueued
        # before the block has its own slice (_program_events) and is
        # not charged here. Lanes stay non-overlapping (Perfetto-
        # clean); device-busy time is the union of beat AND program
        # slices. The raw dispatch stamp rides in args.
        t_vis = max(t_d, prev)
        # Round the ENDPOINTS and subtract (rounding ts and dur
        # independently would let adjacent slices overlap by one
        # rounding ulp and break strict nesting).
        ts_us = round(t_vis * 1e6, 1)
        dur_us = max(0.0, round(round(t_r * 1e6, 1) - ts_us, 1))
        out.append({
            "name": plan_label(int(b["decode_k"]), int(b["spec_k"]),
                               int(b["tree_branches"]),
                               int(b["rider_width"]),
                               bool(b["spec_state"])),
            "cat": "beat", "ph": "X", "pid": pid, "tid": TID_BEATS,
            "ts": ts_us, "dur": dur_us,
            "args": {
                "seq": int(b["seq"]),
                "t_dispatch_us": round(t_d * 1e6, 1),
                "tokens_emitted": int(b["tokens_emitted"]),
                "host_gap_ms": round(host_gap_ms, 3),
                "busy": {"latency": int(b["busy_latency"]),
                         "standard": int(b["busy_standard"]),
                         "batch": int(b["busy_batch"])},
                "waiting": {"latency": int(b["wait_latency"]),
                            "standard": int(b["wait_standard"]),
                            "batch": int(b["wait_batch"])},
                "fused_rider": bool(b["fused_rider"]),
                "qos_paused": bool(b["qos_paused"]),
                "kv_demote_pages": int(b["kv_demote_pages"]),
                "kv_promote_pages": int(b["kv_promote_pages"]),
            },
        })
    return out


def _program_start(ev: Dict[str, Any]) -> float:
    """A `program` event's inferred device start, on the event's clock."""
    return ev["ts"] - ev["b"] / 1e3


def _program_events(pid: int, events: List[Dict[str, Any]],
                    base: float) -> List[Dict[str, Any]]:
    """The prefill, chunk and encoder programs as slices on the device
    lane, start -> ready (a decode block's slice is its beat's)."""
    out: List[Dict[str, Any]] = []
    for ev in events:
        if ev["kind"] != EV_PROGRAM or ev["code"] == PROG_DECODE:
            continue
        cls = PROGRAM_CLASSES[ev["code"]] \
            if 0 <= ev["code"] < len(PROGRAM_CLASSES) else str(ev["code"])
        aux = parse_program_aux(ev["aux"])
        ts_us = round((_program_start(ev) - base) * 1e6, 1)
        end_us = round((ev["ts"] - base) * 1e6, 1)
        out.append({
            "name": f"{cls} {aux.get('shape', '')}".strip(),
            "cat": "program", "ph": "X", "pid": pid, "tid": TID_BEATS,
            "ts": ts_us, "dur": max(0.0, round(end_us - ts_us, 1)),
            "args": {"class": cls, "seq": int(aux.get("seq", -1)),
                     "n": int(aux.get("n", 0)), "rows": ev["slot"],
                     "waited_ms": round(ev["a"], 3)},
        })
    return out


def _host_pause_events(pid: int, events: List[Dict[str, Any]],
                       base: float) -> List[Dict[str, Any]]:
    """The host's pauses as slices on the scheduler lane: a `host_pause`
    event's (named by its cause) and every dispatch call of
    `PAUSE_MIN_MS` or more (`dispatch_call`: its program's enqueue ->
    the call returned). Pauses of different threads can overlap by a
    part; a slice that would is cut to start where the one before it
    ends, so the lane nests and still shows their union."""
    spans: List[Tuple[float, float, str, Dict[str, Any]]] = []
    for ev in events:
        if ev["kind"] == EV_HOST_PAUSE:
            cause = PAUSE_CAUSES[ev["code"]] \
                if 0 <= ev["code"] < len(PAUSE_CAUSES) else str(ev["code"])
            spans.append((ev["ts"] - ev["a"] / 1e3, ev["ts"], cause,
                          {"ms": round(ev["a"], 3), "aux": ev["aux"]}))
        elif ev["kind"] == EV_PROGRAM:
            aux = parse_program_aux(ev["aux"])
            call_ms = float(aux.get("call", 0.0))
            if call_ms >= PAUSE_MIN_MS:
                t_enqueue = ev["ts"] - ev["a"] / 1e3
                spans.append((t_enqueue, t_enqueue + call_ms / 1e3,
                              CAUSE_DISPATCH_CALL,
                              {"ms": round(call_ms, 3),
                               "seq": int(aux.get("seq", -1))}))
    out: List[Dict[str, Any]] = []
    open_ends: List[float] = []   # ends of the slices this one is inside
    heap = [(start, -end, i) for i, (start, end, _, _) in enumerate(spans)]
    heapq.heapify(heap)
    while heap:
        start, neg_end, i = heapq.heappop(heap)
        end = -neg_end
        while open_ends and open_ends[-1] <= start:
            open_ends.pop()
        if open_ends and end > open_ends[-1]:
            # starts inside the slice before it and outlasts it: its
            # turn comes again where that slice ends
            heapq.heappush(heap, (open_ends[-1], neg_end, i))
            continue
        open_ends.append(end)
        ts_us = round((start - base) * 1e6, 1)
        end_us = round((end - base) * 1e6, 1)
        out.append({"name": spans[i][2], "cat": "host-pause", "ph": "X",
                    "pid": pid, "tid": TID_SCHED, "ts": ts_us,
                    "dur": max(0.0, round(end_us - ts_us, 1)),
                    "args": spans[i][3]})
    return out


def _request_events(pid: int, events: List[Dict[str, Any]],
                    base: float) -> List[Dict[str, Any]]:
    from generativeaiexamples_tpu.serving.qos import TIERS

    out: List[Dict[str, Any]] = []
    by_rid: Dict[str, Dict[str, Any]] = {}
    for ev in events:
        kind = ev["kind"]
        if kind in GAP_CAUSE_KINDS or kind == EV_QOS_RESUME \
                or kind in FLEET_INSTANT_KINDS:
            out.append({
                "name": EVENT_NAMES.get(kind, str(kind)),
                # Fleet control events get their own category: the
                # analyzer charges host gaps to "gap-cause" instants
                # only, and a scale decision is context, not a cause.
                "cat": ("fleet" if kind in FLEET_INSTANT_KINDS
                        else "gap-cause"),
                "ph": "i", "s": "t",
                "pid": pid, "tid": TID_SCHED,
                "ts": round((ev["ts"] - base) * 1e6, 1),
                "args": {"rid": ev["rid"], "a": ev["a"], "b": ev["b"],
                         "aux": ev["aux"]},
            })
        rid = ev["rid"]
        if not rid:
            continue
        rec = by_rid.setdefault(rid, {"marks": {}, "slot": -1,
                                      "tier": ev["tier"], "aux": ""})
        rec["marks"].setdefault(kind, ev)
        if kind == EV_ADMIT:
            rec["slot"] = ev["slot"]
        if kind == EV_RETIRE:
            rec["aux"] = ev["aux"]
            rec["marks"][EV_RETIRE] = ev  # latest retire wins
    for rid, rec in by_rid.items():
        marks = rec["marks"]
        t1 = max(ev["ts"] for ev in marks.values())
        tid = TID_REQ_BASE + max(0, rec["slot"])
        retire = marks.get(EV_RETIRE)
        tier = TIERS[rec["tier"]] if rec["tier"] < len(TIERS) else "standard"
        args: Dict[str, Any] = {"rid": rid, "tier": tier,
                                "open": retire is None}
        if retire is not None:
            args["finish_reason"] = RETIRE_NAMES.get(retire["code"],
                                                     str(retire["code"]))
            args["tokens_generated"] = int(retire["a"])
        if rec["aux"]:
            args["trace_id"] = rec["aux"]  # rid <-> trace correlation

        def us(t: float) -> float:
            return round((t - base) * 1e6, 1)

        sub, adm = marks.get(EV_SUBMIT), marks.get(EV_ADMIT)
        # The queued phase is an ASYNC span (ph b/e keyed by rid):
        # queued requests overlap each other — and a request queued
        # while its future slot still served the previous occupant
        # would overlap that occupant's span — so the queue phase
        # cannot live on a synchronous X track without breaking strict
        # nesting. Perfetto renders async pairs on their own rows.
        if sub is not None:
            q_end = adm["ts"] if adm is not None else t1
            out.append({"name": "queue_wait", "cat": "queue", "ph": "b",
                        "id": rid, "pid": pid, "tid": TID_SCHED,
                        "ts": us(sub["ts"]),
                        "args": {"rid": rid, "tier": tier}})
            out.append({"name": "queue_wait", "cat": "queue", "ph": "e",
                        "id": rid, "pid": pid, "tid": TID_SCHED,
                        "ts": us(max(q_end, sub["ts"]))})
        if adm is None:
            continue  # never admitted: queue span + instants only
        # The request's X span starts at ADMIT: slot occupancy is
        # exclusive from admit to retire (the scheduler retires a slot
        # before re-admitting into it), so per-slot tracks nest
        # strictly.
        out.append({"name": f"req {rid}" if rid else "req", "cat": "request",
                    "ph": "X", "pid": pid, "tid": tid,
                    "ts": us(adm["ts"]),
                    "dur": max(0.0, round(us(t1) - us(adm["ts"]), 1)),
                    "args": args})
        first = marks.get(EV_FIRST_TOKEN)
        if first and first["ts"] >= adm["ts"]:
            out.append({"name": "ttft", "cat": "request", "ph": "X",
                        "pid": pid, "tid": tid,
                        "ts": us(adm["ts"]),
                        "dur": round(us(first["ts"]) - us(adm["ts"]), 1),
                        "args": {"rid": rid,
                                 "ttft_ms": round(first["a"], 2)}})
    return out


def chrome_trace(recorders: Dict[str, FlightRecorder]) -> Dict[str, Any]:
    """Render one or more recorders (replica name -> recorder) as a
    Chrome trace-event JSON dict. Perfetto / chrome://tracing load the
    serialized form directly; one process lane per replica."""
    events: List[Dict[str, Any]] = []
    snaps = {name: (rec.snapshot_beats(), rec.snapshot_events())
             for name, rec in recorders.items()}
    # Rebase every timestamp onto the earliest one across all lanes:
    # perf_counter's origin is arbitrary and huge, and microsecond
    # rounding at that magnitude would wobble adjacent slices; local
    # replicas share one clock, so one base aligns the lanes. The min
    # scans EVERY stamp, not just the oldest-by-seq entries — submit
    # events are stamped retroactively with the request's submit
    # time, so under QoS reordering a later-seq event can carry the
    # earliest timestamp (a first-entry base would go negative).
    stamps = [float(b["t_dispatch"]) for bs, _ in snaps.values()
              for b in bs]
    stamps += [_program_start(ev) if ev["kind"] == EV_PROGRAM
               else ev["ts"] - ev["a"] / 1e3 if ev["kind"] == EV_HOST_PAUSE
               else ev["ts"]
               for _, evs in snaps.values() for ev in evs]
    base = min(stamps) if stamps else 0.0
    for pid, name in enumerate(sorted(snaps)):
        beats, evs = snaps[name]
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": f"replica {name}"}})
        for tid, tname in ((TID_BEATS, "device programs"),
                           (TID_SCHED, "scheduler events")):
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        events.extend(_beat_events(pid, beats, base))
        events.extend(_program_events(pid, evs, base))
        events.extend(_host_pause_events(pid, evs, base))
        events.extend(_request_events(pid, evs, base))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def spans_nest(trace: Dict[str, Any]) -> bool:
    """Validate the export invariant: per (pid, tid) lane, synchronous
    X slices are pairwise disjoint or strictly contained (async b/e
    pairs — the queue phase — are exempt by design; they overlap).
    One shared checker for smoke_flight.py and tests — two drifting
    copies of a nesting invariant would enforce different contracts."""
    lanes: Dict[Tuple[int, int], List[Tuple[float, float]]] = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        lanes.setdefault((ev["pid"], ev["tid"]), []).append(
            (ev["ts"], ev["ts"] + ev.get("dur", 0.0)))
    for spans in lanes.values():
        # Parent-first: same start -> widest span sorts first, so a
        # child starting inside a parent must also END inside it.
        spans.sort(key=lambda s: (s[0], -s[1]))
        eps = 0.05  # half the 0.1 us rounding quantum
        for i, (lo_a, hi_a) in enumerate(spans):
            for lo_b, hi_b in spans[i + 1:]:
                if lo_b >= hi_a - eps:
                    break  # disjoint (sorted)
                if hi_b > hi_a + eps:
                    return False  # overlaps without containment
    return True


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

_PROM_SANITIZE = str.maketrans({c: "_" for c in "-.:/ "})


def _prom_name(key: str, prefix: str) -> str:
    name = f"{prefix}_{key}".translate(_PROM_SANITIZE)
    return "".join(ch if ch.isalnum() or ch == "_" else "_" for ch in name)


def _is_hist_snapshot(v: Any) -> bool:
    return isinstance(v, dict) and "buckets" in v and "count" in v


def prometheus_text(snap: Dict[str, Any], prefix: str = "gaie") -> str:
    """Render a metrics snapshot dict as Prometheus text exposition
    (format 0.0.4): scalars become gauges, flat str->number dicts
    become labelled gauges (`{key="..."}`), histogram snapshot dicts
    become native Prometheus histograms (cumulative `_bucket{le=}`,
    `_sum`, `_count`). Deep-nested values (per_replica) are skipped —
    scrape each replica's own /metrics for those."""
    lines: List[str] = []
    for key in sorted(snap):
        v = snap[key]
        name = _prom_name(key[5:] if key.startswith("hist_") else key,
                          prefix)
        if _is_hist_snapshot(v):
            lines.append(f"# TYPE {name} histogram")
            cum = 0
            buckets = v.get("buckets") or {}
            for b in sorted(buckets, key=float):
                cum += int(buckets[b])
                lines.append(f'{name}_bucket{{le="{float(b):g}"}} {cum}')
            cum += int(v.get("overflow") or 0)
            lines.append(f'{name}_bucket{{le="+Inf"}} {cum}')
            lines.append(f"{name}_sum {float(v.get('sum') or 0.0):g}")
            lines.append(f"{name}_count {int(v.get('count') or 0)}")
        elif isinstance(v, bool):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {int(v)}")
        elif isinstance(v, (int, float)):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {v:g}")
        elif isinstance(v, dict):
            flat = {k: x for k, x in v.items()
                    if isinstance(x, (int, float)) and not isinstance(x, bool)}
            if not flat:
                continue  # nested non-numeric (per_replica): skipped
            lines.append(f"# TYPE {name} gauge")
            for k in sorted(flat):
                lines.append(f'{name}{{key="{k}"}} {flat[k]:g}')
        # None / strings / lists: no Prometheus representation
    return "\n".join(lines) + "\n"
