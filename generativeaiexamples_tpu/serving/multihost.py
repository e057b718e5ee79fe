"""Multi-host serving runtime: addressable-shard seams + dispatch replay.

The reference's multi-GPU serving is one env var handed to TRT-LLM/NIM
(INFERENCE_GPU_COUNT, deploy/compose/compose.env:17-18 — NCCL hidden
inside the engine). Multi-HOST is not even that: NIM does not span
machines. Here a jax.distributed process group serves one engine across
hosts, with two contracts this module owns:

1. **Addressable-shard fetches.** Under multi-process JAX, `np.asarray`
   on an array that spans non-addressable (remote-process) devices
   raises deep inside XLA with no hint which engine seam pulled it.
   `fetch_replicated` / `fetch_addressable` are the only sanctioned
   host↔device crossings for WHOLE values: they succeed exactly when
   the fetch is process-local-safe and otherwise raise
   `MultihostFetchError` naming the seam (token readback, page gather,
   prefix seeding, ...) and the fix. `fetch_addressable_slice` /
   `put_local_slice` are the per-host halves — each rank parks and
   restores only its own addressable slice of a sharded value (the KV
   pager's host/disk tiers run on exactly this pair). Single-process
   behavior is byte-identical to `np.asarray`.

2. **Dispatch replay.** Cross-process collectives pair up by program
   LAUNCH ORDER, not by tensor names — every process must enter the
   same jitted computations in the same sequence or the slice deadlocks.
   Rank 0 runs the real scheduler (admission, QoS, radix tree,
   allocator, n-gram draft) and publishes a self-describing
   `(kind, static shapes, host scalars)` record of each device dispatch
   through the coordination-service KV store *before* launching it;
   follower ranks replay the records through the engine's generic
   replay table (`LLMEngine._mh_replay_table`) against their own
   (identically placed) params and pool. The record vocabulary covers
   every scheduler-reachable collective: `prefill` (batch prefill +
   last-token scatter), `plan` (ALL plan_step lattice points — decode
   K, speculative tree verify, fused prefill riders, fused rider
   sampling), `seed` (prefix-cache pool→cache gather), `commit`
   (cache→pool scatter + first-token sample), `pages_out`/`pages_in`/
   `publish_pages` (disagg page export/import), and `pager_out`/
   `pager_in` (KV pager demote/promote). Leader-only state (the radix
   tree, the allocator, QoS, the draft model) is never replicated —
   only its *outputs* (launch order + scalar args, e.g. page-index
   vectors) cross the wire, the invariant GL703 enforces.

Divergence detection: the follower CRC-chains every consumed record
blob; the leader interleaves periodic `digest` records carrying its own
per-record CRCs. A mismatch raises `MultihostDivergenceError` naming
the diverging key and kind — a loud, attributable failure instead of a
silent deadlock inside the next mismatched collective.

The replay profile accepts the full serving feature set (speculation,
step plans, fused prefill + fused sampling, prefix cache, KV pager —
see `MULTIHOST_ACCEPTED` for the per-feature invariant each relies
on); only batch-sharded meshes (data/fsdp > 1) stay rejected, because
sampled-token readbacks would stop being fully replicated (GL702).
"""

from __future__ import annotations

import base64
import io
import logging
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

_LOG = logging.getLogger(__name__)

# KV-store key prefix for dispatch records. The coordination service
# retains set keys for the job's lifetime — a serving session publishes
# O(dispatches) small values; acceptable for the coordinator process,
# revisit with key_value_delete if it ever isn't.
_KEY_PREFIX = "gaiemh"
_BARRIER_TIMEOUT_MS = 600_000

# Leader digest cadence: one digest record per DIGEST_EVERY published
# records (plus one final flush before the stop record), so a diverging
# follower fails within a bounded window instead of deadlocking at an
# arbitrary later collective. The follower window cap only bounds
# memory if a leader somehow never digests.
DIGEST_EVERY = 32
_WINDOW_CAP = 1024


class MultihostError(RuntimeError):
    pass


class MultihostFetchError(MultihostError):
    """A host fetch touched device shards owned by another process."""


class MultihostDivergenceError(MultihostError):
    """The follower's consumed record stream does not CRC-match what
    the leader published — replay has diverged; entering the next
    collective would deadlock the slice."""


def coordination_client():
    """The jax.distributed coordination-service client (KV store +
    barriers). Raises if jax.distributed was never initialized."""
    from jax._src import distributed as _dist

    client = _dist.global_state.client
    if client is None:
        raise MultihostError(
            "jax.distributed is not initialized — engine.multihost needs "
            "mesh.coordinator_address/num_processes/process_id (or the "
            "JAX_COORDINATOR_ADDRESS env) set on every process")
    return client


def barrier(name: str, timeout_ms: int = _BARRIER_TIMEOUT_MS) -> None:
    coordination_client().wait_at_barrier(f"{_KEY_PREFIX}_{name}",
                                          timeout_ms)


# ---------------------------------------------------------------------------
# Addressable-shard fetch seams
# ---------------------------------------------------------------------------


# graftlint: hot-path
def fetch_replicated(arr, seam: str) -> np.ndarray:
    """Host fetch for values every process holds in full (sampled
    tokens, scalar flags): fully-addressable or fully-replicated arrays
    only. The ONLY legal way to read a whole array off a multi-host
    engine — anything else raises here, naming the seam, instead of
    letting XLA fail deep in a transfer guard."""
    if not isinstance(arr, jax.Array):
        return np.asarray(arr)
    if arr.is_fully_addressable or arr.is_fully_replicated:
        return np.asarray(arr)
    raise MultihostFetchError(
        f"seam {seam!r} fetched an array sharded across processes "
        f"(sharding={arr.sharding}); multi-host engines may only read "
        f"fully-replicated outputs here. Keep data/fsdp mesh axes at 1 "
        f"for serving (engine.multihost profile) or route this seam "
        f"through fetch_addressable for a per-host shard gather.")


# graftlint: hot-path
def fetch_addressable(arr, seam: str) -> np.ndarray:
    """Host gather that touches ONLY process-local shards: assembles the
    global value from `addressable_shards` when local shards (plus
    replication) cover every index — the per-host half of a KV-page
    export or pager spill. Raises `MultihostFetchError` naming the seam
    when remote-only shards exist (the caller must then ship per-host
    slices instead of assuming one host sees everything — see
    fetch_addressable_slice)."""
    if not isinstance(arr, jax.Array):
        return np.asarray(arr)
    if arr.is_fully_addressable:
        return np.asarray(arr)
    local = {}
    for sh in arr.addressable_shards:
        local[_index_key(sh.index)] = sh
    idx_map = arr.sharding.devices_indices_map(arr.shape)
    missing = [d for d, idx in idx_map.items()
               if _index_key(idx) not in local]
    if missing:
        raise MultihostFetchError(
            f"seam {seam!r}: {len(missing)} shard(s) of shape {arr.shape} "
            f"live only on remote processes (e.g. {missing[0]}); this host "
            f"cannot assemble the full value. Route the seam through "
            f"fetch_addressable_slice for a per-host slice (the KV pager "
            f"does) instead of assuming one host sees everything.")
    out = np.empty(arr.shape, arr.dtype)
    for sh in arr.addressable_shards:
        out[sh.index] = np.asarray(sh.data)
    return out


# graftlint: hot-path
def fetch_addressable_slice(arr, seam: str) -> Tuple[np.ndarray, Tuple]:
    """Per-host SLICE fetch: assemble only this process's addressable
    shards into one contiguous block and return ``(local, index)``
    where ``index`` is the global-slice tuple the block occupies —
    ``put_local_slice(local, index, ...)`` restores it. The KV pager's
    host/disk tiers park each rank's slice through this pair, so no
    rank ever needs remote bytes. Raises `MultihostFetchError` naming
    the seam when the local shards do not tile one hyperrectangle
    (per-host slice export needs a contiguous local block). On plain
    arrays and single-process shardings the block is the whole array —
    byte-identical to `np.asarray`."""
    if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
        out = np.asarray(arr)
        return out, tuple(slice(0, s) for s in out.shape)
    shards: Dict[Tuple, Any] = {}
    for sh in arr.addressable_shards:
        key = tuple((s.start or 0, dim if s.stop is None else s.stop)
                    for s, dim in zip(sh.index, arr.shape))
        shards[key] = sh  # replicated shards dedupe on the index key
    if not shards:
        raise MultihostFetchError(
            f"seam {seam!r}: array of shape {arr.shape} has no "
            f"addressable shards on this process")
    ndim = len(arr.shape)
    lo = [min(k[d][0] for k in shards) for d in range(ndim)]
    hi = [max(k[d][1] for k in shards) for d in range(ndim)]
    box = tuple(h - l for l, h in zip(lo, hi))
    vol = int(np.prod(box)) if box else 1
    covered = sum(int(np.prod([b - a for a, b in key])) if key else 1
                  for key in shards)
    if covered != vol:
        raise MultihostFetchError(
            f"seam {seam!r}: local shards of shape {arr.shape} do not "
            f"tile a contiguous block (covered {covered} of {vol} "
            f"elements in the bounding box); per-host slice export needs "
            f"a hyperrectangular local slice — keep the sharded axes on "
            f"tensor/sequence")
    out = np.empty(box, arr.dtype)
    for key, sh in shards.items():
        rel = tuple(slice(a - l, b - l) for (a, b), l in zip(key, lo))
        out[rel] = np.asarray(sh.data)
    return out, tuple(slice(l, h) for l, h in zip(lo, hi))


def put_local_slice(local: np.ndarray, index: Tuple, global_shape,
                    sharding) -> jax.Array:
    """Per-host SLICE restore, the inverse of `fetch_addressable_slice`:
    build a global jax.Array of `global_shape` under `sharding` by
    placing, for every addressable device, the sub-block of ``local``
    (which covers the global slice ``index``) that the device's shard
    index asks for. Collective-free — per-device `jax.device_put` plus
    `make_array_from_single_device_arrays`, so every process can call it
    at any point without entering a launch-order slot. Works unchanged
    in single-process mode (the local block IS the global value)."""
    global_shape = tuple(int(s) for s in global_shape)
    base = tuple((s.start or 0) for s in index)
    idx_map = sharding.devices_indices_map(global_shape)
    pid = jax.process_index()
    bufs = []
    for dev, idx in idx_map.items():
        if dev.process_index != pid:
            continue
        rel = []
        for d, s in enumerate(idx):
            start = (s.start or 0) - base[d]
            stop = (global_shape[d] if s.stop is None else s.stop) - base[d]
            if start < 0 or stop > local.shape[d]:
                raise MultihostError(
                    f"put_local_slice: device {dev} wants global "
                    f"[{(s.start or 0)}:{s.stop}] on dim {d} but the "
                    f"local block only covers {index[d]} — the sharding "
                    f"does not match the fetched slice")
            rel.append(slice(start, stop))
        bufs.append(jax.device_put(np.ascontiguousarray(local[tuple(rel)]),
                                   dev))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, bufs)


def _index_key(index) -> Tuple:
    return tuple((s.start, s.stop, s.step) for s in index)


# ---------------------------------------------------------------------------
# Dispatch-record transport
# ---------------------------------------------------------------------------


def _encode(kind: str, payload: Dict[str, Any]) -> str:
    buf = io.BytesIO()
    np.savez(buf, **{k: np.asarray(v) for k, v in payload.items()})
    return kind + ":" + base64.b64encode(buf.getvalue()).decode("ascii")


def _decode(blob: str) -> Tuple[str, Dict[str, np.ndarray]]:
    kind, _, b64 = blob.partition(":")
    raw = base64.b64decode(b64.encode("ascii")) if b64 else b""
    if not raw:
        return kind, {}
    with np.load(io.BytesIO(raw)) as z:
        return kind, {k: z[k] for k in z.files}


class DispatchLog:
    """Ordered dispatch-record stream over the coordination KV store.

    Rank 0 `publish`es; followers `next_record` in the same order. Keys
    are a monotone sequence so both sides agree on position without any
    extra coordination; values are npz-in-base64 (the KV store is
    string-typed).

    Both sides CRC-chain the record blobs (zlib.crc32, chained — the
    running value at record N commits to every byte of records 0..N).
    The leader interleaves a `digest` record every DIGEST_EVERY
    publishes (and right before `stop`) carrying its (seq, kind, crc)
    window; `next_record` consumes digests transparently and raises
    `MultihostDivergenceError` naming the first diverging key+kind on a
    mismatch. Digest records occupy a sequence slot on both sides but
    are excluded from the CRC chain itself."""

    def __init__(self, client=None):
        self._client = client if client is not None else coordination_client()
        self._seq = 0
        self._crc = 0
        self._window: List[Tuple[int, str, int]] = []  # (seq, kind, crc)
        # Optional hook called with the record kind after each publish
        # (incl. digests) — the engine counts replay_records_published
        # through it without this module importing engine metrics.
        self.on_publish = None

    def publish(self, kind: str, **payload) -> None:
        if kind == "stop":
            # The final digest must cover every record before the stop,
            # so a divergence can never hide behind shutdown.
            self._flush_digest()
        blob = _encode(kind, payload)
        self._crc = zlib.crc32(blob.encode("ascii"), self._crc)
        self._window.append((self._seq, kind, self._crc))
        self._client.key_value_set(f"{_KEY_PREFIX}/{self._seq:09d}", blob)
        self._seq += 1
        if self.on_publish is not None:
            self.on_publish(kind)
        if len(self._window) >= DIGEST_EVERY:
            self._flush_digest()

    def _flush_digest(self) -> None:
        if not self._window:
            return
        blob = _encode("digest", {
            "seqs": np.asarray([s for s, _, _ in self._window], np.int64),
            "kinds": np.asarray([k for _, k, _ in self._window]),
            "crcs": np.asarray([c for _, _, c in self._window], np.uint32),
        })
        self._client.key_value_set(f"{_KEY_PREFIX}/{self._seq:09d}", blob)
        self._seq += 1
        self._window = []
        if self.on_publish is not None:
            self.on_publish("digest")

    def next_record(
        self, timeout_s: Optional[float] = None,
        poll_s: float = 60.0,
    ) -> Tuple[str, Dict[str, np.ndarray]]:
        """Blocking read of the next record. `timeout_s=None` waits
        forever (idle serving gaps are unbounded), polling in `poll_s`
        chunks so a dead leader is survivable with a finite timeout.
        Digest records are verified and consumed internally — callers
        only ever see dispatch records (and `stop`)."""
        while True:
            key = f"{_KEY_PREFIX}/{self._seq:09d}"
            waited = 0.0
            while True:
                chunk = poll_s if timeout_s is None else min(
                    poll_s, max(0.001, timeout_s - waited))
                try:
                    blob = self._client.blocking_key_value_get(
                        key, int(chunk * 1000))
                    break
                except Exception as e:  # deadline — keep waiting
                    if "eadline" not in str(e) and "imeout" not in str(e):
                        raise
                    waited += chunk
                    if timeout_s is not None and waited >= timeout_s:
                        raise MultihostError(
                            f"no dispatch record {key} within "
                            f"{timeout_s}s — leader gone?") from e
            seq = self._seq
            self._seq += 1
            kind, payload = _decode(blob)
            if kind == "digest":
                self._verify_digest(payload)
                continue
            self._crc = zlib.crc32(blob.encode("ascii"), self._crc)
            self._window.append((seq, kind, self._crc))
            if len(self._window) > _WINDOW_CAP:
                del self._window[:-_WINDOW_CAP]
            return kind, payload

    def _verify_digest(self, payload: Dict[str, np.ndarray]) -> None:
        have = {s: (k, c) for s, k, c in self._window}
        # Ascending seq order: the FIRST mismatch is the record where
        # the streams actually diverged (the chained CRC poisons every
        # later entry too).
        for s, kind, crc in zip(payload["seqs"], payload["kinds"],
                                payload["crcs"]):
            s, crc, kind = int(s), int(crc), str(kind)
            if s not in have:
                continue
            mine = int(have[s][1])
            if mine != crc:
                raise MultihostDivergenceError(
                    f"replay divergence at record {_KEY_PREFIX}/{s:09d} "
                    f"(kind {kind!r}): follower stream CRC {mine:#010x} "
                    f"!= leader {crc:#010x} — the consumed records do "
                    f"not match what rank 0 published; refusing to enter "
                    f"further collectives")
        verified = {int(s) for s in payload["seqs"]}
        self._window = [w for w in self._window if w[0] not in verified]


# ---------------------------------------------------------------------------
# Profile validation + follower loop
# ---------------------------------------------------------------------------


# Features the replay protocol carries, each with the graftlint check
# (GL70x) guarding the invariant that makes it replayable and the
# mechanism. tests/test_multihost.py pins this table against the
# registered lint catalog (acceptance citations plus the remaining
# rejection citations must cover exactly the GL70x family).
MULTIHOST_ACCEPTED = (
    ("speculative_k", "GL703",
     "draft/verify widths ride the plan record (plan_to_record); "
     "acceptance state is device state, identical on every rank"),
    ("step_plans", "GL703",
     "the chosen StepPlan lattice point crosses the wire in full — "
     "followers never re-derive it from scheduler state"),
    ("fused_prefill", "GL701",
     "rider chunk tokens/width/slot ride the plan record, published "
     "before the fused launch"),
    ("fused_sampling", "GL701",
     "sample_token_into params ride the commit record, published "
     "before the fused sample launch"),
    ("prefix_cache", "GL701",
     "seed/commit records carry the leader's page-index rows; "
     "followers launch the identical gather/scatter without running "
     "the radix tree"),
    ("kv_pager", "GL702",
     "demote parks each rank's addressable shard slice "
     "(fetch_addressable_slice); promote scatters it back "
     "(put_local_slice) — no rank ever fetches remote shards"),
    ("kv_pager", "GL704",
     "pager pressure branches stay leader-only; followers replay the "
     "published pager_out/pager_in stream in launch order"),
)


def validate_multihost_profile(ecfg, mesh=None) -> None:
    """Reject engine configs the replay protocol cannot keep in lockstep,
    each with the reason and the fix — a silently-diverging dispatch
    sequence deadlocks the slice, which is strictly worse.

    Since the generalized record vocabulary (see MULTIHOST_ACCEPTED),
    the full serving feature set is accepted; the only remaining
    rejection is a batch-sharded mesh."""
    bad = []
    if mesh is not None:
        for ax in ("data", "fsdp"):
            if int(mesh.shape.get(ax, 1)) > 1:
                bad.append(
                    f"mesh {ax} axis = {mesh.shape[ax]}: batch-sharded "
                    f"token outputs are not fully replicated, so rank 0 "
                    f"cannot read sampled tokens through the replicated "
                    f"fetch seam (fetch-seam, GL702); keep {ax}=1 and "
                    f"put devices on tensor/sequence")
    if bad:
        raise MultihostError(
            "engine.multihost=true rejects this config:\n  - "
            + "\n  - ".join(bad))


def run_follower(engine, timeout_s: Optional[float] = None) -> None:
    """Follower main loop: replay the leader's dispatch records until a
    stop record arrives, dispatching each through the engine's generic
    replay table (kind -> executor). Blocks the calling thread (run it
    as rank>0's main loop — followers serve no HTTP). A stream
    divergence bumps the engine's replay_divergence counter and
    re-raises — the caller must NOT swallow it and keep serving."""
    log = engine._mh_log
    if log is None:
        raise MultihostError("engine was not built with multihost=true")
    # A replaying engine is by definition not the leader: the record
    # executors publish when `_mh_leader` is set, and a follower that
    # re-published every record it consumed would corrupt the stream
    # (single-process replay tests inject a log into an engine whose
    # default is leader=True).
    engine._mh_leader = False
    table = engine._mh_replay_table()
    n = 0
    while True:
        try:
            kind, payload = log.next_record(timeout_s=timeout_s)
        except MultihostDivergenceError:
            metrics = getattr(engine, "metrics", None)
            if metrics is not None:
                metrics.replay_divergence += 1
            raise
        if kind == "stop":
            _LOG.info("follower: stop record after %d dispatches", n)
            return
        fn = table.get(kind)
        if fn is None:
            raise MultihostError(
                f"unknown dispatch record kind {kind!r} — leader and "
                f"follower builds disagree on the replay vocabulary")
        fn(payload)
        n += 1
