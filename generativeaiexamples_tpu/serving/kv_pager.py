"""Session KV pager: tier prefix-cache pages HBM -> host RAM -> disk.

The radix prefix cache (serving/prefix_cache.py) made multi-turn
sessions cheap to RESUME, but every cached page still pins a device
PagePool page — at 100k+ concurrent sessions the "millions of users"
story (SURVEY.md §2.3) dies at HBM capacity: idle sessions either hog
the pool or get evicted and pay a full cold re-prefill on resume.
This module is the Mooncake/DistServe-shaped answer, the KV twin of
PR 8's tiered ANN index (ops/tiered.py): HBM becomes the HOT tier of
a three-tier demand pager, so a paused conversation costs ~zero HBM
while its warm-resume TTFT stays a page gather, not a prefill.

Tiers (per page, geometry fixed by the engine's pool):

- DEVICE — a live PagePool page (exactly PR-1 residency).
- HOST   — a budgeted host-RAM pool (``engine.kv_host_budget_mb``):
  preallocated page-shaped numpy slabs, codes + narrow scales moved
  VERBATIM for int8 pools so a demote->promote round trip is
  bit-identical to never having left the device.
- DISK   — an mmap'd spill file of fixed-size page records, grown and
  compacted crash-safely (temp + ``os.replace``, the utils/fsio
  idiom): a crash mid-rewrite leaves the previous file — and any live
  mapping of it — intact.

The EXISTING radix tree is the pager's index: each node carries a
tier tag and a tier-local handle (serving/prefix_cache.py `_Node`),
so match() finds a session's prefix regardless of where its bytes
live. Wiring through the existing seams:

- Eviction DEMOTES instead of destroying: `PagedPrefixCache` routes
  `RadixTree.evict`'s frontier pops into a batched device->host
  gather (engine_model.pool_to_pages, ONE dispatch per reclaim), so
  the allocator's reclaim hook — live traffic running short of pages
  — now parks cold sessions instead of deleting their KV.
- Admission PROMOTES on match: the engine's `_lookup_prefix` calls
  `PagedPrefixCache.promote`, which re-seats every non-resident page
  of the matched path with ONE engine_model.pages_to_pool scatter.
- Host -> disk demotion and spill compaction run on a SINGLE-FLIGHT
  background worker (the PR-2..8 trainer idiom: heavy work off the
  scheduler thread, errors logged AND counted, installed under the
  tier lock).

Threading: the tree structure, allocator, and all promote/demote
entry points stay scheduler-thread-owned (the PR-1 discipline). The
tier LOCK covers what the background spill worker shares with the
scheduler: host/spill slot tables, node tier flips, pins, and the
counters. ``engine.kv_pager`` is off by default — off is
byte-identical to the PR-1 cache.
"""

from __future__ import annotations

import logging
import os
import shutil
import tempfile
import threading
import weakref
from collections import OrderedDict
from typing import Callable, List, Optional

import numpy as np

from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.disagg import page_geometry
from generativeaiexamples_tpu.serving.kv_cache import PageAllocator
from generativeaiexamples_tpu.serving.prefix_cache import (
    TIER_DEVICE, TIER_DISK, TIER_HOST, TIER_PENDING, RadixPrefixCache)

_LOG = logging.getLogger(__name__)

# Always-present /metrics keys (EngineMetrics.snapshot() emits zeros
# for every one of these when the pager is off — the PR-5 counter
# convention: dashboards never see keys appear and disappear).
KV_PAGER_KEYS = (
    "kv_demotions", "kv_promotions", "kv_promote_tokens",
    "kv_host_pages", "kv_spill_pages", "kv_host_bytes", "kv_spill_bytes",
    "kv_spill_writes", "kv_spill_compactions", "kv_forced_drops",
    "kv_pager_errors",
)

# Spill file sizing: first growth allocates this many records, later
# growths double; compaction triggers once more than half the slots of
# a >=64-slot file are dead (freed by promotions).
SPILL_MIN_SLOTS = 64


def _pow2(n: int) -> int:
    w = 1
    while w < n:
        w *= 2
    return w


def gather_spans(n: int, max_batch_pages: int):
    """Yield (lo, hi) spans covering ``range(n)`` pages so each batched
    pool_to_pages gather/scatter stays at most the power-of-two-rounded
    ``max_batch_pages`` wide (0 = one unbounded span). The shared
    chunking idiom for demotion (PR 11) and the disagg export gather
    (PR 17): every live width is one of the power-of-two variants
    warmup() precompiled, and no single dispatch holds the scheduler's
    control-op slot for a monolithic whole-prefix gather."""
    if n <= 0:
        return
    maxw = _pow2(max(1, n))
    if max_batch_pages:
        maxw = min(maxw, _pow2(max_batch_pages))
    for lo in range(0, n, maxw):
        yield lo, min(n, lo + maxw)


class KVPager:
    """Three-tier page store + the background spill/compaction worker.

    Owns NO tree structure: `PagedPrefixCache` drives it with node
    objects whose ``tier``/``handle``/``page`` fields this class flips
    under the tier lock (the only state the background worker shares
    with the scheduler thread).
    """

    def __init__(self, pool, *, host_budget_mb: int = 256,
                 spill_dir: str = "", put: Optional[Callable] = None,
                 max_batch_pages: int = 0):
        # One page as pool_to_pages hands it over: codes [2, L, KH, ps,
        # Hd] ([0]=k, [1]=v) in the pool dtype (int8 codes for quantized
        # pools, which also carry [2, L, KH, ps] f32 narrow scales).
        (self.codes_shape, self.codes_dtype,
         self.scales_shape) = page_geometry(pool)
        self.page_size = pool.page_size
        self.quantized = bool(pool.quantized)
        self._codes_bytes = int(np.prod(self.codes_shape)
                                * self.codes_dtype.itemsize)
        self._scales_bytes = (int(np.prod(self.scales_shape) * 4)
                              if self.scales_shape else 0)
        self._rec_bytes = self._codes_bytes + self._scales_bytes
        import jax.numpy as jnp
        self._put = put if put is not None else jnp.asarray
        # Largest gather/scatter batch per dispatch (0 = unbounded):
        # the engine passes max_pages so every live width is one of
        # the power-of-two variants warmup() precompiled.
        self.max_batch_pages = max(0, int(max_batch_pages))
        # Multihost dispatch log (engine wires it on the LEADER only):
        # demote/promote publish pager_out/pager_in records BEFORE
        # their gather/scatter launches so follower ranks enter the
        # same collectives in the same order (replaying from their own
        # per-host cold store — serving/multihost.py).
        self.mh_log = None
        # Monotone id stamped on each demoted node (node.cold_key):
        # the wire name followers key their cold store by — slot
        # numbers are leader-local allocator state and never published.
        self._next_cold_key = 0
        # Per-host shard-slice mode, armed at the FIRST demote when
        # the pool gather's addressable shards cover only a slice of
        # the page (cross-process tensor sharding): host/disk tiers
        # then hold THIS RANK's slice and promote reassembles the
        # global array collective-free (put_local_slice). None until
        # then; single-process pools never arm it.
        self._kv_sharding = None
        self._local_index: Optional[tuple] = None
        self._scales_sharding = None
        self._scales_index: Optional[tuple] = None
        self._global_codes_shape = self.codes_shape
        self._global_scales_shape = self.scales_shape
        # Host tier: fixed slabs sized from the budget. The budget is
        # PER-HOST: in shard-slice mode each rank only parks its own
        # slice, so the first demote resizes the slabs for the smaller
        # record (see _arm_slice_mode).
        self._host_budget_mb = int(host_budget_mb)
        n_host = max(0, int(host_budget_mb) * (1 << 20) // self._rec_bytes)
        self.n_host_slots = n_host
        self._host_codes = np.zeros((n_host,) + self.codes_shape,
                                    self.codes_dtype)
        self._host_scales = (np.zeros((n_host,) + self.scales_shape,
                                      np.float32)
                             if self.scales_shape else None)
        # Tier lock: host/spill slot tables, node tier flips, pins,
        # counters — everything the background spill worker shares
        # with the scheduler thread.
        self._lock = threading.Lock()
        self._host_free: List[int] = list(range(n_host - 1, -1, -1))
        # slot -> node in demotion order: the spill worker's LRU (a
        # promoted slot leaves the dict; re-demotion re-enters at the
        # end).
        self._host_lru: "OrderedDict[int, object]" = OrderedDict()
        # Cold tier: one file per pager instance (unique name — two
        # engines may share kv_spill_dir), records appended into free
        # slots of the current mapping, grown/compacted by crash-safe
        # rewrite.
        self._ephemeral = not spill_dir
        self._spill_dir = spill_dir or tempfile.mkdtemp(prefix="kv_pager_")
        os.makedirs(self._spill_dir, exist_ok=True)
        self._spill_path = os.path.join(
            self._spill_dir, f"kv_pages.{os.getpid()}.{id(self):x}.bin")
        self._spill_mm: Optional[np.memmap] = None
        self._spill_slots = 0
        self._spill_free: List[int] = []
        self._spill_nodes: dict = {}  # slot -> node
        # Records freed by promotion/reattach since the last compaction
        # (free-but-never-used growth slots are NOT dead — only dead
        # records justify a rewrite).
        self._spill_dead = 0
        self._pins: set = set()       # id(node) immune to demote/spill
        self._compacting = False      # a rewrite is copying the old mmap
        self._busy = False            # single-flight worker gate
        # Counters (stats() is the one surface; EngineMetrics pulls it).
        self._demotions = 0
        self._promotions = 0
        self._promote_tokens = 0
        self._spill_writes = 0
        self._compactions = 0
        self._forced_drops = 0
        self._bg_errors = 0
        if self._ephemeral:
            weakref.finalize(self, shutil.rmtree, self._spill_dir,
                             ignore_errors=True)

    # -- pins (scheduler pins a matched path for the promote window) -------

    def pin(self, nodes) -> None:
        with self._lock:
            self._pins.update(id(n) for n in nodes)

    def unpin(self, nodes) -> None:
        with self._lock:
            self._pins.difference_update(id(n) for n in nodes)

    def is_pinned(self, node) -> bool:
        with self._lock:
            return id(node) in self._pins

    # -- demotion (scheduler thread, called from PagedPrefixCache) ---------

    # graftlint: hot-path
    def demote(self, pool, nodes) -> List:
        """Move `nodes`' pages device -> host (or straight to disk
        when the host pool is full): ONE batched pool_to_pages gather
        per chunk, then slot writes + tier flips under the lock. The
        host fetch BLOCKS until the gather lands — that is the
        demotion barrier: the caller releases the device pages to the
        allocator only after the bytes are safe. Returns the nodes
        that could NOT be stored (forced drops — host full while a
        compaction rewrite holds the spill); the caller destroys
        those, exactly the PR-1 eviction."""
        dropped: List = []
        for lo, hi in gather_spans(len(nodes), self.max_batch_pages):
            batch = nodes[lo:hi]
            w = _pow2(len(batch))
            row = np.zeros((w,), np.int32)  # padding -> sink page 0
            row[:len(batch)] = [n.page for n in batch]
            # Wire names + publish BEFORE the gather launch (GL701):
            # followers replay the identical pool_to_pages program from
            # the record alone — `row` is the leader allocator's
            # page-index decision, `keys` name each parked page so a
            # later pager_in can reference it without leaking
            # leader-local slot numbers. Forced drops are published
            # too (the launch already happened); followers leak those
            # entries until shutdown — bounded by the drop counter.
            for node in batch:
                node.cold_key = self._next_cold_key
                self._next_cold_key += 1
            log = self.mh_log
            if log is not None:
                log.publish(
                    "pager_out", row=row, n=np.int32(len(batch)),
                    keys=np.asarray([n.cold_key for n in batch],
                                    np.int64))
            codes, scales = engine_model.pool_to_pages(pool, self._put(row))
            # Blocking device->host fetch BY DESIGN: the demotion
            # barrier (pages are recycled the moment this returns).
            # Routed through the multihost seam helper: pool pages are
            # tensor-sharded, so under a cross-process mesh each rank
            # fetches only its ADDRESSABLE SLICE of the page and the
            # host/disk tiers go per-host (slice mode, armed below).
            from generativeaiexamples_tpu.serving.multihost import (
                fetch_addressable_slice)

            fetched, f_idx = fetch_addressable_slice(
                codes, "kv-pager demote gather")
            fetched_s, fs_idx = (fetch_addressable_slice(
                scales, "kv-pager demote gather (scales)")
                if scales is not None else (None, None))
            if (self._kv_sharding is None
                    and fetched.shape[1:] != tuple(self._global_codes_shape)):
                self._arm_slice_mode(codes, f_idx, scales, fs_idx,
                                     fetched, fetched_s)
            with self._lock:
                stored = 0
                for i, node in enumerate(batch):
                    if self._store_locked(node, fetched[i],
                                          None if fetched_s is None
                                          else fetched_s[i]):
                        stored += 1
                    else:
                        dropped.append(node)
                self._demotions += stored
        self._maybe_kick()
        return dropped

    def _store_locked(self, node, codes: np.ndarray,
                      scales: Optional[np.ndarray]) -> bool:
        """Lock held. Park one page's bytes in the warmest tier with
        room: host slot, else a direct (synchronous) spill record.
        Returns False only when neither can take it (compaction holds
        the spill file)."""
        if self._host_free:
            slot = self._host_free.pop()
            self._host_codes[slot] = codes
            if self._host_scales is not None:
                self._host_scales[slot] = scales
            node.tier, node.handle = TIER_HOST, slot
            self._host_lru[slot] = node
            return True
        if self._compacting:
            self._forced_drops += 1
            return False
        slot = self._spill_alloc_locked()
        self._spill_write_locked(slot, codes, scales)
        node.tier, node.handle = TIER_DISK, slot
        self._spill_nodes[slot] = node
        return True

    def _arm_slice_mode(self, codes, f_idx, scales, fs_idx,
                        fetched: np.ndarray,
                        fetched_s: Optional[np.ndarray]) -> None:
        """First demote under a cross-process mesh: this rank's
        addressable shards cover only a slice of each page. Rebase the
        pager's record geometry on the LOCAL slice (host/disk tiers
        are per-host from here on) and remember the gather output's
        sharding + this rank's index so promote can reassemble the
        global array collective-free via put_local_slice. Runs before
        any _store_locked, so both tiers are empty — the slabs can be
        reallocated for the smaller record and the spill file (created
        lazily) has never been written."""
        # Batch dim 0 of the gather output is replicated; the per-page
        # local index is the fetch index minus that dim.
        self._kv_sharding = codes.sharding
        self._local_index = tuple(f_idx[1:])
        if scales is not None:
            self._scales_sharding = scales.sharding
            self._scales_index = tuple(fs_idx[1:])
        with self._lock:
            assert not self._host_lru and not self._spill_nodes, (
                "slice mode armed after pages were parked")
            self.codes_shape = tuple(fetched.shape[1:])
            if fetched_s is not None:
                self.scales_shape = tuple(fetched_s.shape[1:])
            self._codes_bytes = int(np.prod(self.codes_shape)
                                    * self.codes_dtype.itemsize)
            self._scales_bytes = (int(np.prod(self.scales_shape) * 4)
                                  if self.scales_shape else 0)
            self._rec_bytes = self._codes_bytes + self._scales_bytes
            n_host = max(0, self._host_budget_mb * (1 << 20)
                         // self._rec_bytes)
            self.n_host_slots = n_host
            self._host_codes = np.zeros((n_host,) + self.codes_shape,
                                        self.codes_dtype)
            self._host_scales = (np.zeros((n_host,) + self.scales_shape,
                                          np.float32)
                                 if self.scales_shape else None)
            self._host_free = list(range(n_host - 1, -1, -1))

    # -- promotion (scheduler thread, called from PagedPrefixCache) --------

    # graftlint: hot-path
    def promote_into(self, pool, nodes, pages: List[int]):
        """Re-seat `nodes`' bytes into freshly-allocated pool `pages`:
        staging copy under the lock (host slabs / spill mmap -> one
        page-major buffer), then ONE pages_to_pool scatter. Tier flips
        and slot frees happen only after the scatter dispatches, so a
        failure leaves every node still resident in its cold tier (the
        caller releases the pages). Returns the new pool."""
        n = len(nodes)
        w = _pow2(n)
        codes = np.zeros((w,) + self.codes_shape, self.codes_dtype)
        scales = (np.zeros((w,) + self.scales_shape, np.float32)
                  if self.scales_shape else None)
        row = np.zeros((w,), np.int32)
        row[:n] = pages
        with self._lock:
            for i, node in enumerate(nodes):
                if node.tier == TIER_HOST:
                    codes[i] = self._host_codes[node.handle]
                    if scales is not None:
                        scales[i] = self._host_scales[node.handle]
                elif node.tier == TIER_DISK:
                    self._spill_read_locked(node.handle, codes[i],
                                            None if scales is None
                                            else scales[i])
                else:
                    raise RuntimeError(
                        f"promote of a tier-{node.tier} node")
        # Publish BEFORE the scatter launch (GL701): `keys` reference
        # the pager_out records whose bytes each follower parked in
        # its own per-host cold store.
        log = self.mh_log
        if log is not None:
            log.publish(
                "pager_in", row=row, n=np.int32(n),
                keys=np.asarray([node.cold_key for node in nodes],
                                np.int64))
        if self._kv_sharding is not None:
            from generativeaiexamples_tpu.serving.multihost import (
                put_local_slice)

            buf = put_local_slice(
                codes, (slice(0, w),) + self._local_index,
                (w,) + tuple(self._global_codes_shape), self._kv_sharding)
            sbuf = None
            if scales is not None:
                sbuf = put_local_slice(
                    scales, (slice(0, w),) + self._scales_index,
                    (w,) + tuple(self._global_scales_shape),
                    self._scales_sharding)
            pool = engine_model.pages_to_pool(pool, buf, sbuf,
                                              self._put(row))
        else:
            pool = engine_model.pages_to_pool(
                pool, self._put(codes),
                None if scales is None else self._put(scales),
                self._put(row))
        with self._lock:
            for node, page in zip(nodes, pages):
                self._free_cold_locked(node)
                node.tier, node.page, node.handle = TIER_DEVICE, page, None
            self._promotions += n
            self._promote_tokens += n * self.page_size
        # A promote-heavy phase (many parked sessions resuming) frees
        # spill slots without any demotion to kick the worker — check
        # here too or the dead records linger at high-water size.
        self._maybe_kick()
        return pool

    def read_pages(self, nodes, codes_out: np.ndarray,
                   scales_out: Optional[np.ndarray]) -> None:
        """Copy cold nodes' bytes into caller buffers WITHOUT
        promoting (the disagg export path, serving/disagg.py: a
        prefill-role replica ships a demoted tail to a decode replica
        straight from its cold tier — no device scatter, no pool
        pressure). `codes_out[i]` / `scales_out[i]` receive node i's
        page; every node must be TIER_HOST or TIER_DISK."""
        if self._kv_sharding is not None:
            raise RuntimeError(
                "read_pages under per-host slice mode: each rank's cold "
                "tier holds only its addressable shard slice, which "
                "cannot serve a disagg export of full pages")
        with self._lock:
            for i, node in enumerate(nodes):
                if node.tier == TIER_HOST:
                    codes_out[i] = self._host_codes[node.handle]
                    if scales_out is not None:
                        scales_out[i] = self._host_scales[node.handle]
                elif node.tier == TIER_DISK:
                    self._spill_read_locked(node.handle, codes_out[i],
                                            None if scales_out is None
                                            else scales_out[i])
                else:
                    raise RuntimeError(
                        f"read_pages of a tier-{node.tier} node")

    def reattach(self, node, page: int) -> bool:
        """A re-played prompt re-inserted a chunk whose node had been
        demoted: adopt its fresh device `page` as the node's payload
        and free the cold copy — residency for free, no promotion
        dispatch. Returns False when the node is not in a cold tier
        (already device/pending — nothing to do)."""
        with self._lock:
            if node.tier not in (TIER_HOST, TIER_DISK):
                return False
            self._free_cold_locked(node)
            node.tier, node.page, node.handle = TIER_DEVICE, page, None
        self._maybe_kick()
        return True

    def discard(self, node) -> None:
        """Free a node's cold-tier storage (node destroyed or its
        demotion failed); device/pending nodes are a no-op."""
        with self._lock:
            self._free_cold_locked(node)
            node.handle = None

    def _free_cold_locked(self, node) -> None:
        """Lock held. Release a cold node's slot: host slab back to
        the free list, or spill record marked dead (the compaction
        trigger counts dead records, never unused growth slots)."""
        if node.tier == TIER_HOST:
            self._host_lru.pop(node.handle, None)
            self._host_free.append(node.handle)
        elif node.tier == TIER_DISK:
            self._spill_nodes.pop(node.handle, None)
            self._spill_free.append(node.handle)
            self._spill_dead += 1

    def count_error(self) -> None:
        with self._lock:
            self._bg_errors += 1

    # -- spill file (cold tier) --------------------------------------------

    def _spill_alloc_locked(self) -> int:
        """Lock held. A free spill slot, growing the file (crash-safe
        rewrite) when none remain."""
        if not self._spill_free:
            self._spill_grow_locked(max(SPILL_MIN_SLOTS,
                                        self._spill_slots * 2))
        return self._spill_free.pop()

    def _spill_write_locked(self, slot: int, codes: np.ndarray,
                            scales: Optional[np.ndarray]) -> None:
        """Lock held."""
        rec = self._spill_mm[slot]
        cb = self._codes_bytes
        rec[:cb] = codes.reshape(-1).view(np.uint8)
        if scales is not None:
            rec[cb:] = scales.reshape(-1).view(np.uint8)
        self._spill_writes += 1

    def _spill_read_locked(self, slot: int, codes_out: np.ndarray,
                           scales_out: Optional[np.ndarray]) -> None:
        """Lock held."""
        rec = self._spill_mm[slot]
        cb = self._codes_bytes
        codes_out[...] = rec[:cb].view(self.codes_dtype) \
            .reshape(self.codes_shape)
        if scales_out is not None:
            scales_out[...] = rec[cb:].view(np.float32) \
                .reshape(self.scales_shape)

    def _spill_grow_locked(self, new_slots: int) -> None:
        """Lock held. Extend the spill file IN PLACE: growth only
        appends fresh slots, so old records are never touched and an
        O(new size) sparse truncate is crash-safe by construction (a
        crash leaves a longer file whose extra slots are simply
        unused — the slot table is in-memory state). Reachable
        synchronously on the scheduler thread (direct-spill fallback),
        so it must NOT copy the whole file under the tier lock; the
        full temp + os.replace rewrite is reserved for compaction,
        which actually moves live records and runs on the
        single-flight worker."""
        if self._spill_mm is not None:
            self._spill_mm.flush()
            self._spill_mm = None
        if not os.path.exists(self._spill_path):
            with open(self._spill_path, "wb"):
                pass
        os.truncate(self._spill_path, new_slots * self._rec_bytes)
        self._spill_mm = np.memmap(self._spill_path, np.uint8, "r+",
                                   shape=(new_slots, self._rec_bytes))
        self._spill_free.extend(range(new_slots - 1,
                                      self._spill_slots - 1, -1))
        self._spill_slots = new_slots

    # -- background spill / compaction (single-flight) ---------------------

    def _host_high_water(self) -> int:
        return self.n_host_slots - max(1, self.n_host_slots // 8)

    def maintenance_due(self) -> bool:  # graftlint: ignore[GL202]
        """Cheap, lock-free peek (racy int/len reads are fine — worst
        case one extra no-op kick, and kick re-checks single-flight
        under the lock; the lock-free reads are the point, hence the
        GL202 suppression): the host tier is near its budget, or the
        spill file is mostly dead records."""
        if self._busy:
            return False
        if self.n_host_slots and (self.n_host_slots
                                  - len(self._host_free)
                                  > self._host_high_water()):
            return True
        return self._compact_due()

    def _compact_due(self) -> bool:  # graftlint: ignore[GL202]
        # Dead RECORDS (freed by promotion), not never-used growth
        # slots, justify a rewrite — and only once they outweigh the
        # live set. Callable as a lock-free peek (maintenance_due) —
        # racy int/len reads cost at most one no-op kick, and
        # _run_maintenance re-checks under the lock before acting;
        # hence the GL202 suppression, same rationale as
        # maintenance_due.
        return (self._spill_dead >= SPILL_MIN_SLOTS // 2
                and self._spill_dead > len(self._spill_nodes))

    def _maybe_kick(self) -> None:
        if self.maintenance_due():
            self.kick_maintenance()

    def kick_maintenance(self) -> bool:
        """Run one maintenance pass (host->disk spill + compaction) on
        a background thread, single-flight — the tiered-ANN trainer
        idiom. Returns True when a worker was started."""
        with self._lock:
            if self._busy:
                return False
            self._busy = True

        def run():
            try:
                self._run_maintenance()
            except Exception:
                # No caller to propagate to; a silent crash would
                # freeze the cold tiers with no signal. Log + count;
                # the next demotion re-kicks.
                _LOG.exception("kv-pager maintenance failed")
                with self._lock:
                    self._bg_errors += 1
            finally:
                with self._lock:
                    self._busy = False

        threading.Thread(target=run, name="kv-pager-maintenance",
                         daemon=True).start()
        return True

    def wait_maintenance(self, timeout: float = 10.0) -> bool:
        """Block until the single-flight worker is idle (tests and
        engine shutdown drain before teardown)."""
        import time
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if not self._busy:
                    return True
            time.sleep(0.005)
        return False

    def _run_maintenance(self) -> None:
        """One pass: spill host-LRU pages down to the low-water mark
        (one page per lock acquisition, so the scheduler's
        demote/promote interleave), then compact the spill file if
        mostly dead. Tests call this directly; kick_maintenance runs
        it on the single-flight worker."""
        low_water = self.n_host_slots - max(1, self.n_host_slots // 4)
        while True:
            with self._lock:
                used = self.n_host_slots - len(self._host_free)
                if used <= max(0, low_water) or not self._host_lru:
                    break
                victim = None
                for slot, node in self._host_lru.items():
                    if id(node) not in self._pins:
                        victim = (slot, node)
                        break
                if victim is None:
                    break  # everything left is pinned mid-promotion
                slot, node = victim
                spill_slot = self._spill_alloc_locked()
                scales_src = (self._host_scales[slot]
                              if self._host_scales is not None else None)
                self._spill_write_locked(spill_slot,
                                         self._host_codes[slot],
                                         scales_src)
                node.tier, node.handle = TIER_DISK, spill_slot
                self._spill_nodes[spill_slot] = node
                self._host_lru.pop(slot)
                self._host_free.append(slot)
        with self._lock:
            compact = self._compact_due()
        if compact:
            self._compact()

    def _compact(self) -> None:
        """Rewrite the spill with live records only (promotions leave
        dead slots behind). Snapshot under the lock, copy the OLD
        mapping off-lock (new spill writes are refused while
        `_compacting` — the demote fallback force-drops instead, and
        the worker itself is the only other spill writer), install the
        new mapping + remapped handles under the lock. Crash-safe:
        temp + os.replace, old file intact mid-rewrite."""
        with self._lock:
            snap = list(self._spill_nodes.items())  # [(slot, node)]
            old_mm = self._spill_mm
            self._compacting = True
        try:
            new_slots = max(SPILL_MIN_SLOTS, _pow2(2 * max(1, len(snap))))
            tmp = f"{self._spill_path}.tmp"
            try:
                mm = np.memmap(tmp, np.uint8, "w+",
                               shape=(new_slots, self._rec_bytes))
                for j, (slot, _) in enumerate(snap):
                    mm[j] = old_mm[slot]
                mm.flush()
                del mm
                reader = np.memmap(tmp, np.uint8, "r+",
                                   shape=(new_slots, self._rec_bytes))
                os.replace(tmp, self._spill_path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            with self._lock:
                nodes = {}
                for j, (slot, node) in enumerate(snap):
                    if node.tier == TIER_DISK and node.handle == slot:
                        node.handle = j
                        nodes[j] = node
                    # else: promoted/reattached mid-compaction — its
                    # copied record is dead in the new file.
                self._spill_mm = reader
                self._spill_slots = new_slots
                self._spill_nodes = nodes
                self._spill_free = [s for s in range(new_slots - 1, -1, -1)
                                    if s not in nodes]
                self._spill_dead = 0
                self._compactions += 1
        finally:
            with self._lock:
                self._compacting = False

    # -- surfaces ----------------------------------------------------------

    def stats(self) -> dict:
        """The always-present counter/gauge set (KV_PAGER_KEYS):
        EngineMetrics.snapshot(), /metrics and /health all read this
        one surface."""
        with self._lock:
            host_pages = self.n_host_slots - len(self._host_free)
            spill_pages = len(self._spill_nodes)
            return {
                "kv_demotions": self._demotions,
                "kv_promotions": self._promotions,
                "kv_promote_tokens": self._promote_tokens,
                "kv_host_pages": host_pages,
                "kv_spill_pages": spill_pages,
                "kv_host_bytes": host_pages * self._rec_bytes,
                "kv_spill_bytes": spill_pages * self._rec_bytes,
                "kv_spill_writes": self._spill_writes,
                "kv_spill_compactions": self._compactions,
                "kv_forced_drops": self._forced_drops,
                "kv_pager_errors": self._bg_errors,
            }

    def close(self) -> None:
        """Drain the worker and drop the spill mapping; ephemeral
        spill dirs are removed (the finalizer also covers GC)."""
        self.wait_maintenance()
        with self._lock:
            self._spill_mm = None
            self._spill_nodes = {}
            self._spill_free = []
            self._spill_slots = 0
        if self._ephemeral:
            shutil.rmtree(self._spill_dir, ignore_errors=True)


class PagedPrefixCache(RadixPrefixCache):
    """Radix prefix cache whose eviction DEMOTES through the KV pager
    instead of destroying: the tree stays the index for every tier,
    `evict()` frees device pages by parking their bytes host-side
    (batched — selection runs on the lazy LRU heap over the device
    FRONTIER, then one gather moves the whole set), and `promote()`
    re-seats a matched path's non-resident pages with one scatter.
    Scheduler-thread-owned like its base; cross-thread state lives in
    the pager behind the tier lock."""

    def __init__(self, allocator: PageAllocator, page_size: int,
                 capacity_pages: int, pager: KVPager,
                 pool_ref: Callable):
        super().__init__(allocator, page_size, capacity_pages)
        self.pager = pager
        # The engine's pool is REPLACED by every donated step; demotion
        # gathers from whatever is current at flush time.
        self._pool_ref = pool_ref
        self._pending_demote: List = []

    # -- eviction = demotion -----------------------------------------------

    def _frontier(self, node) -> bool:
        # Demote only device nodes with no device children: the
        # resident set stays closed under ancestors, so a matched path
        # is always [device...][cold...] and promotion is contiguous.
        return node.tier == TIER_DEVICE and node.dev_children == 0

    def _evictable(self, node) -> bool:
        return (node.tier == TIER_DEVICE
                and self.allocator.refcount(node.page) == 1
                and not self.pager.is_pinned(node))

    def _evict_node(self, node) -> None:
        # No shadow "evict" report: the prefix is still servable (the
        # router should keep scoring it); only a forced drop reports.
        node.tier = TIER_PENDING
        parent = node.parent
        parent.dev_children -= 1
        self._n_pages -= 1
        self._pending_demote.append(node)
        if parent is not self.root and self._frontier(parent):
            self._heap_push(parent)

    def evict(self, n_pages: int) -> int:
        freed = super().evict(n_pages)
        self._flush_demotions()
        return freed

    def _flush_demotions(self) -> None:
        """Move every selected page's bytes off-device (ONE batched
        gather), then hand the device pages back to the allocator —
        the caller is usually the allocator's own reclaim hook, so the
        free list must have grown by the time evict() returns."""
        nodes, self._pending_demote = self._pending_demote, []
        if not nodes:
            return
        try:
            dropped = self.pager.demote(self._pool_ref(), nodes)
        except Exception:
            # Demotion failed wholesale (gather/fetch error): fall
            # back to PR-1 destruction so the allocator still gets its
            # pages — losing cold KV beats failing live admissions.
            _LOG.exception("kv-pager demotion failed; dropping %d pages",
                           len(nodes))
            self.pager.count_error()
            dropped = nodes
        for node in dropped:
            self._destroy_pending(node)
        self.allocator.release([n.page for n in nodes])

    def _destroy_pending(self, node) -> None:
        """A selected node whose bytes could not be stored: remove it
        from the tree (its cold descendants become unreachable and
        free their storage too — a broken chain must never match)."""
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            # Descendants of a frontier node are never device-resident
            # (the set is ancestor-closed): cold storage is all they
            # hold. The root of the destroyed subtree may itself hold
            # a slot when a wholesale demote failure lands here AFTER
            # an earlier chunk of the same flush stored it; discard
            # no-ops on pending/device nodes.
            self.pager.discard(n)
            n.children = {}
        if self._reporting():
            self._report("evict", self._path_ids(node))
        del node.parent.children[node.key]
        node.parent = None

    # -- promotion ---------------------------------------------------------

    # graftlint: hot-path
    def promote(self, pool, path_nodes):
        """Make every node of a matched path device-resident: allocate
        pool pages for the cold suffix (the alloc may reclaim-demote
        OTHER cold sessions — the path is pinned so it cannot demote
        itself), then one pages_to_pool scatter. Raises MemoryError
        when the allocator cannot cover the cold pages even after
        reclaim; the caller falls back to the resident prefix."""
        nonres = [n for n in path_nodes if n.tier != TIER_DEVICE]
        if not nonres:
            return pool
        self.pager.pin(path_nodes)
        try:
            pages = self.allocator.alloc(len(nonres))
            try:
                pool = self.pager.promote_into(pool, nonres, pages)
            except BaseException:
                self.allocator.release(pages)
                raise
        finally:
            self.pager.unpin(path_nodes)
        for node in nonres:
            node.parent.dev_children += 1
            self._n_pages += 1
            self._heap_push(node)
        return pool

    # -- overrides keeping PR-1 semantics tier-aware -----------------------

    def _on_existing(self, node, payload) -> None:
        # Re-played prompt over a demoted chunk: adopt the fresh
        # device page in place (free residency — no promote dispatch).
        if payload is None:
            return
        if self.pager.reattach(node, payload):
            self._adopt(payload)
            node.parent.dev_children += 1
            self._n_pages += 1
            self._heap_push(node)

    def match(self, ids) -> List[int]:
        """Device-RESIDENT page ids of the longest cached prefix (the
        leading device run — cold nodes have no valid pool page). The
        engine's pager path uses match_nodes + promote instead."""
        pages = []
        for n in self.match_nodes(ids):
            if n.tier != TIER_DEVICE:
                break
            pages.append(n.page)
        return pages

    def reclaimable(self) -> int:
        """Device pages evict() could DEMOTE right now: pendant
        device-subtrees in which every device node's page is
        referenced only by the tree (cold children never block — they
        hold no device pages)."""
        count = 0

        def visit(node) -> bool:
            nonlocal count
            oks = [visit(c) for c in list(node.children.values())
                   if c.tier == TIER_DEVICE]
            if node is self.root:
                return False
            if all(oks) and self.allocator.refcount(node.page) == 1 \
                    and not self.pager.is_pinned(node):
                count += 1
                return True
            return False

        for child in list(self.root.children.values()):
            if child.tier == TIER_DEVICE:
                visit(child)
        return count
