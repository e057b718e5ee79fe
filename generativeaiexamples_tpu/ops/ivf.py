"""IVF (inverted-file) approximate MIPS on TPU: the GPU_IVF_FLAT role.

The reference delegates ANN to Milvus `GPU_IVF_FLAT` (knowhere/RAFT,
common/utils.py:198-203); `ops/topk.py` replaced it with exact
brute-force MIPS — one [Q,D]x[D,N] matmul over the whole corpus per
query. That is recall-1.0 but linear in N; at millions of chunks the
retrieval hot path must stop scaling with corpus size. IVF restores the
classic two-stage shape, entirely on device:

1. train: k-means centroids over the corpus (Lloyd iterations, each one
   a [N,D]x[D,nlist] matmul + segment_sum — MXU-friendly), then a
   capacity-balanced assignment pass (greedy spill of each row to its
   nearest centroid with room, cap 1.25x the mean list size). The cap
   matters twice: it bounds the padded refine width (an unbalanced
   k-means run was measured at 2.5x the mean — all padding, all wasted
   bandwidth), and it leaves tail headroom that incremental adds
   scatter into without reshaping device arrays.
2. search: coarse [Q,D]x[D,nlist] centroid scan -> top-`nprobe`
   partitions per query -> gather ONLY those partitions' row blocks ->
   one batched refine matmul -> top-k. Cost per query is
   O(nlist + nprobe*N/nlist) rows instead of O(N).

Storage is partition-blocked: `db3 [nlist, max_len, D]` (+ a
local->global row-id map, pad = -1), so the probe gather moves
`nprobe` CONTIGUOUS blocks instead of tens of thousands of scattered
rows — measured ~2x faster than a row-gather layout on the same
corpus. Optional int8 scalar quantization (per-row symmetric amax/127
scales, the `ops/quant.py` idiom) stores the corpus at 1/4 the f32 HBM
footprint; scores dequantize during the refine matmul.

Two layouts, mirroring `ops/topk.py`:
- `IVFIndex`: single-device. Incremental `add()` assigns new rows with
  one [M,D]x[D,nlist] matmul and SCATTERS them into partition tail
  slots — no retrain, and only the M new rows cross the host->device
  link.
- `ShardedIVFIndex`: corpus rows round-robin across a mesh axis, every
  shard holding a full [nlist, max_len_local, D] table of its rows
  (shared centroids). Each shard refines the probed partitions over
  its local rows, then the [Q, n_shards*k] candidate set is
  all-gathered and reduced — the same two-phase top-k as
  `ShardedMIPSIndex`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Balanced-assignment capacity: cap each partition at this multiple of
# the mean list size (padding bound + incremental-add headroom).
BALANCE_CAP = 1.25
# Nearest centroids considered per row before the overflow fallback.
BALANCE_CANDIDATES = 8


# -- k-means training --------------------------------------------------------


def _sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    """Pairwise squared L2 distances, [N,D] x [K,D] -> [N,K] via one
    matmul (the |x|^2 term is rank-constant and dropped)."""
    c2 = jnp.sum(c * c, axis=1)
    return c2 - 2.0 * jnp.einsum(
        "nd,kd->nk", x, c, preferred_element_type=jnp.float32)


@jax.jit
def _kmeans_step(data: jax.Array, centroids: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    assign = jnp.argmin(_sq_dists(data, centroids), axis=1)
    k = centroids.shape[0]
    sums = jax.ops.segment_sum(data, assign, num_segments=k)
    counts = jax.ops.segment_sum(
        jnp.ones((data.shape[0],), jnp.float32), assign, num_segments=k)
    # Empty partitions keep their old centroid (standard Lloyd fallback).
    new_c = jnp.where(counts[:, None] > 0,
                      sums / jnp.maximum(counts[:, None], 1.0), centroids)
    return new_c, assign


@jax.jit
def assign_partitions(data: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest-centroid assignment, [M,D] -> [M] int32 — the whole cost
    of an incremental add."""
    return jnp.argmin(_sq_dists(data, centroids), axis=1).astype(jnp.int32)


def kmeans_fit(data, nlist: int, *, iters: int = 8, seed: int = 0
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Lloyd k-means on device: (centroids [nlist,D] f32, assignments
    [N] int32), both returned as host numpy. `nlist` is clamped to N."""
    data = jnp.asarray(np.asarray(data, np.float32))
    n = data.shape[0]
    nlist = max(1, min(int(nlist), n))
    init = jax.random.choice(jax.random.PRNGKey(seed), n, (nlist,),
                             replace=False)
    c = data[init]
    for _ in range(max(1, iters)):
        c, _ = _kmeans_step(data, c)
    assign = assign_partitions(data, c)
    return np.asarray(c), np.asarray(assign)


def rank_round_assign(order: np.ndarray, best: np.ndarray, nlist: int,
                      cap: int) -> np.ndarray:
    """Capacity-capped assignment over precomputed candidate lists.

    `order` [N, c] holds each row's `c` nearest centroids
    (nearest-first), `best` [N] its nearest distance. Vectorized rank
    rounds (a per-row Python loop is minutes of host time at the
    10M-row design point): round r offers every still-unplaced row its
    r-th nearest centroid; within a partition, slots go to rows in
    best-distance priority order. Rows whose every candidate is full
    land on the globally emptiest partition (rare)."""
    n, candidates = order.shape
    counts = np.zeros(nlist, np.int64)
    out = np.full(n, -1, np.int32)
    pending = np.argsort(best, kind="stable")  # row ids, priority order
    for r in range(candidates):
        if not len(pending):
            break
        cand = order[pending, r].astype(np.int64)
        sort_idx = np.argsort(cand, kind="stable")  # keeps priority order
        sp = cand[sort_idx]
        grp_start = np.searchsorted(sp, np.arange(nlist))
        pos_in_grp = np.arange(len(sp)) - grp_start[sp]
        take = pos_in_grp < (cap - counts)[sp]
        rows = pending[sort_idx[take]]
        out[rows] = sp[take].astype(np.int32)
        counts += np.bincount(sp[take], minlength=nlist)
        pending = pending[out[pending] < 0]
    for i in pending:  # all `candidates` nearest were full (rare)
        p = int(np.argmin(counts))
        out[i] = p
        counts[p] += 1
    return out


@functools.partial(jax.jit, static_argnames=("c",))
def _chunk_candidates(x: jax.Array, centroids: jax.Array, c: int):
    d2 = _sq_dists(x, centroids)
    neg, idx = jax.lax.top_k(-d2, c)
    return idx.astype(jnp.int32), -neg[:, 0]


def centroid_candidates(data: np.ndarray, centroids: np.ndarray, *,
                        candidates: int = BALANCE_CANDIDATES,
                        chunk: int = 65536
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-`candidates` nearest centroids per row, computed ON DEVICE
    in bounded chunks: (order [N, c] int32 nearest-first, best [N] f32
    nearest squared distance). The host-matmul equivalent inside
    `balanced_assign` is fine at 100k rows x 512 lists but takes tens
    of minutes at the tiered design point (10M rows x 16k lists); this
    is the same arithmetic as one [N,D]x[D,nlist] scan, MXU-shaped."""
    centroids = np.asarray(centroids, np.float32)
    c = min(candidates, len(centroids))
    cd = jnp.asarray(centroids)
    n = len(data)
    order = np.empty((n, c), np.int32)
    best = np.empty((n,), np.float32)
    for lo in range(0, n, chunk):
        x = jnp.asarray(np.asarray(data[lo:lo + chunk], np.float32))
        o, b = _chunk_candidates(x, cd, c)
        order[lo:lo + chunk] = np.asarray(o)
        best[lo:lo + chunk] = np.asarray(b)
    return order, best


def balanced_assign(data: np.ndarray, centroids: np.ndarray, *,
                    cap_factor: float = BALANCE_CAP,
                    candidates: int = BALANCE_CANDIDATES) -> np.ndarray:
    """Capacity-capped assignment: rows claim their nearest centroid in
    best-distance order; a full partition spills the row to its next
    nearest with room (then to the globally emptiest — rare). Bounds
    every list at cap_factor * N/nlist, which bounds the padded refine
    width the search gather pays for. Candidates come from the same
    device-chunked scan the tiered build uses (one arithmetic, no
    host/device twin to drift)."""
    data = np.asarray(data, np.float32)
    centroids = np.asarray(centroids, np.float32)
    n, nlist = len(data), len(centroids)
    cap = int(cap_factor * n / nlist) + 1
    order, best = centroid_candidates(data, centroids,
                                      candidates=candidates)
    return rank_round_assign(order, best, nlist, cap)


# -- int8 row quantization (ops/quant.py idiom, per-row scales) --------------


def quantize_rows(v: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 over the trailing (feature) axis: scale =
    amax/127. Returns (q int8 [..., D], s f32 [...])."""
    vf = v.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(vf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


# -- the search kernel -------------------------------------------------------


def _score_probed(q, centroids, db3, scales3, g3, k: int, nprobe: int):
    """The shared two-stage scoring block (trace-time helper): coarse
    [Q,D]x[D,nlist] scan -> top-`nprobe` partition block gather ->
    batched refine matmul (+ int8 dequant) -> pad-masked top-k.
    q [Q,D]; db3 [nlist,M,D] f32 or int8 (+ scales3 [nlist,M] when
    int8, else None); g3 [nlist,M] int32 local->global ids (pad = -1).
    Returns (scores [Q,kk], row ids [Q,kk], scanned-row count); padded
    slots come back as -inf / id -1. Both the single-device jit and the
    per-shard body of ShardedIVFIndex trace through this one kernel."""
    coarse = jnp.einsum("qd,ld->ql", q, centroids,
                        preferred_element_type=jnp.float32)
    _, pids = jax.lax.top_k(coarse, min(nprobe, centroids.shape[0]))
    part = db3[pids]                       # [Q, P, M, D] block gather
    gids = g3[pids].reshape(q.shape[0], -1)
    sc = jax.lax.dot_general(
        part.reshape(q.shape[0], -1, db3.shape[-1]).astype(jnp.float32),
        q[:, :, None], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)[:, :, 0]
    if scales3 is not None:
        sc = sc * scales3[pids].reshape(q.shape[0], -1)
    valid = gids >= 0
    sc = jnp.where(valid, sc, -jnp.inf)
    best, pos = jax.lax.top_k(sc, min(k, sc.shape[1]))
    return best, jnp.take_along_axis(gids, pos, axis=1), valid.sum()


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _ivf_search(q, centroids, db3, scales3, g3, k: int, nprobe: int):
    """Single-device jitted entry over `_score_probed`."""
    return _score_probed(q, centroids, db3, scales3, g3, k, nprobe)


def _partition_lists(assign: np.ndarray, nlist: int):
    """Bucket row ids by partition in one argsort + searchsorted pass
    (one flatnonzero scan PER partition is O(nlist*N) — minutes at the
    10M-row design point). Rows within a list stay in ascending order,
    matching the previous flatnonzero layout."""
    order = np.argsort(assign, kind="stable")
    sorted_a = assign[order]
    bounds = np.searchsorted(sorted_a, np.arange(nlist + 1))
    lists = [order[bounds[p]:bounds[p + 1]] for p in range(nlist)]
    max_len = max(1, int(np.diff(bounds).max(initial=0)))
    return lists, max_len


class IVFIndex:
    """Single-device IVF index over an [N,D] corpus.

    Pass `centroids`/`assignments` (e.g. from a persisted snapshot) to
    skip training. The corpus crosses the host->device link once at
    construction; `add()` ships only the new rows.
    """

    def __init__(self, vectors: np.ndarray, nlist: int, *,
                 nprobe: int = 16, quantize_int8: bool = False,
                 train_iters: int = 8, seed: int = 0,
                 centroids: Optional[np.ndarray] = None,
                 assignments: Optional[np.ndarray] = None):
        vectors = np.asarray(vectors, np.float32)
        self.dim = vectors.shape[1]
        self.nprobe = int(nprobe)
        self.quantize_int8 = bool(quantize_int8)
        if centroids is None or assignments is None:
            centroids, _ = kmeans_fit(vectors, nlist, iters=train_iters,
                                      seed=seed)
            assignments = balanced_assign(vectors, centroids)
        self.centroids = jnp.asarray(np.asarray(centroids, np.float32))
        self.nlist = int(self.centroids.shape[0])
        self._assign = np.asarray(assignments, np.int32)
        self.n_rows = int(vectors.shape[0])
        self._build_tables(vectors)

    def _build_tables(self, vectors: np.ndarray) -> None:
        lists, ml = _partition_lists(self._assign, self.nlist)
        self.max_list_len = ml
        self._list_len = np.array([len(l) for l in lists], np.int64)
        db3 = np.zeros((self.nlist, ml, self.dim), np.float32)
        g3 = np.full((self.nlist, ml), -1, np.int32)
        for p, l in enumerate(lists):
            db3[p, :len(l)] = vectors[l]
            g3[p, :len(l)] = l
        self._g3 = jnp.asarray(g3)
        if self.quantize_int8:
            self._db3, self._scales3 = quantize_rows(jnp.asarray(db3))
        else:
            self._db3, self._scales3 = jnp.asarray(db3), None

    def add(self, new_vectors: np.ndarray,
            max_grow_factor: float = 4.0) -> bool:
        """Assign new rows to existing partitions (one matmul) and
        scatter them into partition tail slots device-side — no
        retrain, no full-corpus re-transfer. Tables widen (device-side
        pad) only when a partition outgrows its headroom. Returns False
        WITHOUT mutating anything when the add would skew a partition
        past max_grow_factor x the mean list size — the padded table is
        max_len wide for EVERY partition, so one hot partition (e.g. a
        same-topic bulk ingest) would multiply the whole index's HBM
        footprint; the owning store retrains instead."""
        new_vectors = np.asarray(new_vectors, np.float32)
        m = len(new_vectors)
        if not m:
            return True
        new_dev = jnp.asarray(new_vectors)
        a = np.asarray(assign_partitions(new_dev, self.centroids))
        counts = self._list_len.copy()
        slots = np.empty(m, np.int64)
        for i, p in enumerate(a):
            slots[i] = counts[p]
            counts[p] += 1
        need = int(counts.max())
        cap = max_grow_factor * max(1.0, (self.n_rows + m) / self.nlist)
        if need > self.max_list_len and need > cap:
            return False
        self._list_len = counts
        if need > self.max_list_len:
            pad = need - self.max_list_len
            self._db3 = jnp.pad(self._db3, ((0, 0), (0, pad), (0, 0)))
            self._g3 = jnp.pad(self._g3, ((0, 0), (0, pad)),
                               constant_values=-1)
            if self._scales3 is not None:
                self._scales3 = jnp.pad(self._scales3, ((0, 0), (0, pad)))
            self.max_list_len = need
        gids = jnp.asarray(self.n_rows + np.arange(m, dtype=np.int32))
        pa, sa = jnp.asarray(a), jnp.asarray(slots)
        if self.quantize_int8:
            q, s = quantize_rows(new_dev)
            self._db3 = self._db3.at[pa, sa].set(q)
            self._scales3 = self._scales3.at[pa, sa].set(s)
        else:
            self._db3 = self._db3.at[pa, sa].set(new_dev)
        self._g3 = self._g3.at[pa, sa].set(gids)
        self._assign = np.concatenate([self._assign, a])
        self.n_rows += m
        return True

    def search(self, queries: jax.Array, k: int,
               nprobe: Optional[int] = None):
        """queries [Q,D] -> (scores [Q,kk], global row ids [Q,kk],
        n_scanned_rows int). Padded slots: -inf score, id -1."""
        nprobe = int(nprobe or self.nprobe)
        best, idx, scanned = _ivf_search(
            jnp.asarray(queries, jnp.float32), self.centroids,
            self._db3, self._scales3, self._g3, k, nprobe)
        return best, idx, int(scanned)

    def state(self) -> dict:
        """Persistable training state (corpus itself lives with the
        owning store)."""
        return {"centroids": np.asarray(self.centroids),
                "assignments": np.asarray(self._assign)}


# -- sharded variant ---------------------------------------------------------


class ShardedIVFIndex:
    """IVF with corpus rows round-robin over a mesh axis.

    Every shard holds the full partition structure (shared centroids)
    over ITS rows: a local [nlist, max_len_local, D] table, stacked to
    [n_shards, ...] and sharded on the leading mesh-axis dim. Search
    runs under shard_map: each shard probes the same top-`nprobe`
    partitions over its local rows (~1/n_shards of each list), takes a
    local top-k, then the [Q, n_shards*k] candidate set is all-gathered
    and reduced — the `ShardedMIPSIndex` two-phase shape. The candidate
    set equals the single-device index's exactly (same centroids, same
    assignments), so results match modulo float ordering.
    """

    def __init__(self, vectors: np.ndarray, nlist: int, mesh: Mesh,
                 axis: str = "tensor", *, nprobe: int = 16,
                 quantize_int8: bool = False, train_iters: int = 8,
                 seed: int = 0, centroids: Optional[np.ndarray] = None,
                 assignments: Optional[np.ndarray] = None):
        vectors = np.asarray(vectors, np.float32)
        self.mesh, self.axis = mesh, axis
        self.n_shards = mesh.shape[axis]
        self.dim = vectors.shape[1]
        self.nprobe = int(nprobe)
        self.quantize_int8 = bool(quantize_int8)
        if centroids is None or assignments is None:
            centroids, _ = kmeans_fit(vectors, nlist, iters=train_iters,
                                      seed=seed)
            assignments = balanced_assign(vectors, centroids)
        self.centroids = jnp.asarray(np.asarray(centroids, np.float32))
        self.nlist = int(self.centroids.shape[0])
        self._assign = np.asarray(assignments, np.int32)
        self.n_rows = int(vectors.shape[0])
        self._build_layout(vectors)

    def _build_layout(self, vectors: np.ndarray) -> None:
        S, nlist = self.n_shards, self.nlist
        ml = 1
        per_shard_lists = []
        for s in range(S):
            rows = np.arange(s, self.n_rows, S)  # round-robin split
            local_lists, local_ml = _partition_lists(self._assign[rows],
                                                     nlist)
            per_shard_lists.append([rows[l] for l in local_lists])
            ml = max(ml, local_ml)
        db3 = np.zeros((S, nlist, ml, self.dim), np.float32)
        g3 = np.full((S, nlist, ml), -1, np.int32)
        for s, lists in enumerate(per_shard_lists):
            for p, l in enumerate(lists):
                db3[s, p, :len(l)] = vectors[l]
                g3[s, p, :len(l)] = l
        self.max_list_len = ml
        shard = NamedSharding(self.mesh, P(self.axis))
        if self.quantize_int8:
            q, sc = quantize_rows(jnp.asarray(db3))
            self._db3 = jax.device_put(q, shard)
            self._scales3 = jax.device_put(sc, shard)
        else:
            self._db3 = jax.device_put(jnp.asarray(db3), shard)
            # shard_map in_specs must match a real array pytree, so the
            # unquantized path carries a replicated dummy scalar.
            self._scales3 = jnp.zeros((1,), jnp.float32)
        self._g3 = jax.device_put(jnp.asarray(g3), shard)
        self._searches: dict = {}

    def add(self, new_vectors: np.ndarray, all_vectors: np.ndarray,
            max_grow_factor: float = 4.0) -> bool:
        """Assign new rows WITHOUT retraining (one device matmul), then
        rebuild the sharded layout from the full host corpus
        (`all_vectors`, old rows first) — the per-shard blocks change
        shape under the round-robin row split, so unlike `IVFIndex.add`
        this re-ships the corpus; centroids and assignments are reused
        as-is. Batch adds where that matters. Returns False without
        mutating when a partition would skew past max_grow_factor x the
        mean (see IVFIndex.add) — the store retrains instead."""
        new_vectors = np.asarray(new_vectors, np.float32)
        if not len(new_vectors):
            return True
        a = np.asarray(assign_partitions(jnp.asarray(new_vectors),
                                         self.centroids))
        n_total = self.n_rows + len(new_vectors)
        counts = np.bincount(np.concatenate([self._assign, a]),
                             minlength=self.nlist)
        if counts.max() > max_grow_factor * max(1.0, n_total / self.nlist):
            return False
        self._assign = np.concatenate([self._assign, a])
        all_vectors = np.asarray(all_vectors, np.float32)
        self.n_rows = int(all_vectors.shape[0])
        self._build_layout(all_vectors)
        return True

    def _build(self, k: int, nprobe: int):
        axis, quant = self.axis, self.quantize_int8
        centroids = self.centroids
        n_shards = self.n_shards

        def local(q, db3, g3, scales3):
            best, gidx, n_local = _score_probed(
                q, centroids, db3[0], scales3[0] if quant else None,
                g3[0], k, nprobe)
            scanned = jax.lax.psum(n_local, axis)
            kk = best.shape[1]
            best = jax.lax.all_gather(best, axis, axis=1)  # [Q, S, kk]
            gidx = jax.lax.all_gather(gidx, axis, axis=1)
            best = best.reshape(best.shape[0], -1)
            gidx = gidx.reshape(gidx.shape[0], -1)
            top, pos = jax.lax.top_k(best, min(k, n_shards * kk))
            return top, jnp.take_along_axis(gidx, pos, axis=1), scanned

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(axis), P(axis),
                      P(axis) if quant else P()),
            out_specs=(P(), P(), P()), check_vma=False)
        return jax.jit(fn)

    def search(self, queries: jax.Array, k: int,
               nprobe: Optional[int] = None):
        nprobe = int(nprobe or self.nprobe)
        key = (k, nprobe, self.max_list_len)
        if key not in self._searches:
            self._searches[key] = self._build(k, nprobe)
        best, idx, scanned = self._searches[key](
            jnp.asarray(queries, jnp.float32), self._db3, self._g3,
            self._scales3)
        return best, idx, int(scanned)

    def state(self) -> dict:
        return {"centroids": np.asarray(self.centroids),
                "assignments": np.asarray(self._assign)}
