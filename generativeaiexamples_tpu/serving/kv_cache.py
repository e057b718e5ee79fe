"""Paged KV cache: host-side page allocator + device page pool.

The TPU-native replacement for what TRT-LLM's paged KV manager does
inside NIM (invisible to the reference repo; SURVEY.md §2.3). Design:

- Device: one page pool per model, k/v arrays [R, KH, P, page_size, Hd],
  R = cfg.cache_rows: one row per layer, times the passes of a looped
  model (a row per (pass, block); written "L" below where a model has
  one pass) (kv-heads outermost after the row axis: per-layer slices are the
  [KH, P, ps, Hd] layout the JetStream-style multi-page Pallas kernel
  wants, and the TP sharding axis is a leading dim). Page 0 is a
  reserved garbage sink — padding positions in bucketed prefills and
  unused page-table slots point at it, so scatter/gather never needs
  dynamic shapes.
- Host: PageAllocator hands out page ids (plain Python free list — the
  scheduler thread owns it; no device sync needed to allocate).
- Page tables are [B, max_pages] int32 arrays shipped to the device each
  step (tiny; rides along with the token ids).

Sized so `bytes = R * P * page_size * KH * Hd * 2 dtypes * itemsize`;
`PagePool.for_budget` picks P from an HBM byte budget.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models.llama import LlamaConfig


@dataclasses.dataclass
class PagePool:
    """Device-side page pool (a pytree leaf pair) + geometry."""

    k: jax.Array  # [L, KH, P, page_size, Hd]
    v: jax.Array
    page_size: int

    @property
    def n_pages(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return False

    @staticmethod
    def zeros(cfg: LlamaConfig, n_pages: int, page_size: int = 64,
              dtype=None, sharding=None, scale_sharding=None):
        """With `sharding`, each buffer is allocated ALREADY sharded
        (jit with out_shardings) — a TP-serving pool sized to fill the
        whole mesh must never materialize on one device first.
        `dtype="int8"` returns the fused QuantPagePool."""
        dtype = jnp.dtype(dtype or cfg.dtype)
        if dtype == jnp.int8:
            return QuantPagePool.zeros(cfg, n_pages, page_size,
                                       sharding=sharding,
                                       scale_sharding=scale_sharding)
        shape = (cfg.cache_rows, cfg.n_kv_heads, n_pages, page_size,
                 cfg.head_dim)
        k = _alloc(shape, dtype, sharding)
        v = _alloc(shape, dtype, sharding)
        return PagePool(k, v, page_size)

    @staticmethod
    def for_budget(cfg: LlamaConfig, hbm_bytes: int, page_size: int = 64,
                   dtype=None):
        dtype = jnp.dtype(dtype or cfg.dtype)
        itemsize = dtype.itemsize
        per_tok = cfg.n_kv_heads * cfg.head_dim * itemsize
        if dtype == jnp.int8:
            per_tok += cfg.n_kv_heads * 4  # narrow f32 scales
        per_page = cfg.cache_rows * page_size * per_tok * 2
        n_pages = max(2, hbm_bytes // per_page)
        return PagePool.zeros(cfg, int(n_pages), page_size, dtype)


def _alloc(shape, dtype, sharding):
    if sharding is not None:
        return jax.jit(lambda: jnp.zeros(shape, dtype),
                       out_shardings=sharding)()
    return jnp.zeros(shape, dtype)


@dataclasses.dataclass
class QuantPagePool:
    """int8 page pool with FUSED k/v storage and narrow scales
    (VERDICT r2 next-step #1b + ENGINEERING_NOTES "paths past 2300"
    #1). Codes hold k and v side by side per page — `kv[..., 0, :, :]`
    is k, `[..., 1, :, :]` is v — so the decode kernel moves each
    page's k AND v (and both scale rows) with ONE strided DMA
    descriptor each instead of four: descriptor issue count, not
    bandwidth, is the measured attention floor at decode shapes.
    Scales are one f32 per (layer, kv-head, k|v, token): 3% overhead
    vs the 200% of a head_dim-broadcast layout. Halves pool HBM vs
    bf16, which is what lets B=128 fit on a 16 GB v5e next to 8 GB of
    int8 weights."""

    # The k|v axis leads: decode's per-token scatter indexes
    # [:, l, kh, page, offset] — layer + kv-head + page + offset are
    # ADJACENT advanced indices (a scalar layer index counts as one!)
    # and lower to a plain in-place scatter. Any layout that splits the
    # advanced indices with a slice makes XLA materialize transposed
    # pool copies (+4.6 GB, OOM at B=128).
    kv: jax.Array  # int8 [2, L, KH, P, page_size, Hd]; [0]=k, [1]=v
    s: jax.Array   # f32  [2, L, KH, P, page_size] (amax/127)
    page_size: int

    @property
    def n_pages(self) -> int:
        return self.kv.shape[3]

    @property
    def quantized(self) -> bool:
        return True

    @staticmethod
    def zeros(cfg: LlamaConfig, n_pages: int, page_size: int = 64,
              sharding=None, scale_sharding=None) -> "QuantPagePool":
        shape = (2, cfg.cache_rows, cfg.n_kv_heads, n_pages, page_size,
                 cfg.head_dim)
        kv = _alloc(shape, jnp.int8, sharding)
        s = _alloc(shape[:-1], jnp.float32, scale_sharding)
        return QuantPagePool(kv, s, page_size)


jax.tree_util.register_dataclass(
    PagePool, data_fields=["k", "v"], meta_fields=["page_size"]
)
jax.tree_util.register_dataclass(
    QuantPagePool, data_fields=["kv", "s"], meta_fields=["page_size"]
)


class PageAllocator:
    """Host-side REF-COUNTED free list. Page 0 is never handed out
    (garbage sink).

    Pages are born with refcount 1 at alloc(); retain() adds a
    reference (prefix-cache sharing: the radix tree and every adopting
    sequence each hold one), release() drops one and returns the page
    to the free list at zero. free() is the historical name for
    release() and now RAISES on a double free or on a page id that was
    never allocated — a silent double free used to put the same id on
    the free list twice, handing one page to two sequences.

    `reclaim` (optional callable, n_short -> None) runs when alloc()
    comes up short, before failing: the prefix cache registers its LRU
    eviction here so cold cached pages always yield to live traffic.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._rc: dict = {}  # page id -> refcount (allocated pages only)
        self.reclaim = None

    @property
    def n_free(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free) and self.reclaim is not None:
            self.reclaim(n - len(self._free))
        if n > len(self._free):
            raise MemoryError(f"KV page pool exhausted: want {n}, have "
                              f"{len(self._free)} of {self.n_pages}")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._rc[p] = 1
        return out

    def retain(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p not in self._rc:
                raise ValueError(f"retain of unallocated page {p}")
            self._rc[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        for p in pages:
            if not 0 < p < self.n_pages:
                raise ValueError(f"page id {p} out of range "
                                 f"(pool has {self.n_pages})")
            rc = self._rc.get(p, 0)
            if rc <= 0:
                raise ValueError(f"double free of page {p}")
            if rc == 1:
                del self._rc[p]
                self._free.append(p)
            else:
                self._rc[p] = rc - 1

    free = release  # historical name; raising beats silent corruption


class SequencePages:
    """Page bookkeeping for one active sequence."""

    def __init__(self, allocator: PageAllocator, page_size: int, max_pages: int):
        self.allocator = allocator
        self.page_size = page_size
        self.max_pages = max_pages
        self.pages: List[int] = []
        self.length = 0  # tokens written
        # Leading pages adopted READ-ONLY from the prefix cache: this
        # sequence holds a reference but must never write them (the
        # engine points their scatter rows at the page-0 sink).
        self.n_shared = 0

    def adopt(self, pages: Sequence[int], n_tokens: int):
        """Adopt a cached prefix: `pages` (ref-counted, read-only)
        cover `n_tokens` (<= len(pages) * page_size). Fully-covered
        pages are shared in place; a partially-covered tail page is
        COPY-ON-WRITE — a fresh private page takes its table slot and
        the caller must fill its contents (the engine's scratch-cache
        scatter rewrites the whole page: cached head + computed tail).
        Returns the (src_page, dst_page) CoW pair, or None when the
        prefix ends exactly on a page boundary."""
        assert not self.pages and self.length == 0, "adopt() before ensure()"
        ps = self.page_size
        if not 0 < n_tokens <= len(pages) * ps:
            raise ValueError(f"adopt: {n_tokens} tokens not covered by "
                             f"{len(pages)} pages of {ps}")
        n_full = n_tokens // ps
        self.allocator.retain(pages[:n_full])
        self.pages = list(pages[:n_full])
        self.n_shared = n_full
        cow = None
        if n_tokens % ps:
            dst = self.allocator.alloc(1)[0]
            self.pages.append(dst)
            cow = (pages[n_full], dst)
        self.length = n_tokens
        return cow

    def ensure(self, new_length: int) -> None:
        """Grow the page list to cover new_length tokens."""
        need = -(-new_length // self.page_size)  # ceil
        if need > self.max_pages:
            raise MemoryError(
                f"sequence needs {need} pages > max_pages {self.max_pages}")
        if need > len(self.pages):
            self.pages.extend(self.allocator.alloc(need - len(self.pages)))
        self.length = new_length

    def table_row(self) -> np.ndarray:
        row = np.zeros((self.max_pages,), np.int32)  # padding -> page 0
        row[: len(self.pages)] = self.pages
        return row

    def release(self) -> None:
        """Idempotent: the page list is nulled out BEFORE the allocator
        call, so engine error paths that release twice (_fail_request
        racing _fail_active) are no-ops instead of double frees."""
        pages, self.pages = self.pages, []
        self.length = 0
        self.n_shared = 0
        if pages:
            self.allocator.release(pages)
