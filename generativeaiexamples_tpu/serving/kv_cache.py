"""Paged KV cache: host-side page allocator + device page pool.

The TPU-native replacement for what TRT-LLM's paged KV manager does
inside NIM (invisible to the reference repo; SURVEY.md §2.3). Design:

- Device: one page pool per model, built by the model's architecture
  entry (serving/served_models.py: `PagePool.zeros` asks `served(cfg)`
  and knows no architecture itself). A Llama caches K and V per head:
  k/v arrays [R, KH, P, page_size, Hd], R = cfg.cache_rows: one row per
  layer, times the passes of a looped model (a row per (pass, block);
  written "L" below where a model has one pass) (kv-heads outermost
  after the row axis: per-layer slices are the [KH, P, ps, Hd] layout
  the JetStream-style multi-page Pallas kernel wants, and the TP
  sharding axis is a leading dim). Page 0 is a reserved garbage sink —
  padding positions in bucketed prefills and unused page-table slots
  point at it, so scatter/gather never needs dynamic shapes. Nothing
  reads it: XLA's scatters leave an idle decode slot's row there, and
  the int8 kernels, which walk the live slots alone, neither write nor
  read it in a decode step.
- The other architectures' pool classes live here too, each described
  where it is defined: LatentPagePool (serving/served_latent.py builds
  and walks it), HybridPool (served_hybrid.py), SparseIndexPool
  (served_sparse.py), WindowPool with WindowTables and
  WindowSequencePages (served_window.py).
- Host: PageAllocator hands out page ids (plain Python free list — the
  scheduler thread owns it; no device sync needed to allocate).
- Page tables are [B, max_pages] int32 arrays shipped to the device each
  step (tiny; rides along with the token ids).
- A decode step's new row a slot goes into the int8 pool through ONE
  in-place Pallas call a cache row where kernels are on
  (`kernel_append` decides, from the pool, the slots' rank and the step
  program's `use_pallas`): the ATTENTION's own call in a looped model's
  walk (QuantPagePool.attend_appending, serving/paged_attention_int8.py;
  engine_model.fuses_append decides), the append's
  (serving/kv_append_int8.py) in a one-pass model's blocks and in the
  other entries' blocks, whose call sites append first; and through XLA's
  scatters everywhere else: off the chip, a bf16 pool, a verify's r rows
  a slot. All three write the same bytes outside the sink page
  (QuantPagePool.append; tests/test_kv_append_kernel.py,
  tests/test_paged_attention_int8_pages.py). Where the kernels are on, the step's `active` mask reaches them as
  `TokenSlots.live` (`kernel_live_rows`, once a step): the append and the
  attention kernel walk the live slots and no other.

Sized so `bytes = R * P * page_size * KH * Hd * 2 dtypes * itemsize`;
`PagePool.for_budget` picks P from an HBM byte budget.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models.llama import LlamaConfig
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

if TYPE_CHECKING:
    from generativeaiexamples_tpu.serving.paged_attention_int8 import LiveRows


class PoolGeometry(NamedTuple):
    """What a pool holds for one page, whatever its layout."""

    rows: int       # cfg.cache_rows
    kv_heads: int
    page_size: int
    head_dim: int
    dtype: jnp.dtype  # of the stored K and V: the codes' for an int8 pool


def _page_axes(rows, kv_heads, table_flat):
    """The advanced indices of whole pages, every (row, kv head) of them."""
    li = jnp.arange(rows)[:, None, None]
    kh = jnp.arange(kv_heads)[None, :, None]
    return li, kh, table_flat[None, None, :]


class TokenSlots(NamedTuple):
    """Where a step's new tokens go: token [b, ...] of every kv head
    lands in page `page_idx[b, ...]` at `offset[b, ...]`; and what the
    step program was told of kernels and the mesh, which decides the
    form of an int8 pool's append (`kernel_append`), and the slots the
    kernels walk."""

    kh: jax.Array        # [KH, 1, ...]: the kv heads, over the slots
    page_idx: jax.Array  # [B, ...]
    offset: jax.Array    # [B, ...]
    use_pallas: Optional[bool] = None  # the step program's; None: on a TPU
    mesh: Optional[jax.sharding.Mesh] = None
    live: Optional["LiveRows"] = None  # kernel_live_rows(pool, active, ...)


def token_slots(kv_heads: int, page_idx: jax.Array, offset: jax.Array,
                use_pallas: Optional[bool] = None, mesh=None,
                live=None) -> TokenSlots:
    """The slots `append` writes (any rank: one row a slot, or r).
    Taken once a step, outside the layer walk: every layer writes the
    same slots."""
    kh = jnp.arange(kv_heads)[(slice(None),) + (None,) * page_idx.ndim]
    return TokenSlots(kh, page_idx, offset, use_pallas, mesh, live)


def kernel_live_rows(pool, active, use_pallas: Optional[bool] = None):
    """A step's `active` mask [B] as the int8 pool's two kernels take it
    (paged_attention_int8.LiveRows: the live slots' indices first, and
    their count), made on the device, or None where the kernels are off
    (`kernel_append`: the attention kernel runs under the same
    conditions; never for a bf16 or a latent pool) or the caller has no
    mask: the XLA forms compute every slot, as they did. Taken once a
    step, outside the layer walk, by the decode bodies that
    `decode_multi_step` hands its `active` (engine_model._decode_once,
    served_hybrid.decode_once)."""
    if active is None or not kernel_append(pool, use_pallas):
        return None
    from generativeaiexamples_tpu.serving import paged_attention_int8

    return paged_attention_int8.live_rows(active)


def kernel_append(pool, use_pallas: Optional[bool] = None,
                  rank: int = 1) -> bool:
    """Whether `pool.append` for slots of `rank` is the Pallas kernel
    (serving/kv_append_int8.py) and not XLA's scatters: an int8 pool,
    one new row a slot, kernels on (`use_pallas`; None means on a TPU),
    and a page and head size its DMAs can tile (else the log says so,
    once). From what the step program can observe and nothing else; the
    engine counts `decode_steps_kernel_append` by the same function."""
    if not pool.quantized or rank != 1:
        return False
    if not ((jax.default_backend() == "tpu") if use_pallas is None
            else use_pallas):
        return False
    _, _, ps, Hd, _ = pool.geometry
    if ps % 128 or Hd % 128:
        log_kernel_declined(
            "kv_append_int8", "XLA's four scatters a cache row",
            f"page_size {ps} and head_dim {Hd} must both be multiples of 128")
        return False
    return True


@dataclasses.dataclass
class PagePool:
    """Device-side page pool (a pytree leaf pair) + geometry.

    Everything that indexes into `k` and `v` is a method here (or an
    attention kernel): the step programs of serving/engine_model.py
    call `append`, `write_pages`, `move_tokens` and the reads, and know
    no axis order. QuantPagePool has the same methods."""

    k: jax.Array  # [L, KH, P, page_size, Hd]
    v: jax.Array
    page_size: int

    @property
    def n_pages(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return False

    @property
    def geometry(self) -> PoolGeometry:
        L, KH, _, ps, Hd = self.k.shape
        return PoolGeometry(L, KH, ps, Hd, self.k.dtype)

    def devices(self):
        return self.k.devices()

    def attention_operands(self, row):
        """Cache row `row` as the attention dispatchers take it:
        (k_pages, v_pages, k_scales, layer), the last two None here."""
        return self.k[row], self.v[row], None, None

    def append(self, row, slots, k_new, v_new) -> "PagePool":
        """Write a step's new K and V ([KH, B, ..., Hd], `slots` from
        token_slots for the same [B, ...]) into cache row `row`, a
        Python int or a traced scalar (the looped walk's)."""
        kh, page_idx, offset = slots[:3]
        k = self.k.at[row, kh, page_idx[None], offset[None], :].set(
            k_new.astype(self.k.dtype))
        v = self.v.at[row, kh, page_idx[None], offset[None], :].set(
            v_new.astype(self.v.dtype))
        return PagePool(k, v, self.page_size)

    def encode_pages(self, k, v):
        """K and V as `write_pages` stores them: as they are."""
        return k, v

    def write_pages(self, pages, table_flat) -> "PagePool":
        """Scatter page-shaped K and V (`pages` = encode_pages' pair,
        each [L, KH, M, ps, Hd]) into pages `table_flat` [M].

        ALL advanced indices are contiguous from axis 0 ([li, kh,
        pages]) — the old bracketed form `at[li, :, pages]` made XLA
        materialize a full copy of the donated pool once the group had
        >1 row, which is +3.3 GB HBM at the B=128 deployment shape and
        an OOM at long-context pool sizes."""
        kw, vw = pages
        li, kh, tb = _page_axes(*kw.shape[:2], table_flat)
        return PagePool(self.k.at[li, kh, tb].set(kw.astype(self.k.dtype)),
                        self.v.at[li, kh, tb].set(vw.astype(self.v.dtype)),
                        self.page_size)

    def read_pages(self, table_row, dtype):
        """Pages `table_row` [n] as (k, v), each [L, KH, n, ps, Hd] in
        `dtype`: the values decode attention reads for those pages."""
        L, KH = self.k.shape[:2]
        li, kh, tb = _page_axes(L, KH, table_row)
        return (self.k[li, kh, tb].astype(dtype),
                self.v[li, kh, tb].astype(dtype))

    def export_pages(self, table_row):
        """Pages `table_row` [n] VERBATIM and page-major: (codes
        [n, 2, L, KH, ps, Hd] with [:, 0] = k and [:, 1] = v, None)."""
        L, KH = self.k.shape[:2]
        li, kh, tb = _page_axes(L, KH, table_row)
        codes = jnp.stack([self.k[li, kh, tb], self.v[li, kh, tb]])
        return jnp.moveaxis(codes, 3, 0), None

    def import_pages(self, codes, scales, table_row) -> "PagePool":
        """export_pages' inverse: scatter its arrays into `table_row`."""
        del scales
        return self.write_pages((jnp.moveaxis(codes[:, 0], 0, 2),
                                 jnp.moveaxis(codes[:, 1], 0, 2)), table_row)

    def move_tokens(self, src, dst) -> "PagePool":
        """Copy tokens from `src` to `dst`, each a (page, offset) pair
        of [B, n], in every (row, kv head): ONE gather and one scatter
        per array, over all rows."""
        L, KH = self.k.shape[:2]
        li = jnp.arange(L)[:, None, None, None]
        kh = jnp.arange(KH)[None, :, None, None]

        def at(slots):
            return (li, kh) + tuple(t[None, None] for t in slots)

        k, v = self.k[at(src)], self.v[at(src)]
        return PagePool(self.k.at[at(dst)].set(k), self.v.at[at(dst)].set(v),
                        self.page_size)

    @staticmethod
    def zeros(cfg: LlamaConfig, n_pages: int, page_size: int = 64,
              dtype=None, sharding=None, scale_sharding=None, slots=None):
        """The pool of `cfg`'s architecture, as its entry builds it
        (serving/served_models.py; the entry's errors are this call's).
        With `sharding`, each buffer is allocated ALREADY sharded (jit
        with out_shardings): a TP-serving pool sized to fill the whole
        mesh must never materialize on one device first."""
        from generativeaiexamples_tpu.serving.served_models import served

        return served(cfg).zeros(cfg, n_pages, page_size,
                                 jnp.dtype(dtype or cfg.dtype), sharding,
                                 scale_sharding, slots)

    @staticmethod
    def for_budget(cfg: LlamaConfig, hbm_bytes: int, page_size: int = 64,
                   dtype=None):
        dtype = jnp.dtype(dtype or cfg.dtype)
        per_page = page_size * kv_token_bytes(cfg, cfg.cache_rows, dtype)
        n_pages = max(2, hbm_bytes // per_page)
        return PagePool.zeros(cfg, int(n_pages), page_size, dtype)


def _alloc(shape, dtype, sharding):
    if sharding is not None:
        return jax.jit(lambda: jnp.zeros(shape, dtype),
                       out_shardings=sharding)()
    return jnp.zeros(shape, dtype)


def kv_pool_zeros(cfg, n_pages: int, page_size: int, dtype, sharding=None,
                  scale_sharding=None):
    """A pool of K and V per head for `cfg.cache_rows` rows: the fused
    QuantPagePool for int8, a PagePool in every other type."""
    if dtype == jnp.int8:
        return QuantPagePool.zeros(cfg, n_pages, page_size,
                                   sharding=sharding,
                                   scale_sharding=scale_sharding)
    shape = (cfg.cache_rows, cfg.n_kv_heads, n_pages, page_size,
             cfg.head_dim)
    return PagePool(_alloc(shape, dtype, sharding),
                    _alloc(shape, dtype, sharding), page_size)


def kv_token_bytes(cfg, rows: int, kv_dtype, tensor: int = 1) -> int:
    """Bytes a device holds of ONE cached token in `rows` rows of such a
    pool: int8 codes and a float32 scale a (head, token), or the values."""
    kh = -(-cfg.n_kv_heads // tensor)
    if jnp.dtype(kv_dtype) == jnp.int8:
        return rows * kh * (2 * cfg.head_dim + 2 * 4)
    return rows * kh * 2 * cfg.head_dim * jnp.dtype(kv_dtype).itemsize


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

    @property
    def geometry(self) -> PoolGeometry:
        _, L, KH, _, ps, Hd = self.kv.shape
        return PoolGeometry(L, KH, ps, Hd, self.kv.dtype)

    def devices(self):
        return self.kv.devices()

    def attention_operands(self, row):
        """PagePool.attention_operands: the WHOLE fused pool as
        k_pages, no v_pages, the scales, and the row as `layer`, which
        the kernel indexes inside its DMA descriptors (a host-side
        slice of the kv-leading layout is not contiguous)."""
        return self.kv, None, self.s, row

    def _quantize(self, x):
        from generativeaiexamples_tpu.serving.paged_attention_int8 import (
            quantize_kv)

        return quantize_kv(x, scale_dtype=self.s.dtype)

    def append(self, row, slots, k_new, v_new) -> "QuantPagePool":
        """PagePool.append for the fused pool: codes and scales of the
        new K and V, one scale a (kv head, token), in one of two forms
        that write the same bytes (tests/test_kv_append_kernel.py).

        A step's ONE new row a slot (slots of rank 1) with kernels on:
        ONE Pallas call that patches, in place, the int8 tile and the
        scale row the new token lives in (serving/kv_append_int8.py;
        under a tensor-parallel mesh through `shard_map` on the kv
        heads, as the attention kernel). `kernel_append` decides, from
        the pool, the slots' rank and the step program's `use_pallas`:
        no option selects the form. A LOOPED model's programs never call
        `append` where this form would apply: their attention call
        writes the row (`attend_appending`; engine_model.fuses_append,
        PR 46).

        With `slots.live` the kernel reads and writes the live slots'
        tiles alone; an idle slot's (page 0, the sink) is left as it is.

        Everything else keeps XLA's scatters, the reference form: off
        the chip (every CPU-lowered program), a page or head size the
        kernel cannot tile, and slots of rank 2 (the linear and the tree
        verify: a slot's r rows share a tile and would race in a per-row
        read-modify-write; no benchmark cell speculates). TWO scatters
        per array (k then v), all advanced indices adjacent (scalar
        kv-index + scalar row + kh/page/offset) -> plain in-place
        scatters with natural layouts; a single stacked [2, ...] update
        makes XLA transpose the whole pool (OOM). Each is a serial loop
        over KV heads x slots index tuples, 57-70 us whatever a tuple
        carries: they were 36 % of a Mistral-7B decode step and 56 % of
        an Ouro step (PERF.md section 5)."""
        kh, page_idx, offset, use_pallas, mesh, live = slots
        if kernel_append(self, use_pallas, page_idx.ndim):
            return self._append_kernel(row, page_idx, offset, mesh,
                                       *self.new_row(k_new, v_new), live)
        kq, ks = self._quantize(k_new)
        vq, vs = self._quantize(v_new)
        kv = self.kv.at[0, row, kh, page_idx[None], offset[None], :].set(kq)
        kv = kv.at[1, row, kh, page_idx[None], offset[None], :].set(vq)
        s = self.s.at[0, row, kh, page_idx[None], offset[None]].set(ks)
        s = s.at[1, row, kh, page_idx[None], offset[None]].set(vs)
        return QuantPagePool(kv, s, self.page_size)

    def new_row(self, k_new, v_new):
        """A step's new K and V ([KH, B, Hd]) as the pool's kernels take
        one row a slot: (codes [2, KH, B, Hd] int8, scales [2, KH, B])."""
        kq, ks = self._quantize(k_new)
        vq, vs = self._quantize(v_new)
        return jnp.stack([kq, vq]), jnp.stack([ks, vs])

    def attend_appending(self, row, k_new, v_new, attend) -> tuple:
        """append's third form, for a looped model's step, whose attention
        is the plain int8 kernel (`engine_model.fuses_append`): `attend(
        *attention_operands(row), new) -> (out, kv, s)` is that call with
        the new row as an operand (`new_row`), and the kernel writes the
        row into the page it has just copied, where the append's kernel
        read the tile again in a launch of its own
        (serving/paged_attention_int8.py, rule 6; the same bytes:
        tests/test_paged_attention_int8_pages.py). -> (out, the pool with
        the row in it)."""
        out, kv, s = attend(*self.attention_operands(row),
                            self.new_row(k_new, v_new))
        return out, QuantPagePool(kv, s, self.page_size)

    def _append_kernel(self, row, page_idx, offset, mesh, codes, scales,
                       live=None):
        """append's kernel form: codes [2, KH, B, Hd], scales [2, KH, B];
        `live`: the slots it writes (None: every one)."""
        from jax.sharding import PartitionSpec as P

        from generativeaiexamples_tpu.serving.kv_append_int8 import (
            kv_append_int8)

        fn = kv_append_int8
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            # kv heads (the TP axis) at axis 2 of the pool, 1 of the new
            # rows: paged_attention_dispatch's specs for the fused pool
            fused_s, new_s = P(None, None, "tensor"), P(None, "tensor")
            fn = jax.shard_map(
                kv_append_int8, mesh=mesh,
                in_specs=(fused_s, fused_s, P(), P(), P(), new_s, new_s,
                          P()),  # the mask is replicated
                out_specs=(fused_s, fused_s), check_vma=False)
        kv, s = fn(self.kv, self.s, jnp.asarray(row, jnp.int32), page_idx,
                   offset, codes, scales, live)
        return QuantPagePool(kv, s, self.page_size)

    def encode_pages(self, k, v):
        """K and V ([..., Hd]) as `write_pages` stores them: (k codes,
        k scales, v codes, v scales). A prefill calls it INSIDE its
        layer scan, so that the stacked bf16 K and V never materialize."""
        return self._quantize(k) + self._quantize(v)

    def write_pages(self, pages, table_flat) -> "QuantPagePool":
        """Scatter page-shaped codes ([L, KH, M, ps, Hd]) and scales
        ([L, KH, M, ps]), `pages` = encode_pages' four, into pages
        `table_flat` [M]. TWO scatters per array (k then v) with a
        scalar leading index: a single stacked [2, ...] update drives
        XLA to a transposed pool layout whose conversion copies the
        whole 3 GB pool (OOM); separate scatters with contiguous
        advanced indices keep the natural layout and alias in place."""
        kq, ks, vq, vs = pages
        li, kh, tb = _page_axes(*kq.shape[:2], table_flat)
        kv = self.kv.at[0, li, kh, tb].set(kq)
        kv = kv.at[1, li, kh, tb].set(vq)
        s = self.s.at[0, li, kh, tb].set(ks)
        s = s.at[1, li, kh, tb].set(vs)
        return QuantPagePool(kv, s, self.page_size)

    def read_pages(self, table_row, dtype):
        """PagePool.read_pages, dequantized with the narrow scales."""
        li, kh, tb = _page_axes(*self.kv.shape[1:3], table_row)
        k = (self.kv[0, li, kh, tb].astype(dtype)
             * self.s[0, li, kh, tb][..., None].astype(dtype))
        v = (self.kv[1, li, kh, tb].astype(dtype)
             * self.s[1, li, kh, tb][..., None].astype(dtype))
        return k, v

    def export_pages(self, table_row):
        """PagePool.export_pages: int8 codes and [n, 2, L, KH, ps]
        scales, untouched (no dequantize: import_pages scatters the
        exact bytes back)."""
        li, kh, tb = _page_axes(*self.kv.shape[1:3], table_row)
        codes = self.kv[:, li, kh, tb]  # [2, L, KH, n, ps, Hd]
        scales = self.s[:, li, kh, tb]  # [2, L, KH, n, ps]
        return jnp.moveaxis(codes, 3, 0), jnp.moveaxis(scales, 3, 0)

    def import_pages(self, codes, scales, table_row) -> "QuantPagePool":
        kq = jnp.moveaxis(codes[:, 0], 0, 2)  # [L, KH, n, ps, Hd]
        vq = jnp.moveaxis(codes[:, 1], 0, 2)
        ks = jnp.moveaxis(scales[:, 0], 0, 2)  # [L, KH, n, ps]
        vs = jnp.moveaxis(scales[:, 1], 0, 2)
        return self.write_pages((kq, ks, vq, vs), table_row)

    def move_tokens(self, src, dst) -> "QuantPagePool":
        """PagePool.move_tokens; codes and scales move verbatim (no
        requantization error)."""
        L, KH = self.kv.shape[1:3]
        kvi = jnp.arange(2)[:, None, None, None, None]
        li = jnp.arange(L)[None, :, None, None, None]
        kh = jnp.arange(KH)[None, None, :, None, None]

        def at(slots):
            return (kvi, li, kh) + tuple(t[None, None, None] for t in slots)

        kv, s = self.kv[at(src)], self.s[at(src)]
        return QuantPagePool(self.kv.at[at(dst)].set(kv),
                             self.s.at[at(dst)].set(s), self.page_size)

    @staticmethod
    def zeros(cfg: LlamaConfig, n_pages: int, page_size: int = 64,
              sharding=None, scale_sharding=None) -> "QuantPagePool":
        shape = (2, cfg.cache_rows, cfg.n_kv_heads, n_pages, page_size,
                 cfg.head_dim)
        kv = _alloc(shape, jnp.int8, sharding)
        s = _alloc(shape[:-1], jnp.float32, scale_sharding)
        return QuantPagePool(kv, s, page_size)


LANES = 128  # a DMA's slice of the minor dimension is tiled by this


def latent_lanes(latent_row) -> int:
    """The lanes a latent row (C, R) takes: whole tiles."""
    return -(-sum(latent_row) // LANES) * LANES


def latent_token_bytes(cfg, kv_dtype) -> int:
    """Bytes a device holds of ONE cached token in the `cfg.cache_rows`
    rows of a LatentPagePool."""
    return (cfg.cache_rows * latent_lanes(cfg.latent_row)
            * jnp.dtype(kv_dtype).itemsize)


@dataclasses.dataclass
class LatentPagePool:
    """The pool of a latent-attention model (cfg.latent_row = (C, R)):
    ONE row per cached token and layer, shared by all heads,
    `[c_kv (C) ; k_rope (R)]`, where PagePool holds K and V per head.
    `c` is [rows, P, page_size, W] in the model's type, W = C + R
    rounded up to 128 lanes: the TPU tiles the minor dimension by 128
    (a [.., 576] array takes the bytes of [.., 640] anyway) and the
    kernel's page DMA must slice whole tiles; the spare lanes stay zero.

    The decode kernel (serving/paged_attention_mla.py) takes the WHOLE
    array and the row, as the int8 kernel does: a slice of one row
    handed to a kernel would be copied out first. Only this class and
    that kernel index `c`. The lanes that read pages back, move them
    or share them (prefix reuse, the pager, the disaggregated transfer,
    the verifies' relocation, the long-prompt scratch cache) have no
    method here: LLMEngine refuses them by name for such a model."""

    c: jax.Array
    page_size: int

    @property
    def n_pages(self) -> int:
        return self.c.shape[1]

    @property
    def quantized(self) -> bool:
        return False

    @property
    def geometry(self) -> PoolGeometry:
        R, _, ps, W = self.c.shape
        return PoolGeometry(R, 1, ps, W, self.c.dtype)

    def devices(self):
        return self.c.devices()

    def attention_operands(self, row):
        """(pool, row) for paged_attention_mla_dispatch."""
        return self.c, row

    def _padded(self, x):
        """[..., C + R] in the pool's type and width."""
        spare = self.c.shape[-1] - x.shape[-1]
        return jnp.pad(x.astype(self.c.dtype),
                       [(0, 0)] * (x.ndim - 1) + [(0, spare)])

    def append(self, row, slots, c_new) -> "LatentPagePool":
        """A step's new rows `c_new` [B, C + R] into cache row `row` at
        the slots' (page, offset): one scatter, the scalar row and the
        two index vectors adjacent."""
        c = self.c.at[row, slots.page_idx, slots.offset, :].set(
            self._padded(c_new))
        return dataclasses.replace(self, c=c)

    def encode_pages(self, c):
        return self._padded(c)

    def write_pages(self, pages, table_flat) -> "LatentPagePool":
        """Page-shaped rows [rows, M, ps, W] (encode_pages') into pages
        `table_flat` [M]."""
        li = jnp.arange(pages.shape[0])[:, None]
        return dataclasses.replace(
            self, c=self.c.at[li, table_flat[None, :]].set(pages))

    @staticmethod
    def zeros(cfg, n_pages: int, page_size: int = 64, dtype=None,
              sharding=None) -> "LatentPagePool":
        shape = (cfg.cache_rows, n_pages, page_size,
                 latent_lanes(cfg.latent_row))
        return LatentPagePool(_alloc(shape, jnp.dtype(dtype or cfg.dtype),
                                     sharding), page_size)


@dataclasses.dataclass
class HybridPool:
    """The pool of a model with recurrent state (cfg.recurrent_state):
    two kinds of state in one donated tree.

    `pages` is the page pool of the ATTENTION layers' rows (cfg.cache_rows
    of them; a PagePool or a QuantPagePool, with every method those have,
    or a LatentPagePool where those layers cache a latent row); the page
    allocator, the page tables and the attention kernels see only it.
    `state` [Ls, slots, H, P, N] float32 and `tail` [Ls, T,
    slots, W] (the convolution's last T inputs, oldest first, in the
    model's type; the slots before the channels so that T = 3 is not
    padded to a tile) belong to DECODE SLOTS, not to pages: slot b's rows
    are whatever sequence occupies slot b. Nothing allocates them: a
    prefill writes a slot's rows whole (`write_slots`), so a reused slot
    never sees its predecessor's, and a decode step updates them in place
    (`state` through serving/ssm_state_update.py, or
    serving/kda_state_update.py for a delta-rule layer's).

    The lanes that re-read, share, move, snapshot or roll back cache
    (prefix reuse, the pager, the disaggregated transfer, speculation,
    the long-prompt scratch cache) would have to carry this state too and
    do not: LLMEngine refuses them by name for such a model."""

    pages: "PagePool | QuantPagePool | LatentPagePool"
    state: jax.Array
    tail: jax.Array

    @property
    def page_size(self) -> int:
        return self.pages.page_size

    @property
    def n_pages(self) -> int:
        return self.pages.n_pages

    @property
    def quantized(self) -> bool:
        return self.pages.quantized

    @property
    def geometry(self) -> PoolGeometry:
        return self.pages.geometry

    @property
    def slots(self) -> int:
        return self.state.shape[1]

    def devices(self):
        return self.pages.devices()

    def write_slots(self, slots, state, tail) -> "HybridPool":
        """A prefill's end: `state` [Ls, n, H, P, N] and `tail` [Ls, n, T,
        W] into decode slots `slots` [n], whole (an index past the last
        slot is dropped: a padding row). One scatter an array, every
        advanced index adjacent and leading."""
        Ls, n = state.shape[:2]
        li = jnp.arange(Ls)[:, None]
        new = self.state.at[li, slots[None, :]].set(
            state.astype(self.state.dtype), mode="drop")
        T = tail.shape[2]
        li3 = jnp.arange(Ls)[:, None, None]
        ti = jnp.arange(T)[None, :, None]
        new_tail = self.tail.at[li3, ti, slots[None, None, :]].set(
            tail.transpose(0, 2, 1, 3).astype(self.tail.dtype), mode="drop")
        return dataclasses.replace(self, state=new, tail=new_tail)

    @staticmethod
    def zeros(cfg, n_pages: int, page_size: int, dtype,
              slots: int, pages=None) -> "HybridPool":
        """`pages`: the attention layers' pool where it is not K and V a
        head (a LatentPagePool)."""
        rs = cfg.recurrent_state
        return HybridPool(
            pages if pages is not None
            else kv_pool_zeros(cfg, n_pages, page_size, dtype),
            _alloc((rs.layers, slots, rs.heads, rs.head_dim, rs.state),
                   jnp.float32, None),
            _alloc((rs.layers, rs.tail, slots, rs.conv_width), cfg.dtype,
                   None))


@dataclasses.dataclass
class SparseIndexPool:
    """The pool of a model with learned sparse attention (cfg.index_row):
    three kinds of row under ONE page table in one donated tree.

    `pages` is the QuantPagePool of K and V (every method it has; the
    attention kernel serving/paged_attention_sparse.py reads it as
    paged_attention_int8 does). `idx` [R, P, Di, page_size] bf16 holds the
    indexer's key of every cached token, a page TRANSPOSED (the values
    before the tokens): a page is then [Di, 128], whole 128-lane tiles (a
    token-major [128, 64] page would be padded to 128 lanes and take twice
    its bytes), contiguous, and the operand `q @ page` takes as it lies.
    Page p of `idx` belongs to whoever holds page p of `pages`: nothing
    allocates or frees it apart.

    The lanes that re-read, share, move, snapshot or roll back cache
    (prefix reuse, the pager, the disaggregated transfer, speculation,
    the long-prompt scratch cache) would have to carry the index rows too
    and do not: LLMEngine refuses them by name for such a model."""

    pages: "QuantPagePool"
    idx: jax.Array

    @property
    def page_size(self) -> int:
        return self.pages.page_size

    @property
    def n_pages(self) -> int:
        return self.pages.n_pages

    @property
    def quantized(self) -> bool:
        return True

    @property
    def geometry(self) -> PoolGeometry:
        return self.pages.geometry

    def devices(self):
        return self.pages.devices()

    def attention_operands(self, row):
        return self.pages.attention_operands(row)

    def append(self, row, slots, k_new, v_new, ki_new) -> "SparseIndexPool":
        """QuantPagePool.append (its kernel and live list where they are
        on) and, with it, the step's new index keys `ki_new` [B, Di] at
        the same (page, offset): each slot's page is read, the one column
        patched and the page written back whole, B index tuples where a
        scatter of single values would take B x Di. An idle slot's page is
        the sink."""
        pages = self.pages.append(row, slots, k_new, v_new)
        page = self.idx[row, slots.page_idx]              # [B, Di, ps]
        at = jnp.arange(page.shape[-1])[None, None, :] \
            == slots.offset[:, None, None]
        page = jnp.where(at, ki_new.astype(page.dtype)[:, :, None], page)
        return SparseIndexPool(pages,
                               self.idx.at[row, slots.page_idx].set(page))

    def encode_pages(self, k, v, ki):
        """K and V ([..., Hd]) and index keys [..., ps, Di] as
        `write_pages` stores them."""
        return self.pages.encode_pages(k, v) + (
            jnp.swapaxes(ki, -1, -2).astype(self.idx.dtype),)

    def write_pages(self, pages, table_flat) -> "SparseIndexPool":
        """QuantPagePool.write_pages' four and the index pages
        [R, M, Di, ps] into pages `table_flat` [M]."""
        *kv, ki = pages
        li = jnp.arange(ki.shape[0])[:, None]
        return SparseIndexPool(
            self.pages.write_pages(tuple(kv), table_flat),
            self.idx.at[li, table_flat[None, :]].set(ki))

    @staticmethod
    def zeros(cfg, n_pages: int, page_size: int) -> "SparseIndexPool":
        return SparseIndexPool(
            QuantPagePool.zeros(cfg, n_pages, page_size),
            _alloc((cfg.cache_rows, n_pages, cfg.index_row, page_size),
                   jnp.bfloat16, None))


class WindowTables(NamedTuple):
    """A WindowPool's page tables as the step programs take them where
    every other pool's take one array. `glob` is that array, for the
    global rows. A decode block's `win` [B, window_table_pages] is a
    slot's window table, the pages it holds oldest first, and `base` [B]
    the position of the first token of its first page (a multiple of the
    page size): token t of the sequence lies in `win[(t - base) // ps]`.
    A prefill's `win` [N, S // ps] is indexed like `glob`, the pages
    behind the window pointing at the sink, and `base` is None."""

    glob: jax.Array
    win: jax.Array
    base: Optional[jax.Array] = None


@dataclasses.dataclass
class WindowPool:
    """The pool of a model with window layers beside global ones
    (cfg.window_rows): two int8 pools in one donated tree, `glob` with a
    row a global layer and `win` with a row a window layer
    (window_attn_moe.layer_plan maps a layer to its row), each with its
    own number of pages, its own PageAllocator and its own page table a
    sequence (WindowTables). A page id means nothing across the two.

    The lanes that re-read, share, move, snapshot or roll back cache
    (prefix reuse, the pager, the disaggregated transfer, speculation,
    the long-prompt scratch cache) know one table a sequence and pages
    that are never given back while it lives: LLMEngine refuses them by
    name for such a model."""

    glob: "QuantPagePool"
    win: "QuantPagePool"

    @property
    def page_size(self) -> int:
        return self.glob.page_size

    @property
    def n_pages(self) -> int:
        return self.glob.n_pages

    @property
    def quantized(self) -> bool:
        return True

    @property
    def geometry(self) -> PoolGeometry:
        """Of a page of either group's row; `rows` counts both."""
        g = self.glob.geometry
        return g._replace(rows=g.rows + self.win.geometry.rows)

    def devices(self):
        return self.glob.devices()

    @staticmethod
    def zeros(cfg, n_pages: int, n_window_pages: int,
              page_size: int) -> "WindowPool":
        wr = cfg.window_rows

        def rows(n_rows, pages):
            shape = (2, n_rows, cfg.n_kv_heads, pages, page_size,
                     cfg.head_dim)
            return QuantPagePool(_alloc(shape, jnp.int8, None),
                                 _alloc(shape[:-1], jnp.float32, None),
                                 page_size)

        return WindowPool(rows(wr.n_global, n_pages),
                          rows(wr.n_window, n_window_pages))


def window_table_pages(window: int, page_size: int, ahead: int) -> int:
    """Pages a sequence's window table holds at most: those that reach
    into a window of `window` tokens, at any alignment (window //
    page_size + 1 where the window starts inside a page), and `ahead`
    tokens more, written by decode blocks that were dispatched before
    the page behind them was released (a block of K steps, `depth` of
    them in flight: ahead = depth * K; a run of K tokens that straddles
    a page boundary takes the one page more)."""
    return -(-(window + ahead) // page_size) + 1


def engine_window_table_pages(window: int, ecfg) -> int:
    """window_table_pages for an engine configuration: its decode blocks
    of K steps, `pipeline_depth` of them in flight."""
    return window_table_pages(
        window, ecfg.page_size, max(1, ecfg.pipeline_depth)
        * max(1, ecfg.decode_steps_per_dispatch))


def window_pool_pages(window: int, ecfg) -> int:
    """Pages of a WindowPool's window rows for an engine configuration:
    every slot's table full, one sequence of slack (a retired slot's
    pages free when its parked block lands) and the sink."""
    return (ecfg.max_batch_size + 1) \
        * engine_window_table_pages(window, ecfg) + 1


jax.tree_util.register_dataclass(
    WindowPool, data_fields=["glob", "win"], meta_fields=[]
)
jax.tree_util.register_dataclass(
    SparseIndexPool, data_fields=["pages", "idx"], meta_fields=[]
)
jax.tree_util.register_dataclass(
    HybridPool, data_fields=["pages", "state", "tail"], meta_fields=[]
)
jax.tree_util.register_dataclass(
    PagePool, data_fields=["k", "v"], meta_fields=["page_size"]
)
jax.tree_util.register_dataclass(
    LatentPagePool, data_fields=["c"], meta_fields=["page_size"]
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

    def __init__(self, n_pages: int, name: str = "KV"):
        self.n_pages = n_pages
        self.name = name  # which pool's pages, in alloc's MemoryError
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
            raise MemoryError(f"{self.name} page pool exhausted: want {n}, "
                              f"have {len(self._free)} of {self.n_pages}")
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


class WindowSequencePages(SequencePages):
    """SequencePages for a model with window layers: the global rows'
    pages as every sequence holds them (`pages`, `table_row`), and beside
    them the WINDOW rows' pages from a second allocator: only those that
    reach into `[length - window, length)`. `window_first` is the index,
    in the sequence, of the first page held; `slide` gives the pages
    behind a position back. A live sequence holds at most
    `max_window_pages` of them (window_table_pages)."""

    def __init__(self, allocator: PageAllocator,
                 window_allocator: PageAllocator, page_size: int,
                 max_pages: int, window: int, max_window_pages: int):
        super().__init__(allocator, page_size, max_pages)
        self.window_allocator = window_allocator
        self.window = window
        self.max_window_pages = max_window_pages
        self.window_pages: List[int] = []
        self.window_first = 0

    def adopt(self, pages, n_tokens):
        raise NotImplementedError("a prefix-cache hit has no meaning for a "
                                  "window row's pages yet")

    def ensure(self, new_length: int) -> None:
        """Grow BOTH page lists to cover new_length tokens. The first
        call (a prompt's) takes no window page that lies wholly behind
        the window of the first decode step, which is at new_length."""
        ps = self.page_size
        if not self.window_pages:
            self.window_first = max(0, new_length + 1 - self.window) // ps
        more = -(-new_length // ps) - self.window_first \
            - len(self.window_pages)
        if len(self.window_pages) + more > self.max_window_pages:
            raise MemoryError(
                f"sequence needs {len(self.window_pages) + more} window "
                f"pages > its table's {self.max_window_pages}")
        if more > 0:
            self.window_pages.extend(self.window_allocator.alloc(more))
        super().ensure(new_length)

    def slide(self, start: int) -> int:
        """Release the window pages that lie wholly before token `start`
        (never the last one). Returns how many went."""
        n = min(max(0, start) // self.page_size - self.window_first,
                len(self.window_pages) - 1)
        if n <= 0:
            return 0
        gone, self.window_pages = self.window_pages[:n], self.window_pages[n:]
        self.window_first += n
        self.window_allocator.release(gone)
        return n

    def window_row(self):
        """(the window table's row [max_window_pages], padding -> page 0;
        the position of its first page's first token)."""
        row = np.zeros((self.max_window_pages,), np.int32)
        row[: len(self.window_pages)] = self.window_pages
        return row, self.window_first * self.page_size

    def release(self) -> None:
        pages, self.window_pages = self.window_pages, []
        if pages:
            self.window_allocator.release(pages)
        super().release()
