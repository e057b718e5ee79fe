"""Paged decode attention: XLA gather fallback + Pallas TPU kernels.

The decode hot op (SURVEY.md §7.4 hard part #1): one new query token per
sequence attends over that sequence's KV pages. Layouts (per layer):

  q        [B, H, Hd]           one token per sequence
  k_pages  [KH, P, ps, Hd]      device page pool slice for this layer
  page_table [B, maxp] int32    page ids per sequence (0 = padding sink)
  lengths  [B] int32            valid tokens (incl. the new one)

Kernel strategy (r2): the one-page-per-grid-step kernel paid a fixed
per-grid-step cost x (B * maxp * L) steps, which dominated decode at
batch >= 32 (VERDICT r1 weak #1c). Dispatch now prefers the multi-page
JetStream-style kernel shipped with JAX
(jax.experimental.pallas.ops.tpu.paged_attention — pages stream
HBM->VMEM via double-buffered async copies, `pages_per_compute_block`
pages per flash block, grid (B, KH) instead of (B, maxp)); our own
single-page kernel remains as the in-repo fallback and the
interpret-mode (CPU) oracle for it.

Under a multi-device mesh the chosen kernel runs inside a shard_map over
the "tensor" axis: attention is head-parallel in the Megatron layout
(q heads and kv heads/pages both sharded on tensor), no collectives.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.paged_attention import (
    paged_attention as _stdlib_paged_attention)

from generativeaiexamples_tpu.utils.platform import log_kernel_declined

NEG_INF = -1e30

# own | stdlib | auto (benchmark knob; auto prefers the multi-page
# stdlib kernel on TPU when page counts allow it)
_KERNEL_CHOICE = os.environ.get("ENGINE_PAGED_KERNEL", "auto")


def paged_attention_reference(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, lengths: jax.Array, *, scale: Optional[float] = None,
    starts: Optional[jax.Array] = None,
) -> jax.Array:
    """Gather-based paged attention (any backend; the numerics oracle).
    `starts` [B]: the first token a row sees (a window row's, counted
    from its table's first page as `lengths` is); None: every token."""
    B, H, Hd = q.shape
    KH, P, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    scale = scale if scale is not None else Hd ** -0.5

    # [KH, B, maxp, ps, Hd] -> [B, KH, maxp*ps, Hd]
    k = k_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        B, KH, maxp * ps, Hd)
    v = v_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        B, KH, maxp * ps, Hd)

    from generativeaiexamples_tpu.ops.attention import mha_reference

    out = mha_reference(q[:, :, None, :], k, v, causal=False, lengths=lengths,
                        scale=scale, kv_start=starts)
    return out[:, :, 0, :]


def _tree_attention_core(q, k, v, lengths, anc_mask, scale):
    """Shared tree-verify attention math over GATHERED pool rows.

    q [B, H, r, Hd]: r packed tree positions whose k/v were just
    written (write-then-attend) at pool slots lengths-1 .. lengths-2+r.
    k/v [B, KH, S, Hd] are the sequence's gathered pages. Node j
    attends the committed prefix (slots < lengths-1) plus its
    ancestor-or-self chain inside the tree (anc_mask [r, r], a static
    bool array — row j marks j's ancestors). Same fp32-softmax recipe
    as mha_reference so tree targets match the linear verify path's
    numerics as closely as the mask allows."""
    B, H, r, Hd = q.shape
    S = k.shape[2]
    from generativeaiexamples_tpu.ops.attention import _gqa_expand

    k = _gqa_expand(k, H)
    v = _gqa_expand(v, H)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    col = jnp.arange(S)[None, :]                 # [1, S]
    rel = col - (lengths - 1)[:, None]           # [B, S] slot - root slot
    prefix_ok = rel < 0                          # committed prefix
    in_tree = (rel >= 0) & (rel < r)
    anc = jnp.asarray(anc_mask, dtype=bool)      # [r, r] static
    anc_cols = anc[:, jnp.clip(rel, 0, r - 1)]   # [r, B, S]
    tree_ok = in_tree[:, None, :] & anc_cols.transpose(1, 0, 2)  # [B, r, S]
    mask = (prefix_ok[:, None, :] | tree_ok)[:, None, :, :]      # [B,1,r,S]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_tree_attention_reference(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, lengths: jax.Array, anc_mask, *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Tree-verify attention over the bf16 page pool (any backend):
    gather-based like paged_attention_reference, plus the packed
    tree-attention mask (see _tree_attention_core). This is the
    ORACLE and the non-TPU fallback for the Pallas tree kernel in
    serving/paged_attention_tree.py, which applies the same mask
    inside the paged flash-block loop instead of materializing
    gathered KV (dispatch rule in that module's docstring)."""
    B, H, r, Hd = q.shape
    KH = k_pages.shape[0]
    ps = k_pages.shape[2]
    maxp = page_table.shape[1]
    scale = scale if scale is not None else Hd ** -0.5
    k = k_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        B, KH, maxp * ps, Hd)
    v = v_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        B, KH, maxp * ps, Hd)
    return _tree_attention_core(q, k, v, lengths, anc_mask, scale)


def paged_tree_attention_int8_reference_fused(
    q: jax.Array, kv_pages: jax.Array, kv_scales: jax.Array,
    page_table: jax.Array, lengths: jax.Array, anc_mask, *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Tree-verify twin over ONE layer's fused int8 pool slice
    ([2, KH, P, ps, Hd] codes + [2, KH, P, ps] narrow scales):
    gather-THEN-dequantize — only the batch's pages are ever widened
    to f32, never the whole pool (the whole-pool dequant of the int8
    oracle would be a multi-GB materialization per layer here)."""
    B, H, r, Hd = q.shape
    KH = kv_pages.shape[1]
    ps = kv_pages.shape[3]
    maxp = page_table.shape[1]
    scale = scale if scale is not None else Hd ** -0.5

    def deq(i):
        codes = kv_pages[i][:, page_table]          # [KH, B, maxp, ps, Hd]
        s = kv_scales[i][:, page_table]             # [KH, B, maxp, ps]
        x = codes.astype(jnp.float32) * s[..., None].astype(jnp.float32)
        return x.transpose(1, 0, 2, 3, 4).reshape(B, KH, maxp * ps, Hd)

    return _tree_attention_core(q, deq(0), deq(1), lengths, anc_mask, scale)


# ---------------------------------------------------------------------------
# In-repo Pallas kernel (single page per grid step; interpret-friendly)
# ---------------------------------------------------------------------------


def _paged_kernel(
    lengths_ref,  # scalar prefetch [B]
    table_ref,  # scalar prefetch [B * maxp]
    q_ref,  # [1, H, Hd]
    k_ref,  # [KH, 1, ps, Hd]  (page selected by index_map)
    v_ref,
    o_ref,  # [1, H, Hd]
    m_ref,  # scratch [H, 128]
    l_ref,  # scratch [H, 128]
    acc_ref,  # scratch [H, Hd]
    *,
    scale: float,
    page_size: int,
    max_pages: int,
    n_kv_heads: int,
    group: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    @pl.when(p * page_size < length)
    def _body():
        KH, ps = n_kv_heads, page_size
        H = KH * group
        q = q_ref[0].astype(jnp.float32).reshape(KH, group, -1)  # [KH,g,Hd]
        k = k_ref[:, 0].astype(jnp.float32)  # [KH, ps, Hd]
        v = v_ref[:, 0].astype(jnp.float32)
        # Batched over kv heads: [KH, g, Hd] x [KH, ps, Hd] -> [KH, g, ps]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        pos = p * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = pos < length
        s = jnp.where(valid, s, NEG_INF)

        s2 = s.reshape(H, ps)
        valid2 = valid.reshape(H, ps)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(valid2, jnp.exp(s2 - m_new), 0.0)  # [H, ps]
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(pexp, axis=1, keepdims=True),
            l_ref.shape)
        # [KH, g, ps] x [KH, ps, Hd] -> [KH, g, Hd]
        pv = jax.lax.dot_general(
            pexp.reshape(KH, group, ps), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv.reshape(H, -1)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == max_pages - 1)
    def _finish():
        denom = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, lengths: jax.Array, *,
    scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """In-repo Pallas paged decode attention (see module docstring)."""
    B, H, Hd = q.shape
    KH, P, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    group = H // KH
    scale = scale if scale is not None else Hd ** -0.5

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=ps, max_pages=maxp,
        n_kv_heads=KH, group=group,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, H, Hd), lambda b, p, L, T: (b, 0, 0)),
            pl.BlockSpec((KH, 1, ps, Hd),
                         lambda b, p, L, T: (0, T[b * maxp + p], 0, 0)),
            pl.BlockSpec((KH, 1, ps, Hd),
                         lambda b, p, L, T: (0, T[b * maxp + p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Hd), lambda b, p, L, T: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.reshape(-1).astype(jnp.int32),
      q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _pages_per_block(maxp: int, want: Optional[int]) -> int:
    """Largest divisor of maxp that is <= want (default 8)."""
    want = want or 8
    for g in range(min(want, maxp), 0, -1):
        if maxp % g == 0:
            return g
    return 1


def _paged_tpu(q, k_pages, v_pages, page_table, lengths, *, scale,
               interpret, pages_per_compute_block):
    maxp = page_table.shape[1]
    Hd = q.shape[-1]
    # The stdlib kernel tiles its softmax-state outputs on (groups, Hd)
    # blocks and requires head_dim % 128 == 0 — llama3.2-1b (Hd=64)
    # lowers to a BlockSpec error. Our single-page kernel handles any
    # (8-aligned) head_dim, so geometry gates the choice.
    use_stdlib = (not interpret and Hd % 128 == 0
                  and _KERNEL_CHOICE in ("auto", "stdlib"))
    if use_stdlib:
        ppcb = _pages_per_block(maxp, pages_per_compute_block)
        # The stdlib kernel applies no softmax scale — fold it into q.
        Hd = q.shape[-1]
        s = scale if scale is not None else Hd ** -0.5
        return _stdlib_paged_attention(
            (q.astype(jnp.float32) * s).astype(q.dtype),
            k_pages, v_pages, lengths.astype(jnp.int32),
            page_table.astype(jnp.int32), pages_per_compute_block=ppcb)
    return paged_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=scale, interpret=interpret)


def _paged_tpu_int8(q, kv_pages, kv_scales, page_table, lengths, layer,
                    live=None, starts=None, new=None, *, scale,
                    pages_per_compute_block):
    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        paged_attention_int8, paged_attention_int8_reference_fused,
        paged_attention_int8_window)

    ps, Hd = kv_pages.shape[-2], kv_pages.shape[-1]
    # Mosaic DMA slices must be 128-lane aligned: the kernel needs
    # page_size % 128 == 0 (scale pages are (1, ps) f32 tiles) and
    # head_dim % 128 == 0. int8 serving configs use page_size=128.
    if ps % 128 == 0 and Hd % 128 == 0:
        kw = dict(scale=scale, live=live, new=new,  # -> (out, pool) with it
                  pages_per_compute_block=pages_per_compute_block)
        if starts is not None:
            return paged_attention_int8_window(
                q, kv_pages, kv_scales, page_table, lengths, layer, starts,
                **kw)
        return paged_attention_int8(
            q, kv_pages, kv_scales, page_table, lengths, layer, **kw)
    assert new is None, "kv_cache.kernel_append: the same two multiples"
    log_kernel_declined(
        "paged_attention_int8", "the XLA gather reference",
        f"page_size {ps} and head_dim {Hd} must both be multiples of 128")
    return paged_attention_int8_reference_fused(
        q, kv_pages[:, layer], kv_scales[:, layer], page_table, lengths,
        scale=scale, starts=starts)


def paged_attention_dispatch(
    q, k_pages, v_pages, page_table, lengths, *, scale=None,
    k_scales=None, layer=None,
    use_pallas: Optional[bool] = None, mesh=None, interpret: bool = False,
    pages_per_compute_block: Optional[int] = None,
    live=None, starts=None, new=None,
):
    """Pick the fastest available implementation for the current
    backend/mesh. `lengths` INCLUDES the current token, whose k/v must
    already be written to the pool (write-then-attend decode), unless
    the caller hands it over as `new` (codes [2, KH, B, Hd], scales [2,
    KH, B]; only where kv_cache.kernel_append holds, so that the form is
    the int8 kernel, which then writes the row itself): the result is
    then (out, the pool's codes, its scales). `live`
    (kv_cache.kernel_live_rows of the step's `active` mask, or None):
    the rows the int8 kernel walks, an idle row's output zeros; no other
    form reads it. `starts` [B] (a WINDOW row of an int8 pool on one
    chip, kv_cache.WindowPool): `page_table` is then the sequence's
    window table and `lengths` and `starts` count from its first page;
    the row attends tokens [starts, lengths).

    Quantized (fused) form: `v_pages=None`, `k_pages` holds the FULL
    fused int8 pool [2, L, KH, P, ps, Hd], `k_scales` the full narrow
    scales [2, L, KH, P, ps] (kv_cache.QuantPagePool) and `layer` the
    layer to attend over — the layer is indexed inside the kernel's DMA
    descriptors because host-side slicing of the kv-leading layout is
    non-contiguous (32 materialized copies, OOM)."""
    quantized = k_scales is not None
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    assert new is None or (quantized and use_pallas and starts is None), (
        "the new row rides the int8 kernel alone")
    if not use_pallas:
        if quantized:
            from generativeaiexamples_tpu.serving.paged_attention_int8 import (
                paged_attention_int8_reference_fused)

            return paged_attention_int8_reference_fused(
                q, k_pages[:, layer], k_scales[:, layer], page_table,
                lengths, scale=scale, starts=starts)
        assert starts is None, "a window row lives in an int8 pool"
        return paged_attention_reference(q, k_pages, v_pages, page_table,
                                         lengths, scale=scale)
    assert starts is None or (quantized and mesh is None), (
        "a window row lives in an int8 pool on one chip")
    if mesh is not None and mesh.shape.get("tensor", 1) > 1:
        from jax.sharding import PartitionSpec as P

        hs = P(None, "tensor", None)
        if quantized:
            # Full fused pool [2, L, KH, P, ...]: kv-heads (the TP
            # axis) at axis 2.
            fused_s = P(None, None, "tensor")
            # ... and at axis 1 of a new row's codes and scales
            new_s = None if new is None else P(None, "tensor")
            fn = jax.shard_map(
                functools.partial(
                    _paged_tpu_int8, scale=scale,
                    pages_per_compute_block=pages_per_compute_block),
                mesh=mesh,  # the mask is replicated, as the tables are
                in_specs=(hs, fused_s, fused_s, P(), P(), P(), P(), None,
                          new_s),
                out_specs=hs if new is None else (hs, fused_s, fused_s),
                check_vma=False)
            return fn(q, k_pages, k_scales, page_table, lengths,
                      jnp.asarray(layer, jnp.int32), live, None, new)
        pool_s = P("tensor", None, None, None)
        fn = jax.shard_map(
            lambda q_, kp_, vp_, t_, ln_: _paged_tpu(
                q_, kp_, vp_, t_, ln_, scale=scale, interpret=interpret,
                pages_per_compute_block=pages_per_compute_block),
            mesh=mesh, in_specs=(hs, pool_s, pool_s, P(), P()),
            out_specs=hs, check_vma=False)
        return fn(q, k_pages, v_pages, page_table, lengths)
    if quantized:
        return _paged_tpu_int8(q, k_pages, k_scales, page_table, lengths,
                               layer, live, starts, new, scale=scale,
                               pages_per_compute_block=pages_per_compute_block)
    return _paged_tpu(q, k_pages, v_pages, page_table, lengths, scale=scale,
                      interpret=interpret,
                      pages_per_compute_block=pages_per_compute_block)
