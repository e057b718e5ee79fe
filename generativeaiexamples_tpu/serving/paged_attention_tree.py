"""Paged TREE-VERIFY attention: Pallas TPU kernels + dispatch.

Tree speculation (engine.speculative_tree_branches, PR 6) verifies an
M-branch, depth-k n-gram lattice in one widened decode step: the r =
1 + M*k packed tree nodes sit at pool slots lengths-1 .. lengths-2+r
(write-then-attend) and node j attends the committed prefix plus its
ancestor-or-self chain (engine_model._tree_layout). Until this module
the tree path always took the XLA gather route
(paged_attention.paged_tree_attention_reference): every verify step
materialized the batch's gathered KV — maxp*ps tokens per row
regardless of true length — so the widened step that exists to be
HBM-efficient paid MORE pool traffic than linear decode.

Here the ancestor mask is applied INSIDE the paged flash-block loop:

- bf16/f32 pools: `paged_tree_attention` below — same double-buffered
  multi-page HBM->VMEM streaming as the linear int8 kernel (grid (B,),
  a fori_loop over compute blocks of `pages_per_compute_block` pages,
  the next block's async copies in flight while the current one
  computes; 2 DMA descriptors per page — one k, one v — each covering
  all kv heads). Only `length + r - 1` tokens of KV move, not maxp*ps.
- int8 pools: the twin rides the existing fused-pool kernel —
  paged_attention_int8(..., q_rep=r, tree=(k, M)) streams k AND v
  codes+scales with the linear verify path's 2-descriptors-per-page
  layout; the tree only edits the in-kernel mask, never the traffic.

The mask is not a table: _tree_layout's lattice is regular (node
1 + m*k + (d-1) is branch m's depth-d draft), so ancestor-or-self is
ARITHMETIC in the node indices (same branch, depth <=) and the whole
mask costs a handful of iota compares per flash block
(paged_attention_int8._tree_keep — Pallas kernels cannot capture
vector constants, and none is needed).

Dispatch rule (the tree-path sibling of paged_attention's
own|stdlib|auto note): Pallas on single-device TPU when the geometry
allows it (head_dim % 128 == 0 and page_size % 128 == 0 — Mosaic's
128-lane DMA alignment, the linear int8 kernel's gate); everywhere
else — CPU, meshes with tensor > 1, odd geometries — the XLA
references in paged_attention.py stay the oracle and the fallback,
and CPU CI pins bit-level commit semantics against them.
ENGINE_TREE_KERNEL=0 forces the reference route on TPU;
ENGINE_TREE_KERNEL_INTERPRET=1 forces the Pallas kernels in interpret
mode on any backend (the CPU parity suite's hook). Both dispatchers
fall back to the reference when the provided ancestor mask is not the
canonical _tree_layout lattice for (k, n_branches) — the arithmetic
mask is exact only for that shape.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.serving.paged_attention import _pages_per_block
from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    _tree_keep, paged_attention_int8)
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

NEG_INF = -1e30


def _interpret_forced() -> bool:
    """ENGINE_TREE_KERNEL_INTERPRET=1: run the Pallas tree kernels in
    interpret mode regardless of backend/geometry — the CPU parity
    suite's dispatch hook (read at trace time; tests that flip it
    clear jit caches first)."""
    return os.environ.get("ENGINE_TREE_KERNEL_INTERPRET", "0") == "1"


@functools.lru_cache(maxsize=None)
def _canonical_tree(k: int, n_branches: int):
    """The [r, r] ancestor-or-self mask _tree_keep's arithmetic
    reproduces — must equal engine_model._tree_layout for the kernel
    route to be sound (checked per dispatch; both are tiny numpy)."""
    r = 1 + n_branches * k
    n = np.arange(r)
    branch = np.maximum(n - 1, 0) // k
    depth = np.where(n == 0, 0, np.maximum(n - 1, 0) % k + 1)
    anc = (n[None, :] == 0) | (
        (n[:, None] > 0) & (n[None, :] > 0)
        & (branch[:, None] == branch[None, :])
        & (depth[None, :] <= depth[:, None]))
    return anc


def tree_shape_of(anc_mask, k: int, n_branches: int) -> Optional[Tuple]:
    """(k, n_branches) when `anc_mask` is the canonical packed lattice
    for those parameters (the only shape the arithmetic in-kernel mask
    reproduces), else None — the dispatchers' kernel-eligibility test."""
    anc = np.asarray(anc_mask, bool)
    r = 1 + n_branches * k
    if anc.shape != (r, r):
        return None
    if not np.array_equal(anc, _canonical_tree(k, n_branches)):
        return None
    return (k, n_branches)


# ---------------------------------------------------------------------------
# bf16/f32 TPU kernel (separate k/v pools, multi-page double-buffered)
# ---------------------------------------------------------------------------


def _copy_block(tables_ref, hbm, buf, sem, b, i, slot, *, ppcb, maxp):
    """Async copies for compute block i of row b into buffer `slot`:
    one descriptor per page covering all kv heads (hbm.at[:, pid]).
    Returns the descriptors (recreate-and-wait pattern: semaphores
    count bytes, so identical descriptors built later can wait)."""
    copies = []
    for j in range(ppcb):
        pid = tables_ref[b * maxp + i * ppcb + j]
        copies.append(pltpu.make_async_copy(
            hbm.at[:, pid], buf.at[slot, j], sem.at[slot]))
    return copies


def _tree_kernel(
    lengths_ref,   # scalar prefetch [B]
    tables_ref,    # scalar prefetch [B * maxp]
    buf_idx_ref,   # scalar prefetch [1] — persists ACROSS grid steps
    init_ref,      # scalar prefetch [1] — 1 on the very first grid step
    q_ref,         # [1, KH, G, Hd] f32 (scale pre-folded, j-major rows)
    k_hbm,         # [KH, P, ps, Hd] (ANY) — ONE layer's pool slice
    v_hbm,         # [KH, P, ps, Hd] (ANY)
    o_ref,         # [1, KH, G, Hd]
    k_buf,         # VMEM [2, ppcb, KH, ps, Hd] pool dtype
    v_buf,         # VMEM [2, ppcb, KH, ps, Hd]
    sem,           # DMA sems [2]
    *,
    ppcb: int,
    maxp: int,
    page_size: int,
    batch_size: int,
    tree: Tuple[int, int],   # (k, n_branches) static
    group: int,              # q heads per kv head
):
    """One grid step per BATCH ROW — the linear int8 kernel's shape
    (cross-grid-step double buffering, recreate-and-wait descriptors,
    2 per page) over separate bf16/f32 k/v pools, with the linear
    length mask replaced by the packed tree mask: query row
    g_row = j*group + gg sits at pool slot lengths-1+j and attends
    pos < lengths-1 (committed prefix) plus the tree slots its
    ancestor chain allows (paged_attention_int8._tree_keep)."""
    b = pl.program_id(0)
    ps = page_size
    bk = ppcb * ps
    r = 1 + tree[0] * tree[1]
    length = lengths_ref[b]
    span = length + (r - 1)  # kv slots the deepest node sees
    nblk = lax.div(span + bk - 1, bk)
    KH, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]

    def copies(bb, i, slot):
        return (_copy_block(tables_ref, k_hbm, k_buf, sem, bb, i, slot,
                            ppcb=ppcb, maxp=maxp)
                + _copy_block(tables_ref, v_hbm, v_buf, sem, bb, i, slot,
                              ppcb=ppcb, maxp=maxp))

    def next_block(i):
        return lax.cond(i * bk < span,
                        lambda: (b, i),
                        lambda: (b + 1, jnp.int32(0)))

    @pl.when(init_ref[0] == 1)
    def _first():
        init_ref[0] = 0
        for c in copies(b, 0, buf_idx_ref[0]):
            c.start()

    q = q_ref[0].astype(jnp.float32)  # [KH, G, Hd]

    def body(i, carry):
        slot = buf_idx_ref[0]
        nxt_b, nxt_i = next_block(i + 1)

        @pl.when(nxt_b < batch_size)
        def _prefetch():
            nslot = 1 - slot
            for c in copies(nxt_b, nxt_i, nslot):
                c.start()
            buf_idx_ref[0] = nslot

        for c in copies(b, i, slot):
            c.wait()
        carry_i = carry
        for j in range(ppcb):
            m_prev, l_prev, acc = carry_i
            kq = k_buf[slot, j].astype(jnp.float32)  # [KH, ps, Hd]
            vq = v_buf[slot, j].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kq, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [KH, G, ps]
            pos = i * bk + j * ps + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            jrow = lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
            s = jnp.where(_tree_keep(pos, length, jrow, r, tree),
                          s, NEG_INF)

            m_curr = jnp.max(s, axis=2, keepdims=True)  # [KH, G, 1]
            m_new = jnp.maximum(m_prev, m_curr)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # masked cols: exp(NEG_INF - m) == 0
            l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                p, vq, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [KH, G, Hd]
            carry_i = (m_new, l_new, acc * alpha + pv)
        return carry_i

    init = (jnp.full((KH, G, 1), NEG_INF, jnp.float32),
            jnp.zeros((KH, G, 1), jnp.float32),
            jnp.zeros((KH, G, Hd), jnp.float32))
    m, l, acc = lax.fori_loop(0, nblk, body, init)
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tree", "scale",
                                             "pages_per_compute_block",
                                             "interpret"))
def paged_tree_attention(
    q: jax.Array,           # [B, H, r, Hd] packed tree queries
    k_pages: jax.Array,     # [KH, P, ps, Hd] — ONE layer's pool slice
    v_pages: jax.Array,     # [KH, P, ps, Hd]
    page_table: jax.Array,  # [B, maxp] int32
    lengths: jax.Array,     # [B] int32, incl. the tree ROOT (node 0)
    tree: Tuple[int, int],  # (k, n_branches) STATIC
    *,
    scale: Optional[float] = None,
    pages_per_compute_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas tree-verify attention over a bf16/f32 page pool — the
    in-kernel-mask replacement for paged_tree_attention_reference
    (which stays the numerics oracle; see module docstring for the
    dispatch rule). Returns [B, H, r, Hd] in q's dtype."""
    B, H, r, Hd = q.shape
    assert r == 1 + tree[0] * tree[1], (r, tree)
    KH, P, ps, _ = k_pages.shape
    maxp = page_table.shape[1]
    g = H // KH
    G = g * r
    s = scale if scale is not None else Hd ** -0.5
    # [B, H, r, Hd] -> j-major [B, KH, G, Hd] (row = j * g + gg).
    qk = (q.astype(jnp.float32) * s).transpose(0, 2, 1, 3).reshape(
        B, r, KH, g, Hd).transpose(0, 2, 1, 3, 4).reshape(B, KH, G, Hd)
    ppcb = _pages_per_block(maxp, pages_per_compute_block or 8)

    kernel = functools.partial(_tree_kernel, ppcb=ppcb, maxp=maxp,
                               page_size=ps, batch_size=B, tree=tree,
                               group=g)
    qmap = lambda b, Ln, T, BI, IF: (b, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KH, G, Hd), qmap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KH, G, Hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((2, ppcb, KH, ps, Hd), k_pages.dtype),
            pltpu.VMEM((2, ppcb, KH, ps, Hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # Same >= 1 clamp as the linear kernel: the cross-row prefetch
    # assumes every row owns at least one block.
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Hd), jnp.float32),
        # Sequential grid: the prefetch buffer index threads through
        # SMEM from one grid step to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths, page_table.reshape(-1).astype(jnp.int32),
      jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
      qk, k_pages, v_pages)
    out = out.reshape(B, KH, r, g, Hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, r, H, Hd).transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _kernel_ok(tree, ps: int, Hd: int, use_pallas, mesh) -> bool:
    """Geometry + backend gate shared by both twins (see module
    docstring): the canonical lattice on a single-device TPU with
    Mosaic's 128-lane DMA alignment, unless interpret mode is forced
    for the parity suite. A gate that declines on the TPU says so."""
    on_tpu = bool((jax.default_backend() == "tpu") if use_pallas is None
                  else use_pallas)
    why = None
    if tree is None:
        why = "anc_mask is not the canonical (k, n_branches) lattice"
    elif mesh is not None:
        why = "tensor-parallel mesh; the tree kernels are single-device"
    elif os.environ.get("ENGINE_TREE_KERNEL", "1") == "0":
        why = "ENGINE_TREE_KERNEL=0"
    elif _interpret_forced():
        return True
    elif ps % 128 or Hd % 128:
        why = (f"page_size {ps} and head_dim {Hd} must both be multiples "
               f"of 128")
    if why is None:
        return on_tpu
    if on_tpu:
        log_kernel_declined("paged_tree_attention",
                            "the XLA gather reference", why)
    return False


# graftlint: hot-path
def paged_tree_attention_dispatch(
    q, k_pages, v_pages, page_table, lengths, anc_mask, k: int,
    n_branches: int, *, scale=None, use_pallas=None, mesh=None,
):
    """bf16/f32 tree-verify attention: the Pallas kernel when the gate
    allows (TPU, or forced interpret) AND anc_mask is the canonical
    (k, n_branches) lattice, else the XLA reference oracle. Meshes
    with tensor parallelism keep the reference route — the linear
    verify kernel has the same single-device scope."""
    tree = tree_shape_of(anc_mask, k, n_branches)
    if _kernel_ok(tree, k_pages.shape[-2], k_pages.shape[-1], use_pallas,
                  mesh):
        return paged_tree_attention(
            q, k_pages, v_pages, page_table, lengths, tree,
            scale=scale, interpret=_interpret_forced())
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_tree_attention_reference)

    return paged_tree_attention_reference(
        q, k_pages, v_pages, page_table, lengths, anc_mask, scale=scale)


# graftlint: hot-path
def paged_tree_attention_int8_dispatch(
    q, kv_pages, kv_scales, page_table, lengths, anc_mask, k: int,
    n_branches: int, layer, *, scale=None, use_pallas=None, mesh=None,
):
    """int8 twin over the FULL fused pool [2, L, KH, P, ps, Hd]: the
    linear verify kernel with the tree mask folded in (q_rep=r +
    tree=(k, M) — identical DMA stream, edited mask), else the
    gather-then-dequantize reference on the layer slice."""
    B, H, r, Hd = q.shape
    tree = tree_shape_of(anc_mask, k, n_branches)
    if _kernel_ok(tree, kv_pages.shape[-2], Hd, use_pallas, mesh):
        qm = q.transpose(0, 2, 1, 3)  # [B, r, H, Hd]
        out = paged_attention_int8(
            qm, kv_pages, kv_scales, page_table, lengths, layer,
            scale=scale, q_rep=r, tree=tree,
            interpret=_interpret_forced())
        return out.transpose(0, 2, 1, 3)  # [B, H, r, Hd]
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_tree_attention_int8_reference_fused)

    return paged_tree_attention_int8_reference_fused(
        q, kv_pages[:, layer], kv_scales[:, layer], page_table, lengths,
        anc_mask, scale=scale)
