"""Paged decode attention over a FUSED int8 KV pool with narrow scales.

Why this kernel exists (VERDICT r2 next-step #1b): bf16 KV caps the
engine at B=64 on a 16 GB v5e (B=128 OOMs; docs/ENGINEERING_NOTES.md),
and decode throughput is HBM-bandwidth-bound — weights are read once
per step regardless of batch, so doubling the batch nearly doubles
tokens/sec *if the KV pool fits and stays cheap to read*. int8 KV
halves pool bytes. The stdlib JetStream-style kernel's quantized path
is useless for this: it broadcasts f32 scales to head_dim width
(5 B/token-elem effective vs bf16's 2) AND materializes the broadcast
in HBM. Here scales are one f32 per (kv-head, k|v, token): 4 bytes
next to the 128-byte int8 token row — 3% overhead instead of 200%.

Layouts (per layer, matching kv_cache.QuantPagePool):
  q          [B, H, Hd]          softmax scale PRE-FOLDED by the caller
  kv_pages   [2, KH, P, ps, Hd]  int8; [0] = k, [1] = v
  kv_scales  [2, KH, P, ps]      bf16/f32 (amax/127 over Hd at write)
  page_table [B, maxp] int32     page ids (0 = garbage sink)
  lengths    [B] int32           valid tokens INCLUDING the current one

Kernel shape: grid (B,) — ONE grid step per batch row covering ALL kv
heads, as a fori_loop over compute blocks of `pages_per_compute_block`
pages. Each page's k AND v move HBM->VMEM as a SINGLE DMA descriptor
strided across the (KH, 2) axes, and both scale rows as one more —
2 descriptors per page instead of the 4 an unfused pool needs and the
8 a per-head grid pays. Descriptor issue count, not bandwidth, is the
measured floor at decode shapes (scripts/decompose_decode.py;
docs/ENGINEERING_NOTES.md r3 notes). The next block's copies start
while the current one computes (cross-grid-step double buffering).

Dequantization never touches head_dim: K scales multiply the score
columns ((q @ k_q^T) * ks == q @ (k_q * ks)^T), V scales fold into the
softmax weights before the PV matmul — the VPU work per block is
O(KH x G x bk), not O(bk x Hd).

No reference-repo counterpart: the reference delegates KV management to
TRT-LLM inside NIM (SURVEY.md §2.3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def quantize_kv(x: jax.Array, scale_dtype=jnp.float32):
    """Symmetric int8 over the last axis (head_dim): one scale per
    (…, token) row. Returns (q int8, s scale_dtype[...-1])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.round(xf / s).clip(-127, 127).astype(jnp.int8)
    return q, jnp.squeeze(s, -1).astype(scale_dtype)


def dequantize_pages(q_pages: jax.Array, scales: jax.Array,
                     dtype=jnp.float32) -> jax.Array:
    """[..., ps, Hd] int8 + [..., ps] -> float pages (CPU oracle)."""
    return q_pages.astype(dtype) * scales.astype(dtype)[..., None]


def paged_attention_int8_reference(q, k_pages, k_scales, v_pages, v_scales,
                                   page_table, lengths, *, scale=None):
    """Dequantize-then-attend oracle over UNFUSED pages (any backend;
    numerics tests build k/v separately)."""
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_reference)

    k = dequantize_pages(k_pages, k_scales)
    v = dequantize_pages(v_pages, v_scales)
    return paged_attention_reference(q, k, v, page_table, lengths,
                                     scale=scale).astype(q.dtype)


def paged_attention_int8_reference_fused(q, kv_pages, kv_scales, page_table,
                                         lengths, *, scale=None):
    """Oracle over the fused [2, KH, P, ps, Hd] layout."""
    return paged_attention_int8_reference(
        q, kv_pages[0], kv_scales[0], kv_pages[1], kv_scales[1],
        page_table, lengths, scale=scale)


def fuse_kv(kq, ks, vq, vs):
    """Separate quantized k/v ([KH, P, ps, Hd] + [KH, P, ps]) -> the
    fused pool layout (tests + oracle comparisons)."""
    return jnp.stack([kq, vq], axis=0), jnp.stack([ks, vs], axis=0)


# ---------------------------------------------------------------------------
# TPU kernel
# ---------------------------------------------------------------------------


def _tree_keep(pos, length, jrow, r, tree):
    """Tree-verify keep mask over _tree_layout's packed lattice,
    computed ARITHMETICALLY from iota values (Pallas kernels cannot
    capture vector constants, and the lattice is regular enough that
    no table is needed): node 0 is the root, node 1 + m*k + (d-1) is
    branch m's depth-d draft, so t is an ancestor-or-self of j iff
    t == 0, or both sit on the same branch with depth(t) <= depth(j).

    pos: absolute kv slot ids [KH, G, bk-block]; length: row's length
    incl. the root; jrow: query node index per G row (iota // g_base);
    r = 1 + M*k nodes; tree = (k, n_branches) static."""
    k, _branches = tree
    rel = pos - (length - 1)            # kv slot offset into the tree
    in_tree = (rel >= 0) & (rel < r)
    # Clamped to keep the div/mod on non-negative values; the guards
    # (jrow > 0, rel >= 1) exclude every clamped case from mattering.
    jn = jnp.maximum(jrow - 1, 0)
    tn = jnp.maximum(rel - 1, 0)
    same_chain = ((jrow > 0) & (rel >= 1)
                  & (jn // k == tn // k) & (tn % k <= jn % k))
    return (rel < 0) | (in_tree & ((rel == 0) | same_chain))


def _copy_block(pages_ref, layer, hbm, buf, sem, b, i, slot, *, ppcb, maxp,
                split_kv=False):
    """Async copies for compute block i of row b into buffer `slot`:
    one STRIDED descriptor per page covering all kv heads AND both of
    k/v (hbm.at[:, layer, :, pid] on the FULL [2, L, KH, P, ...] pool —
    the layer is indexed inside the descriptor because a host-side
    per-layer slice of the kv-leading layout is non-contiguous and XLA
    would materialize 32 copies of it). Returns the descriptors
    (recreate-and-wait pattern: semaphores count bytes, so identical
    descriptors built later can wait).

    `split_kv`: one descriptor for k and one for v. The single one
    strides from a page's k to its v over L*KH*P*ps*Hd bytes, and a pool
    whose half is 4 GiB or more (a looped model's 192 rows) reads wrong
    pages through it (SPLIT_KV_BYTES below)."""
    copies = []
    for j in range(ppcb):
        pid = pages_ref[b * maxp + i * ppcb + j]
        if split_kv:
            copies += [pltpu.make_async_copy(
                hbm.at[h, layer, :, pid], buf.at[slot, j, h], sem.at[slot])
                for h in (0, 1)]
        else:
            copies.append(pltpu.make_async_copy(
                hbm.at[:, layer, :, pid], buf.at[slot, j], sem.at[slot]))
    return copies


# From this many bytes in ONE half (k or v) of the fused code pool, the
# kernel copies a page's k and v with a descriptor each (`_copy_block`).
SPLIT_KV_BYTES = 2 ** 32


def _int8_kernel(
    lengths_ref,   # scalar prefetch [B]
    tables_ref,    # scalar prefetch [B * maxp]
    layer_ref,     # scalar prefetch [1] — which layer's pool slice
    buf_idx_ref,   # scalar prefetch [1] — persists ACROSS grid steps
    init_ref,      # scalar prefetch [1] — 1 on the very first grid step
    q_ref,         # [1, KH, G, Hd] f32 (scale pre-folded)
    kv_hbm,        # [2, L, KH, P, ps, Hd] int8 (ANY)
    s_hbm,         # [2, L, KH, P, 1, ps] f32 (ANY)
    o_ref,         # [1, KH, G, Hd]
    kv_buf,        # VMEM [2, ppcb, 2, KH, ps, Hd] int8
    s_buf,         # VMEM [2, ppcb, 2, KH, 1, ps] f32
    sem,           # DMA sems [2]
    *,
    ppcb: int,
    maxp: int,
    page_size: int,
    batch_size: int,
    q_rep: int = 1,
    tree=None,
    split_kv: bool = False,
):
    """One grid step per BATCH ROW, all kv heads + k and v together.

    q_rep > 1 (speculative verify): the G axis carries q_rep query
    positions per head group, j-major (row = j * G_base + g); query
    sub-row j sits at sequence position length-1+j and masks
    pos < length + j. The KV stream is read ONCE for all positions —
    the whole point vs folding positions into the batch.

    tree = (k, n_branches) (tree verify; requires q_rep == 1 + M*k):
    the q_rep packed positions are engine_model._tree_layout's lattice
    — node 0 the root at pool slot length-1, node 1 + m*k + (d-1)
    branch m's depth-d draft at slot length-1+node. Query row j then
    attends the committed prefix (pos < length-1) plus its ancestor-
    or-self chain, which for this lattice is ARITHMETIC in the node
    indices (same branch, depth <=) — the whole mask is a handful of
    iota compares per flash block, no captured tables, no gathers
    (Pallas kernels cannot capture vector constants). The KV stream
    is identical to linear verify: the tree only edits the mask.

    Design rules, measured on a v5e through the real decode path
    (scripts/decompose_decode.py):
    1. DMA-issue count is the floor — fused pages cut it to 2
       descriptors per page.
    2. Latency hiding is CROSS-grid-step (the JetStream scheme): while
       row b's block computes, the next block's copies are already in
       flight in the other buffer; buf_idx/init persist in SMEM across
       grid steps."""
    b = pl.program_id(0)
    ps = page_size
    bk = ppcb * ps
    length = lengths_ref[b]
    span = length + (q_rep - 1)  # kv entries the LAST query row sees
    nblk = lax.div(span + bk - 1, bk)
    KH, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    g_base = G // q_rep

    layer = layer_ref[0]

    def copies(bb, i, slot):
        return (_copy_block(tables_ref, layer, kv_hbm, kv_buf, sem, bb, i,
                            slot, ppcb=ppcb, maxp=maxp, split_kv=split_kv)
                + _copy_block(tables_ref, layer, s_hbm, s_buf, sem, bb, i,
                              slot, ppcb=ppcb, maxp=maxp))

    def next_block(i):
        """Block after (b, i-1): block i of this row if still inside
        the sequence, else the next row's first block (lengths >= 1, so
        every row has at least one block)."""
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
        # Per-page online softmax (static unroll over ppcb), all kv
        # heads batched: shapes stay <= 3-D with the head axis leading —
        # no Mosaic relayouts, and each dot is KH x (G x ps x Hd).
        carry_i = carry
        for j in range(ppcb):
            m_prev, l_prev, acc = carry_i
            kq = kv_buf[slot, j, 0].astype(jnp.float32)  # [KH, ps, Hd]
            vq = kv_buf[slot, j, 1].astype(jnp.float32)
            ks = s_buf[slot, j, 0]                       # [KH, 1, ps]
            vs = s_buf[slot, j, 1]
            s = jax.lax.dot_general(
                q, kq, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * ks  # [KH, G, ps]
            pos = i * bk + j * ps + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            if tree is not None:
                s = jnp.where(
                    _tree_keep(pos, length,
                               lax.broadcasted_iota(jnp.int32, s.shape, 1)
                               // g_base, q_rep, tree),
                    s, NEG_INF)
            else:
                limit = length
                if q_rep > 1:
                    limit = length + lax.broadcasted_iota(
                        jnp.int32, s.shape, 1) // g_base
                s = jnp.where(pos < limit, s, NEG_INF)

            m_curr = jnp.max(s, axis=2, keepdims=True)  # [KH, G, 1]
            m_new = jnp.maximum(m_prev, m_curr)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # padded cols: exp(NEG_INF - m) == 0
            l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                p * vs, vq, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # [KH, G, Hd]
            carry_i = (m_new, l_new, acc * alpha + pv)
        return carry_i

    init = (jnp.full((KH, G, 1), NEG_INF, jnp.float32),
            jnp.zeros((KH, G, 1), jnp.float32),
            jnp.zeros((KH, G, Hd), jnp.float32))
    m, l, acc = lax.fori_loop(0, nblk, body, init)
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)


def _pages_per_block(maxp: int, want: int) -> int:
    for g in range(min(want, maxp), 0, -1):
        if maxp % g == 0:
            return g
    return 1


@functools.partial(jax.jit, static_argnames=("scale",
                                             "pages_per_compute_block",
                                             "q_rep", "tree",
                                             "interpret", "split_kv"))
def paged_attention_int8(
    q: jax.Array,          # [B, H, Hd], or [B, R, H, Hd] when q_rep=R>1
    kv_pages: jax.Array,   # FULL pool [2, L, KH, P, ps, Hd] int8
    kv_scales: jax.Array,  # FULL scales [2, L, KH, P, ps] f32
    page_table: jax.Array,  # [B, maxp] int32
    lengths: jax.Array,     # [B] int32, incl. current token (R>1: the
                            # FIRST query's; query j attends lengths+j)
    layer,                  # int32 scalar: which layer to attend over
    *,
    scale: float | None = None,
    pages_per_compute_block: int | None = None,
    q_rep: int = 1,
    tree=None,
    interpret: bool = False,
    split_kv: bool | None = None,
) -> jax.Array:
    """q_rep > 1 is the speculative-verify form: R consecutive query
    positions per sequence ride the kernel's G axis, so the KV pages
    stream from HBM ONCE per sequence instead of once per position
    (folding positions into the batch costs R x the KV traffic AND
    R x the DMA issues — the measured kernel floor).

    tree = (k, n_branches) STATIC (tree verify; requires
    q_rep == 1 + n_branches*k): the positions are the packed
    _tree_layout lattice and query row j attends the committed prefix
    plus its ancestor-or-self chain (_tree_keep) instead of the linear
    pos < length+j span. KV traffic is unchanged: the tree only edits
    the in-kernel mask."""
    if tree is not None:
        assert q_rep == 1 + tree[0] * tree[1], (q_rep, tree)
    if q_rep > 1:
        B, R, H, Hd = q.shape
        assert R == q_rep, (q.shape, q_rep)
    else:
        B, H, Hd = q.shape
    two, L, KH, P, ps, _ = kv_pages.shape
    assert two == 2, kv_pages.shape
    maxp = page_table.shape[1]
    G = (H // KH) * q_rep
    s = scale if scale is not None else Hd ** -0.5

    if q_rep > 1:
        # j-major rows: row = j * (H//KH) + g, matching the kernel's
        # qoff = row // g_base masking.
        qk = (q.astype(jnp.float32) * s).reshape(
            B, q_rep, KH, H // KH, Hd).transpose(0, 2, 1, 3, 4).reshape(
            B, KH, G, Hd)
    else:
        qk = (q.astype(jnp.float32) * s).reshape(B, KH, G, Hd)
    ppcb = _pages_per_block(maxp, pages_per_compute_block or 8)
    # Scale pages as 2-D [1, ps] tiles (metadata-only reshape of the
    # CONTIGUOUS full array): the kernel DMAs and consumes them without
    # any vector relayout.
    s2 = kv_scales.reshape(2, L, KH, P, 1, ps)

    if split_kv is None:  # from the pool's shape alone (_copy_block)
        split_kv = L * KH * P * ps * Hd >= SPLIT_KV_BYTES
    kernel = functools.partial(_int8_kernel, ppcb=ppcb, maxp=maxp,
                               page_size=ps, batch_size=B, q_rep=q_rep,
                               tree=tree, split_kv=split_kv)
    qmap = lambda b, Ln, T, LY, BI, IF: (b, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KH, G, Hd), qmap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KH, G, Hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((2, ppcb, 2, KH, ps, Hd), jnp.int8),
            pltpu.VMEM((2, ppcb, 2, KH, 1, ps), kv_scales.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # The kernel's cross-row prefetch assumes every row owns >= 1 block
    # (next_block falls through to row b+1 block 0 otherwise, which would
    # leave the following row consuming a stale buffer). Clamp rather than
    # assert: a length-0 row attends over one masked page and its output
    # is ignored by the engine for inactive slots.
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Hd), jnp.float32),
        # Sequential grid: the prefetch buffer index threads through SMEM
        # from one grid step to the next.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths, page_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
      qk, kv_pages, s2)
    if q_rep > 1:
        return out.reshape(B, KH, q_rep, H // KH, Hd).transpose(
            0, 2, 1, 3, 4).reshape(B, q_rep, H, Hd).astype(q.dtype)
    return out.reshape(B, H, Hd).astype(q.dtype)
