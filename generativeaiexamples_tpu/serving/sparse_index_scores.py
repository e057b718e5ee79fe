"""A decode step's index scores over a slot's cached index keys.

Learned sparse attention (models/sparse_attn_moe.py) scores EVERY cached
token of a slot before it attends to a few: for slot b's query heads
q [Hi, Di] with weights w [Hi] and the cached keys kI,
`I[b, s] = sum_j w[j] * relu(q[j] . kI[s])` for s < length, minus
infinity past it. The keys live in kv_cache.SparseIndexPool.idx
[R, P, Di, ps] bf16, a page transposed: `q @ page` is [Hi, Di] x [Di, ps].

Kernel shape: grid (n_live,), the dynamic bound of the int8 pool's two
kernels (paged_attention_int8.LiveRows): ONE grid step a LIVE slot, a
loop over blocks of PAGES_PER_BLOCK pages, each page one contiguous
descriptor of Di x ps x 2 bytes (16 KB at 64 x 128), the next block's
copies in flight while this one is multiplied. A page past the slot's
last is neither copied nor waited for, and what the buffer holds there is
masked by position. The scores of a block leave as ONE aligned
[PAGES_PER_BLOCK, ps] store into the slot's [maxp, ps] output block, which
starts at minus infinity. An idle slot is never walked: its output row is
whatever the buffer held, and `sparse_index_scores` selects it away.

Off the chip the same function is a gather of the slot's pages and two
einsums in XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    LiveRows, every_row)
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

# Pages a block copies together and multiplies in one batched dot: eight
# rows of the output are one aligned float32 tile.
PAGES_PER_BLOCK = 8


def sparse_index_scores_reference(q, w, idx, row, page_table, lengths):
    """The XLA form. q [B, Hi, Di] bf16, w [B, Hi] float32, idx the whole
    pool [R, P, Di, ps], `row` the cache row -> [B, maxp * ps] float32."""
    B, maxp = page_table.shape
    ps = idx.shape[-1]
    pages = idx[row][page_table]                       # [B, maxp, Di, ps]
    dots = jnp.einsum("bhd,bpds->bphs", q.astype(idx.dtype), pages,
                      preferred_element_type=jnp.float32)
    scores = jnp.einsum("bphs,bh->bps", jax.nn.relu(dots), w) + 0.0
    scores = scores.reshape(B, maxp * ps)
    return jnp.where(jnp.arange(maxp * ps)[None, :] < lengths[:, None],
                     scores, -jnp.inf)


def _scores_kernel(
    lengths_ref,   # scalar prefetch [B]
    tables_ref,    # scalar prefetch [B * maxp]
    row_ref,       # scalar prefetch [1]: which cache row
    order_ref,     # scalar prefetch [B]: LiveRows.order
    n_live_ref,    # scalar prefetch [1] (the grid's size; read by no one)
    q_ref,         # [1, Hi, Di] bf16
    w_ref,         # [1, Hi, 1] float32
    idx_hbm,       # [R, P, Di, ps] bf16 (ANY)
    o_ref,         # [1, maxp_padded, ps] float32
    buf,           # VMEM [2, ppb, Di, ps] bf16
    sem,           # DMA sems [2]
    *,
    ppb: int,
    maxp: int,
):
    del n_live_ref
    b = order_ref[pl.program_id(0)]
    row = row_ref[0]
    length = lengths_ref[b]
    ps = buf.shape[-1]
    n = jnp.clip(lax.div(length + (ps - 1), ps), 1, maxp)   # pages it has
    n_blocks = lax.div(n + (ppb - 1), ppb)

    def copies(i, slot, act):
        """`act` (start or wait) on the copies of block i's live pages."""
        for j in range(ppb):
            @pl.when(i * ppb + j < n)
            def _():
                pid = tables_ref[b * maxp + i * ppb + j]
                act(pltpu.make_async_copy(idx_hbm.at[row, pid],
                                          buf.at[slot, j], sem.at[slot]))

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    copies(0, 0, lambda c: c.start())
    q = jnp.broadcast_to(q_ref[0][None], (ppb,) + q_ref.shape[1:])
    w = w_ref[0]                                            # [Hi, 1]

    def block(i, carry):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _():
            copies(i + 1, 1 - slot, lambda c: c.start())

        copies(i, slot, lambda c: c.wait())
        dots = lax.dot_general(                  # [ppb, Hi, ps] float32
            q, buf[slot], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(dots, 0.0) * w[None], axis=1) + 0.0
        pos = (i * ppb + lax.broadcasted_iota(jnp.int32, s.shape, 0)) * ps \
            + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        o_ref[0, pl.ds(pl.multiple_of(i * ppb, ppb), ppb), :] = jnp.where(
            pos < length, s, -jnp.inf)
        return carry

    lax.fori_loop(0, n_blocks, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_index_scores_pallas(q, w, idx, row, page_table, lengths,
                               live: Optional[LiveRows] = None, *,
                               interpret: bool = False):
    B, Hi, Di = q.shape
    maxp = page_table.shape[1]
    ps = idx.shape[-1]
    ppb = PAGES_PER_BLOCK
    rows_out = -(-maxp // ppb) * ppb      # whole blocks of output rows
    rows = every_row(B) if live is None else live
    n_walk = jnp.maximum(rows.n_live, 1)

    def at_slot(k, Ln, T, R, order, n):
        return (order[k], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_walk[0],),
        in_specs=[pl.BlockSpec((1, Hi, Di), at_slot),
                  pl.BlockSpec((1, Hi, 1), at_slot),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows_out, ps), at_slot),
        scratch_shapes=[pltpu.VMEM((2, ppb, Di, ps), idx.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_scores_kernel, ppb=ppb, maxp=maxp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows_out, ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sparse_index_scores",
    )(jnp.maximum(lengths.astype(jnp.int32), 1),
      page_table.reshape(-1).astype(jnp.int32),
      jnp.asarray(row, jnp.int32).reshape(1), rows.order, n_walk,
      q.astype(idx.dtype), w.astype(jnp.float32)[:, :, None], idx)
    out = out[:, :maxp].reshape(B, maxp * ps)
    if live is not None:  # a slot the grid never served: nothing scored
        out = jnp.where(live.mask[:, None], out, -jnp.inf)
    return out


def sparse_index_scores(q, w, idx, row, page_table, lengths, *,
                        use_pallas: Optional[bool] = None,
                        live: Optional[LiveRows] = None):
    """Slot b's scores of its cached tokens, [B, maxp * ps] float32, minus
    infinity at and past `lengths[b]` and everywhere for a slot that is
    not live. q [B, Hi, Di], w [B, Hi], `idx` the WHOLE index pool
    [R, P, Di, ps] and `row` its cache row (a slice handed to a kernel
    would be copied out first), page_table [B, maxp], lengths [B]
    INCLUDING the current token, whose key is already in the pool."""
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    Di, ps = idx.shape[-2:]
    if use_pallas and (ps % 128 or Di % 16):
        log_kernel_declined(
            "sparse_index_scores", "a gather of the pages and two einsums",
            f"page_size {ps} must be a multiple of 128 and the index key's "
            f"width {Di} of 16")
        use_pallas = False
    if use_pallas:
        return sparse_index_scores_pallas(q, w, idx, row, page_table,
                                          lengths, live)
    out = sparse_index_scores_reference(q, w, idx, row, page_table, lengths)
    if live is not None:
        out = jnp.where(live.mask[:, None], out, -jnp.inf)
    return out
