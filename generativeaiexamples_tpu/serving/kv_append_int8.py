"""A decode step's K/V append into the FUSED int8 pool, in place.

What kv_cache.QuantPagePool.append does for a step's ONE new row a slot,
as one Pallas call a cache row where XLA runs four `scatter` fusions:
each of those is a serial loop over KV heads x slots index tuples at
about 120 ns a tuple whatever it carries (57 us for 128-byte code rows,
70 us for 4-byte scales: 36 % of a Mistral-7B decode step and 56 % of an
Ouro step; PERF.md section 5). The work itself is 33 KB a call.

The pool and its scales stay in HBM (`pl.ANY`) and are ALIASED input to
output, so the donated pool is written where it lies. For every LIVE slot
(`live`, paged_attention_int8.live_rows of the step's `active` mask: the
loops below run over order[0 .. n_live); a caller without a mask gets
every slot) the kernel copies the (32, Hd) int8 tile that holds the new
row's offset, all KV heads and K and V together in ONE strided descriptor
(two where a half of the pool reaches paged_attention_int8.SPLIT_KV_BYTES),
and the page's scale rows in one more, into VMEM; puts the new codes into
sublane `offset % 32` and the new scales into lane `offset` with an iota
compare and a select; and copies both back. All live slots' reads are in
flight together, then all writes, and every write is waited for before
the kernel ends. A row cannot be written alone: a DMA moves whole tiles.

An idle slot (its table row points at page 0, the sink) is neither read
nor written: a decode step's appends leave page 0 alone, and five live
slots of 64 cost five slots' copies (PERF.md section 5, PR 41). Two live
slots of one call never share a tile. Slots of rank 2 (a verify's r rows
a slot) WOULD share live tiles and race; append keeps them on the
scatters.

Who runs it (PR 46): every decode step of one row a slot but a LOOPED
model's, whose attention call takes the new row as an operand and writes
it from the page it has just copied
(serving/paged_attention_int8.py, rule 6; engine_model.fuses_append
decides), the same bytes by the same compare and select
(tests/test_paged_attention_int8_pages.py).

The codes and scales are the caller's, from the same `quantize_kv` as
the scatter form writes: the pool is byte for byte what it would be
everywhere but on the sink page, where the scatters leave an idle slot's
row and the kernel leaves nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    SPLIT_KV_BYTES, TILE_ROWS, LiveRows, every_row)


def _append_kernel(
    row_ref,     # scalar prefetch [1]: the cache row
    page_ref,    # scalar prefetch [B]: the i-th LIVE slot's page
    off_ref,     # scalar prefetch [B]: ... and offset
    snew_ref,    # scalar prefetch [B * 2 * KH] f32: the new scales, by slot
    order_ref,   # scalar prefetch [B]: LiveRows.order
    n_live_ref,  # scalar prefetch [1]: LiveRows.n_live
    new_ref,     # VMEM [B, 2, KH, Hd] int32: the new codes, by slot
    kv_in,       # [2, R, KH, P, ps, Hd] int8 (ANY), aliased to kv_hbm
    s_in,        # [2, R, KH, P, 1, ps] f32 (ANY), aliased to s_hbm
    kv_hbm,
    s_hbm,
    kv_buf,      # VMEM [B, 2, KH, 32, Hd] int8: the i-th live slot's tile
    s_buf,       # VMEM [B, 2, KH, 1, ps] f32
    sem,         # DMA sems [2]: reads, writes
    *,
    split_kv: bool,
):
    del kv_in, s_in  # the same buffers as the outputs
    _, _, KH, _, Hd = kv_buf.shape
    ps = s_buf.shape[-1]
    row = row_ref[0]

    def copies(i, back):
        """The i-th live slot's descriptors, pool -> VMEM or (`back`)
        VMEM -> pool; built again to wait (a semaphore counts bytes)."""
        page = page_ref[i]
        rows = pl.ds(pl.multiple_of(
            (off_ref[i] // TILE_ROWS) * TILE_ROWS, TILE_ROWS), TILE_ROWS)
        if split_kv:
            pairs = [(kv_hbm.at[h, row, :, page, rows], kv_buf.at[i, h])
                     for h in (0, 1)]
        else:
            pairs = [(kv_hbm.at[:, row, :, page, rows], kv_buf.at[i])]
        pairs.append((s_hbm.at[:, row, :, page], s_buf.at[i]))
        return [pltpu.make_async_copy(*(p[::-1] if back else p),
                                      sem.at[int(back)]) for p in pairs]

    def each_slot(fn):
        """`fn(i)` for the place i of every live slot in `order`."""
        def body(i, carry):
            fn(i)
            return carry
        lax.fori_loop(0, n_live_ref[0], body, 0)

    each_slot(lambda i: [c.start() for c in copies(i, False)])
    each_slot(lambda i: [c.wait() for c in copies(i, False)])

    sub = lax.broadcasted_iota(jnp.int32, (TILE_ROWS, Hd), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, ps), 1)

    def patch(i):
        b = order_ref[i]  # the slot: its new codes and scales
        off = off_ref[i]
        r = off % TILE_ROWS
        for h in (0, 1):
            new = new_ref[b, h]  # [KH, Hd] int32
            for kh in range(KH):
                tile = kv_buf[i, h, kh].astype(jnp.int32)
                kv_buf[i, h, kh] = jnp.where(
                    sub == r, new[kh:kh + 1, :], tile).astype(jnp.int8)
                s_buf[i, h, kh] = jnp.where(
                    lane == off, snew_ref[(b * 2 + h) * KH + kh],
                    s_buf[i, h, kh])
        for c in copies(i, True):
            c.start()

    each_slot(patch)
    each_slot(lambda i: [c.wait() for c in copies(i, True)])


@functools.partial(jax.jit, static_argnames=("interpret", "split_kv"))
def kv_append_int8(
    kv: jax.Array,        # FULL pool [2, R, KH, P, ps, Hd] int8
    s: jax.Array,         # FULL scales [2, R, KH, P, ps] f32
    row,                  # int32 scalar: the cache row, traced or not
    page_idx: jax.Array,  # [B] int32
    offset: jax.Array,    # [B] int32, < ps
    codes: jax.Array,     # [2, KH, B, Hd] int8: the new K and V codes
    scales: jax.Array,    # [2, KH, B] f32: their scales
    live: LiveRows | None = None,  # the slots to write; None: every one
    *,
    interpret: bool = False,
    split_kv: bool | None = None,
):
    """(kv, s) with row `offset[b]` of page `page_idx[b]` of cache row
    `row` replaced, for every kv head and K and V, by LIVE slot b's new
    codes and scales. ps and Hd must be multiples of 128 (the caller's
    check)."""
    two, R, KH, P, ps, Hd = kv.shape
    B = page_idx.shape[0]
    assert two == 2 and s.shape == kv.shape[:-1], (kv.shape, s.shape)
    assert codes.shape == (2, KH, B, Hd) and scales.shape == (2, KH, B), (
        codes.shape, scales.shape, kv.shape)
    if split_kv is None:  # from the pool's shape alone, as the attention
        split_kv = R * KH * P * ps * Hd >= SPLIT_KV_BYTES
    rows = every_row(B) if live is None else live
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), any_spec, any_spec],
        out_specs=[any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((B, 2, KH, TILE_ROWS, Hd), jnp.int8),
            pltpu.VMEM((B, 2, KH, 1, ps), s.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # Scale pages as [1, ps] tiles, as the attention kernel reads them:
    # a metadata-only reshape of the contiguous array, both ways.
    s2 = s.reshape(2, R, KH, P, 1, ps)
    # The live slots' pages and offsets in the walk's order: the loops
    # read them straight, with no slot index to look up first. The same
    # for every cache row of a step, so XLA keeps one copy.
    page_live = page_idx.astype(jnp.int32)[rows.order]
    offset_live = offset.astype(jnp.int32)[rows.order]
    kv, s2 = pl.pallas_call(
        functools.partial(_append_kernel, split_kv=split_kv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kv.shape, kv.dtype),
                   jax.ShapeDtypeStruct(s2.shape, s2.dtype)],
        # operands count the scalar prefetches: kv is the 8th, s2 the 9th
        input_output_aliases={7: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kv_append_int8",
    )(jnp.asarray(row, jnp.int32).reshape(1), page_live, offset_live,
      scales.transpose(2, 0, 1).reshape(-1), rows.order, rows.n_live,
      codes.transpose(2, 0, 1, 3).astype(jnp.int32), kv, s2)
    return kv, s2.reshape(s.shape)
