"""A decode step's selection: which cached tokens each slot attends to.

Learned sparse attention (models/sparse_attn_moe.py): from slot b's index
scores [maxp * ps] (minus infinity at and past its length) the `topk`
tokens of largest score, a tie going to the EARLIER token, all of them
while the slot has no more than `topk`: EXACT, the set
`sparse_attn_moe.select_mask` gives (which is what a prompt's tiles use,
and this function off the chip).

The kernel: SLOTS_PER_STEP slots a grid step, their scores one
[slots, maxp, ps] block in VMEM. No sort and no list of indices: the
topk-th largest score of every slot is found on the scores' bit patterns a
bit a pass (32 passes of compare-and-count over the block), then the last
tie that still fits by position (16 more); the thresholds are a vector
[slots, 1, 1], so a pass is compares and adds with no scalar in its
chain. What leaves is a float32 row a slot of ones and zeros that
serving/paged_attention_sparse.py masks a page's scores with. Alone on a
v5e at 16 slots of 19,456 positions (PERF.md section 5, PR 42): 69 us a
call, where the same passes as XLA fusions, four bits a pass, take 77, a
kernel that takes a slot a grid step with scalar thresholds 136, and
`jax.lax.top_k` for the threshold 290. An idle slot costs what a live one
does here (its row is selected away afterwards): the passes run over the
block whoever is in it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from generativeaiexamples_tpu.models.sparse_attn_moe import select_mask
from generativeaiexamples_tpu.serving.paged_attention_int8 import LiveRows
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

_SIGN = -2 ** 31  # int32's sign bit: unsigned order <-> signed order
# Slots a grid step: their scores, keys, positions and masks (some six
# [slots, maxp, ps] arrays of 32 bits) stay inside the kernel's VMEM.
SLOTS_PER_STEP = 16


def _select_kernel(lengths_ref, s_ref, o_ref, *, topk: int):
    bits = lax.bitcast_convert_type(s_ref[...], jnp.int32)
    # float order as signed-integer order: flip a negative's magnitude
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    B, _, ps = key.shape
    pos = lax.broadcasted_iota(jnp.int32, key.shape, 1) * ps \
        + lax.broadcasted_iota(jnp.int32, key.shape, 2)

    def count(keep):
        c = jnp.sum(keep.astype(jnp.int32), axis=2, keepdims=True)
        return jnp.sum(c, axis=1, keepdims=True)           # [B, 1, 1]

    def score_bit(i, t):   # t: the thresholds' bits so far, unsigned order
        cand = t | lax.shift_left(jnp.int32(1), 31 - i)
        return jnp.where(count(key >= (cand ^ _SIGN)) >= topk, cand, t)

    zero = jnp.zeros((B, 1, 1), jnp.int32)
    thr = lax.fori_loop(0, 32, score_bit, zero) ^ _SIGN
    above = key > thr
    tie = key == thr
    need = topk - count(above)   # the ties that still fit

    def place_bit(i, c):   # c: the last position a tie is taken at
        cand = c | lax.shift_left(jnp.int32(1), 15 - i)
        return jnp.where(count(tie & (pos < cand)) < need, cand, c)

    last = lax.fori_loop(0, 16, place_bit, zero)
    keep = (above | (tie & (pos <= last))) & (pos < lengths_ref[...])
    o_ref[...] = keep.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def sparse_select_pallas(scores, lengths, live: Optional[LiveRows] = None, *,
                         topk: int, interpret: bool = False):
    """scores [B, rows, ps] float32 -> [B, rows, ps] float32 of ones and
    zeros; an idle slot's row is zeros."""
    B, rows, ps = scores.shape
    assert rows * ps < 1 << 16, (rows, ps)
    step = min(B, SLOTS_PER_STEP)
    assert B % step == 0, (B, step)
    out = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        grid=(B // step,),
        in_specs=[pl.BlockSpec((step, 1, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec((step, rows, ps), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((step, rows, ps), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows, ps), jnp.float32),
        interpret=interpret,
        name="sparse_select",
    )(lengths.astype(jnp.int32).reshape(B, 1, 1), scores)
    if live is not None:  # an idle slot's scores are whatever they were
        out = jnp.where(live.mask[:, None, None], out, 0.0)
    return out


def sparse_select(scores, lengths, topk: int, page_size: int, *,
                  use_pallas: Optional[bool] = None,
                  live: Optional[LiveRows] = None):
    """Slot b's selection among its cached tokens: scores [B, N] float32
    (sparse_index_scores': minus infinity at and past `lengths[b]`), N
    whole pages of `page_size` -> bool [B, N]; nothing for a slot that is
    not live."""
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    B, N = scores.shape
    rows = N // page_size
    if use_pallas and (page_size % 128 or rows % 8 or N >= 1 << 16
                       or B % min(B, SLOTS_PER_STEP)):
        log_kernel_declined(
            "sparse_select", "a bitwise partial sort in XLA",
            f"page_size {page_size} must be a multiple of 128, the pages "
            f"{rows} of 8, the context {N} under 65,536 and the slots {B} "
            f"whole steps of {SLOTS_PER_STEP}")
        use_pallas = False
    if use_pallas:
        return sparse_select_pallas(
            scores.reshape(B, rows, page_size), lengths, live,
            topk=topk).reshape(B, N) > 0.5
    valid = jnp.arange(N)[None, :] < lengths[:, None]
    if live is not None:
        valid = valid & live.mask[:, None]
    return select_mask(scores, valid, topk)
