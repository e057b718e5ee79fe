"""The jitted prefill/decode steps of the engine, and the Llama walk.

`prefill_step`, `prefill_batch_step`, `decode_step` and `decode_multi_step`
run every architecture: each takes the block from the configuration's
entry (`served(cfg)`, serving/served_models.py) and samples, chains
tokens and stacks the expert-load rows itself. Every other program runs
the Llama walk alone (LLMEngine refuses those lanes by name for another
entry), whose bodies live here (`llama_prefill`, `_decode_once`):
models.llama's transformer block (its two halves, project_qkv and
finish_block, around the attention each step supplies; tests assert
paged forward == contiguous forward) over the serving PagePool, whose
layout only serving/kv_cache.py and the attention kernels know:

- `prefill_step`: one sequence at a bucketed length S; causal flash
  attention over the prompt; k/v written into the sequence's pages
  (padding positions land in sink page 0); returns logits at the last
  valid position.
- `decode_step`: whole slot batch, one token each; k/v appended at
  (page_table[len//ps], len%ps); paged attention over the pool.

Both are shape-stable: prefill compiles once per bucket, decode once per
(batch, max_pages) — no recompiles in steady state (SURVEY.md §7.4 #2).
"""

from __future__ import annotations

import functools
import os
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.llama import (
    LlamaConfig, final_norm, finish_block, project_qkv, rms_norm,
    walk_passes)
from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.ops.quant import mm
from generativeaiexamples_tpu.serving.kv_cache import (
    PagePool, kernel_append, kernel_live_rows, kv_pool_zeros, kv_token_bytes,
    token_slots)
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_attention_dispatch)
from generativeaiexamples_tpu.serving import served_models
from generativeaiexamples_tpu.serving.served_models import served
from generativeaiexamples_tpu.utils.platform import log_kernel_declined


def _replicate_tokens(mesh, *arrs):
    """Pin sampled-token outputs to a fully-replicated layout when the
    mesh spans processes: XLA's sharding propagation otherwise leaves
    them tensor-sharded, and a multi-host scheduler cannot read a token
    array whose shards live on remote hosts (multihost.fetch_replicated
    rejects exactly that). The all-gather this inserts runs INSIDE the
    dispatched program, so leader and followers launch it in lockstep;
    token values are integers, so single-process streams are unchanged.
    Trace-time no-op (returns inputs) for single-process meshes."""
    if mesh is None or jax.process_count() == 1:
        return arrs if len(arrs) > 1 else arrs[0]
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    out = tuple(jax.lax.with_sharding_constraint(a, rep) for a in arrs)
    return out if len(out) > 1 else out[0]


def _scanned_pass(params, body):
    """A prefill's `run_pass` for llama.walk_passes: one scan of
    `body(x, w) -> (x, this row's k/v)` over the stacked blocks. The
    scan emits a pass's rows in block order, so the walk's row map
    (row0 + l) is the order it concatenates them in."""
    def run_pass(x, state, row0):
        x, rows = jax.lax.scan(body, x, params["layers"])
        return x, state, rows
    return run_pass


def _walk_decode(params, cfg: LlamaConfig, x, pool, body):
    """The decode family's walk: `body(x, pool, w, row) -> (x, pool)`
    for every (pass, block) llama.walk_passes names, block l's weights
    with cache row row0 + l.

    The blocks of a pass are UNROLLED: a layer's slice of the weight
    stack is then an operand of its matmul (`wo`, `w_gate`, `w_up` and
    `w_down` are each one `convolution(bf16 x, s8 w)` that streams the
    codes from the stacked parameter where they lie), where a scan
    copies every layer's slices out in every step. For `wq`, `wk` and
    `wv` that takes the direct form too (direct_qkv, below).

    The passes of a looped model are a `lax.fori_loop` around the
    blocks (the row is then traced: the int8 kernel takes it as a
    scalar, the pool's append as a dynamic index). Measured on a v5e at
    Ouro-2.6B's sizes (PERF.md, PR 29): with the passes unrolled too a
    step is 1 % shorter (94.0 against 94.9 ms in a block of one), the
    compile 3.5 times longer (120 against 34 s), and a block of eight
    (1,536 bodies) does not compile in the host's 40 GiB."""
    from generativeaiexamples_tpu.ops.quant import QuantizedTensor

    L = cfg.n_layers

    def vector(t, l):
        """Block l's slice of a per-layer vector: a norm's gains, or
        an int8 weight's scales in the type mm's epilogue reads."""
        return (t.s[l].astype(cfg.dtype) if isinstance(t, QuantizedTensor)
                else t[l])

    # Inside the pass loop XLA re-runs every such slice and convert in
    # every pass (1,700 tiny operations a step at Ouro-2.6B's depth: 0.7 %
    # of it, PERF.md, PR 30), so a looped model takes them here, once,
    # as the loop's constants. The int8 codes stay operands of their dots.
    hoist = cfg.n_passes > 1
    vectors = [{k2: vector(v2, l) for k2, v2 in params["layers"].items()
                if hoist and (isinstance(v2, QuantizedTensor) or v2.ndim == 2)}
               for l in range(L)]

    def take(t, l, held):
        if isinstance(t, QuantizedTensor):
            return QuantizedTensor(t.q[l], t.s[l] if held is None else held)
        return t[l] if held is None else held

    def run_pass(x, pool, row0):
        for l in range(L):
            w = {k2: take(v2, l, vectors[l].get(k2))
                 for k2, v2 in params["layers"].items()}
            x, pool = body(x, pool, w, row0 + l)
        return x, pool, None

    x, pool, _ = walk_passes(cfg, params, x, run_pass, pool, rolled=True)
    return x, pool


def _logits(cfg: LlamaConfig, params, x):
    x = final_norm(cfg, params, x)  # a looped pass is closed by the walk
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return (x @ params["tok_emb"].T.astype(x.dtype)
                    ).astype(jnp.float32)
        return mm(x, params["lm_head"]).astype(jnp.float32)


def expert_load_rows(cfg) -> int:
    """Rows a decode block carries below its B token rows: one per
    (expert block, held expert), column 1 + i the pairs it took in step
    i; 0 for a model without experts. The engine splits them off where
    the block lands (one readback, as before)."""
    if not cfg.experts_held:
        return 0
    return cfg.n_moe_layers * cfg.experts_held


# A prefill program that holds ONE prompt computes, attends over and
# writes only its LIVE rows: the prompt rounded up to one of the bucket's
# few heights (prefill_row_counts), picked INSIDE the program from
# `lengths` (prefill_live_index) by one lax.switch over the program on
# tokens[:, :S_k]. The dispatch shape stays [N, S_bucket]: one compiled
# program a group size and bucket. A bucket of one height lowers to the
# program it always was.
#
# What a height buys and costs, on a v5e at Mistral-7B's widths (PERF.md,
# PR 38; scripts/measure_prefill_rows.py measures the first again): a
# program's time goes with its rows (a prompt of 1,590 in the 2,048
# bucket: 209.4 ms whole, 177.8 at 1,792 rows; one of 130 in the 512
# bucket: 47.5 and 25.1 at 256), and every height beyond the first adds
# 2.5 s to a program's cold compile, 6 MiB to its executable and HALF A
# SECOND to every warm restart (the branch is traced and lowered again
# for the compile cache's key, and read back), for every group size that
# has it. So the heights are the multiples of PREFILL_ROW_STEP (never
# finer than an eighth of the bucket) in the bucket's TOP HALF (what lies
# under it belongs to the bucket below, and pays at most half a bucket
# where there is none), five at most, and only for N = 1 (with eight
# heights a group size, rag.chain-open's restart grew from 72.7 to 81.8
# s); a group of several runs its whole bucket, where the flash kernel
# still skips each row's dead blocks. Sixteen heights would also make XLA
# copy the donated page pool through the conditional (6 GB: the program
# no longer fits beside the weights); tests/test_chip_compile.py holds
# that the pool stays in place.
PREFILL_ROW_STEP = 256


def prefill_row_counts(bucket: int, page_size: int,
                       group: int = 1) -> Tuple[int, ...]:
    """The row counts a prefill program of `group` prompts of `bucket`
    rows can run at, ascending; the last is the bucket."""
    if group > 1:
        return (bucket,)
    step = max(PREFILL_ROW_STEP, bucket // 8)
    step = -(-step // page_size) * page_size  # whole pages
    return tuple(h for h in range(step, bucket, step)
                 if 2 * h >= bucket) + (bucket,)


def prefill_live_index(lengths, bucket: int, page_size: int):
    """Which of prefill_row_counts(bucket, page_size, N) covers the
    longest of `lengths` [N]: the heights below it, counted. The program
    calls it on its traced operand and the engine's counters on the
    host's numpy copy: they cannot disagree."""
    counts = prefill_row_counts(bucket, page_size, lengths.shape[0])
    return (lengths.max() > np.asarray(counts[:-1], np.int32)).sum()


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas", "mesh"),
                   donate_argnames=("pool",))
def prefill_step(
    params, cfg: LlamaConfig, pool: PagePool,
    tokens: jax.Array,      # [1, S_bucket]
    length: jax.Array,      # [] valid prompt tokens
    table_row: jax.Array,   # [S_bucket // page_size] page ids (0-padded)
    use_pallas: Optional[bool] = None,
    mesh=None,
    state_slot: Optional[jax.Array] = None,  # [] the decode slot
) -> Tuple[jax.Array, PagePool]:
    """Prefill one sequence; returns (last-token logits [V], pool).
    `state_slot`: where a model with recurrent state leaves the prompt's
    (no other model reads it).

    The layer scan only READS weights and returns the per-row k/v
    ([R, S, KH, Hd], R = cfg.cache_rows, a few MB); the page pool is
    written once afterwards, all rows of all passes in the one scatter
    — never re-stacked through scan outputs (that would copy the whole
    pool per call)."""
    entry = served(cfg)
    if entry.prefill is not llama_prefill:  # the batch form's body, at N = 1
        logits, pool = entry.prefill(
            params, cfg, pool, tokens, length[None], table_row, use_pallas,
            mesh=mesh, state_slots=state_slot)
        return logits[0], pool
    _, S = tokens.shape
    ps = pool.page_size
    npages = S // ps
    KH, Hd = cfg.n_kv_heads, cfg.head_dim
    positions = jnp.arange(S)[None, :]
    lengths = length[None]

    x = params["tok_emb"][tokens].astype(cfg.residual_dtype)

    def body(x, w):
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = project_qkv(cfg, h, w, positions)
        out = attn_ops.attention(q, k, v, causal=True, lengths=lengths,
                                 use_pallas=use_pallas, mesh=mesh)
        x = finish_block(cfg, x, out, w)
        return x, (k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))  # [S,KH,Hd]

    x, _, (k_stack, v_stack) = walk_passes(cfg, params, x,
                                           _scanned_pass(params, body))
    # [R, S, KH, Hd] -> page-shaped [R, KH, npages, ps, Hd], written once.
    L = k_stack.shape[0]
    kw = k_stack.reshape(L, npages, ps, KH, Hd).transpose(0, 3, 1, 2, 4)
    vw = v_stack.reshape(L, npages, ps, KH, Hd).transpose(0, 3, 1, 2, 4)
    pool = pool.write_pages(pool.encode_pages(kw, vw), table_row)
    last = jnp.take_along_axis(
        x, (length - 1).reshape(1, 1, 1).astype(jnp.int32), axis=1)  # [1,1,D]
    logits = _logits(cfg, params, last)[0, 0]
    return logits, pool


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas",
                                             "sampling_flags", "mesh"),
                   donate_argnames=("pool",))
def prefill_batch_step(
    params, cfg: LlamaConfig, pool: PagePool,
    tokens: jax.Array,       # [N, S_bucket]
    lengths: jax.Array,      # [N] valid prompt tokens (padding rows: 1)
    table_rows: jax.Array,   # [N, S_bucket // page_size] (padding: page 0)
    temperature: jax.Array,  # [N]
    top_p: jax.Array,        # [N]
    top_k: jax.Array,        # [N]
    key: jax.Array,
    use_pallas: Optional[bool] = None,
    sampling_flags: Tuple[bool, bool, bool] = (True, False, False),
    mesh=None,
    state_slots: Optional[jax.Array] = None,  # [N] decode slots
) -> Tuple[jax.Array, PagePool]:
    """Prefill N sequences in ONE dispatch and sample each one's first
    token on device. Under burst admission this reads the weights once
    for the whole group instead of once per request — prefill at S=128
    is weight-bandwidth-bound (~7 GB int8), so N admissions cost barely
    more than one. Returns (first tokens [N], pool).

    Padding rows (lengths=1, table page 0) are computed and their k/v
    land in the sink page; their sampled tokens are ignored by the
    caller. Compiles per (N_bucket, S_bucket); a Llama's program of ONE
    prompt runs its live rows only (prefill_row_counts, above): the rows
    past them are not embedded, multiplied, attended or written, and a
    table entry past the live pages is not read. `state_slots`: the decode
    slot each row's recurrent state goes to, for a model that has one
    (a padding row's is past the last slot and dropped)."""
    from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

    all_greedy, any_top_k, any_top_p = sampling_flags
    sp = SamplingParams(temperature, top_p, top_k)
    logits, pool = served(cfg).prefill(
        params, cfg, pool, tokens, lengths, table_rows, use_pallas,
        mesh=mesh, state_slots=state_slots)  # [N, V]
    toks = sample(logits, sp, key, all_greedy=all_greedy,
                  any_top_k=any_top_k, any_top_p=any_top_p)
    return _replicate_tokens(mesh, toks), pool


def llama_prefill(params, cfg: LlamaConfig, pool, tokens, lengths, table_rows,
                  use_pallas, *, mesh=None, state_slots=None):
    """The Llama entry's prompt body: prompts
    [N, S] block by block, the pages written once, a lone prompt's live
    rows only (prefill_row_counts). -> (last-position logits [N, V],
    pool)."""
    N, S = tokens.shape
    ps = pool.page_size
    KH, Hd = cfg.n_kv_heads, cfg.head_dim

    def first_rows(S_k, pool, tokens, table_rows):
        """The whole program on the prompts' first `S_k` rows: block by
        block, then the page write, then each prompt's last row. At
        S_k = S it is the program this always was, op for op."""
        if S_k < S:
            tokens = tokens[:, :S_k]
            table_rows = table_rows[:, :S_k // ps]
        npages = S_k // ps
        positions = jnp.broadcast_to(jnp.arange(S_k)[None, :], (N, S_k))

        x = params["tok_emb"][tokens].astype(cfg.residual_dtype)

        def body(x, w):
            h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            q, k, v = project_qkv(cfg, h, w, positions)
            out = attn_ops.attention(q, k, v, causal=True, lengths=lengths,
                                     use_pallas=use_pallas, mesh=mesh)
            x = finish_block(cfg, x, out, w)
            # Encoded INSIDE the scan: for an int8 pool the stacked bf16
            # k/v ([L, N, S, KH, Hd] x2 — 2.1 GB at the N=128 deployment
            # shape) never materializes; the scan emits int8 codes +
            # narrow scales.
            return x, pool.encode_pages(  # of [N, S_k, KH, Hd]
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

        x, _, kv_out = walk_passes(cfg, params, x,
                                   _scanned_pass(params, body))
        L = cfg.cache_rows

        def paged(t):  # [L, N, S_k, KH, ...] -> [L, KH, N*npages, ps, ...]
            rest = t.shape[4:]
            t = t.reshape(L, N, npages, ps, KH, *rest)
            order = (0, 4, 1, 2, 3) + tuple(5 + i for i in range(len(rest)))
            return t.transpose(*order).reshape(L, KH, N * npages, ps, *rest)

        flat_rows = table_rows.reshape(-1)
        pool = pool.write_pages(tuple(paged(t) for t in kv_out), flat_rows)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32),
            axis=1)  # [N,1,D]
        return last, pool

    counts = prefill_row_counts(S, ps, N)
    if len(counts) == 1:
        last, pool = first_rows(S, pool, tokens, table_rows)
    else:
        last, pool = jax.lax.switch(
            prefill_live_index(lengths, S, ps),
            [functools.partial(first_rows, S_k) for S_k in counts],
            pool, tokens, table_rows)
    return _logits(cfg, params, last)[:, 0], pool


@functools.partial(jax.jit, donate_argnames=("last_tokens",))
def set_last_tokens(last_tokens: jax.Array, idxs: jax.Array,
                    toks: jax.Array) -> jax.Array:
    """last_tokens[idxs] = toks on device (batched admission). Padding
    rows carry an out-of-bounds index and are dropped, so the arrays
    stay power-of-two padded (one compile per N bucket, not per n)."""
    return last_tokens.at[idxs].set(toks.astype(last_tokens.dtype),
                                    mode="drop")


# The q, k and v projections of a decode step, two forms (llama.project_qkv).
# Staged: the reshape, transpose and rotary embedding that consume a
# projection are fused into its dot, which XLA then rewrites as a
# per-head product with the weight in VMEM, contraction-minor; so once a
# block, for every layer, a `slice_bitcast_fusion` slices `wq`/`wk`/`wv`
# out of the stack into VMEM transposed, a `copy` turns it back and the
# result goes to HBM for the later steps to prefetch. A long block
# amortises that and its steps 2..K read q/k/v weights from VMEM. Direct:
# an `optimization_barrier` keeps the consumer out, and each projection
# is a plain [B, d] x [d, n] matmul that streams its int8 weight from
# HBM, as `wo` does. A looped model's pass loop (`lax.fori_loop`) can
# prefetch nothing across steps and stages inside EVERY pass, so it
# always takes the direct form; a one-pass model takes it in blocks of up
# to DIRECT_QKV_MAX_STEPS steps. The constant is from device time a step
# of both forms at Mistral-7B's widths on a v5e, 64 slots (PERF.md, PR
# 30, the `K*` table; scripts/measure_qkv_forms.py measures it again):
# staging and copies cost 3.4 ms a BLOCK whatever its length, streaming
# q, k and v 0.59 ms a step more than reading them from VMEM, so the
# direct form wins by 2.61, 1.28 and 0.39 ms a step in blocks of 1, 2
# and 4 and loses 0.18 in a block of 8. The compiled forms are pinned in
# tests/test_chip_compile.py. The choice reads the program's static
# arguments and nothing else.
DIRECT_QKV_MAX_STEPS = 4


def direct_qkv(cfg: LlamaConfig, n_steps: int) -> bool:
    """Whether a decode program of `n_steps` steps takes the direct
    form of the q, k and v projections."""
    return cfg.n_passes > 1 or n_steps <= DIRECT_QKV_MAX_STEPS


def fuses_append(cfg: LlamaConfig, pool, use_pallas) -> bool:
    """Whether a decode step of one new row a slot (`_decode_once`) hands
    the row to its attention call, which writes it (kv_cache.
    QuantPagePool.attend_appending), where every other step appends
    first: the pool's append would be the kernel (kv_cache.kernel_append:
    an int8 pool, kernels on, tiles the DMAs take; then the dispatch's
    form is the plain paged_attention_int8), the block is the Llama one
    and not one of the drawn blocks with a call site of their own, and the
    walk is a LOOPED model's (`cfg.n_passes` > 1: the blocks inside a
    `lax.fori_loop`, the program `direct_qkv` singles out too). There a
    step is the time of its operations and the launch saved shows (Ouro,
    192 rows a step: +1.6 to +1.8 % tokens). A one-pass model's unrolled
    step streams its weights at the HBM's rate and XLA prefetches them
    under the two kernels: with one Pallas call a layer fewer its
    schedule changes and the step reads LONGER though the pair alone is
    shorter (Mistral-7B: 13.13 ms for 12.88 in blocks of 8, whose 96
    staging fusions XLA then merges into 6; `gap_p50_ms` 10.665 for
    10.60 in blocks of 2), so those keep the two calls (PERF.md section
    6, PR 46). From the step program's static arguments and the pool and
    nothing else; the engine counts `decode_steps_fused_append` by this
    function."""
    return (cfg.n_passes > 1 and served(cfg).direct_qkv
            and kernel_append(pool, use_pallas))


def _decode_rows(params, cfg: LlamaConfig, pool: PagePool, tokens, positions,
                 slots, attend, direct=False, fused=False):
    """One forward of the decode family, write-then-attend: every block
    appends its new K and V to the pool at `slots` and THEN attends, so
    `attend(q [B, H, r, Hd], pool, row) -> [B, H, r, Hd]` (the caller's:
    which kernel, over which rows) sees the rows just written. `fused`
    (`fuses_append`): the attention writes the row itself, and
    `attend(q, pool, row, k, v) -> ([B, H, r, Hd], pool)`.

    tokens, positions [B, r]; slots = token_slots of the rows' (page,
    offset), each [B, r] — or [B] where a slot has the one row: plain
    decode's K and V then go to the pool as [KH, B, Hd], the shape its
    pinned program has. Returns (logits [B, r, V], pool)."""
    x = params["tok_emb"][tokens].astype(cfg.residual_dtype)  # [B, r, D]

    def rows(t):  # [B, KH, r, Hd] -> [KH, B, r, Hd], as the slots are shaped
        if slots.page_idx.ndim == 1:
            return t[:, :, 0, :].transpose(1, 0, 2)
        return t.transpose(1, 0, 2, 3)

    def body(x, pool, w, row):
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = project_qkv(cfg, h, w, positions, direct)
        if fused:
            out, pool = attend(q, pool, row, rows(k), rows(v))
        else:
            pool = pool.append(row, slots, rows(k), rows(v))
            out = attend(q, pool, row)
        return finish_block(cfg, x, out, w), pool

    x, pool = _walk_decode(params, cfg, x, pool, body)
    return _logits(cfg, params, x), pool


def _decode_once(params, cfg: LlamaConfig, pool: PagePool, tokens, page_tables,
                 lengths, use_pallas, mesh=None, direct=False, active=None):
    """One decode iteration: the current token's k/v goes to
    (page_table[len // ps], len % ps), and paged attention runs over
    the updated pool with `lengths` INCLUDING the current token.
    `active` [B]: the live slots (None: every one). Where the int8
    pool's kernels are on, the append and the attention walk those
    alone: an idle slot writes nothing, attends to nothing, and its
    logits are what zeros attended give (its token is discarded); and
    in a looped model's walk the two are ONE call a block, the
    attention's, which writes the new row into the page it reads
    (`fuses_append`).
    Returns (logits [B, V], updated pool)."""
    B = tokens.shape[0]
    ps = pool.page_size
    positions = (lengths - 1)[:, None]  # [B, 1]
    page_idx = page_tables[jnp.arange(B), (lengths - 1) // ps]  # [B]
    offset = (lengths - 1) % ps  # [B]
    # one row a slot: where kernels are on, the pool's append is one too;
    # and the live list, once a step: every layer's append and attention
    # walk `slots.live`
    slots = token_slots(cfg.n_kv_heads, page_idx, offset, use_pallas, mesh,
                        kernel_live_rows(pool, active, use_pallas))

    fused = fuses_append(cfg, pool, use_pallas)

    def attend(q, pool, row, *new):
        q = q[:, :, 0, :]

        def over(k_pages, v_pages, k_scales, layer, new=None):
            return paged_attention_dispatch(
                q, k_pages, v_pages, page_tables, lengths, k_scales=k_scales,
                layer=layer, use_pallas=use_pallas, mesh=mesh,
                live=slots.live, new=new)

        if fused:
            out, pool = pool.attend_appending(row, *new, over)
            return out[:, :, None, :], pool
        return over(*pool.attention_operands(row))[:, :, None, :]

    logits, pool = _decode_rows(params, cfg, pool, tokens[:, None], positions,
                                slots, attend, direct, fused)
    return logits[:, 0], pool


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas", "mesh"),
                   donate_argnames=("pool",))
def decode_step(
    params, cfg: LlamaConfig, pool: PagePool,
    tokens: jax.Array,       # [B] last sampled token per slot
    page_tables: jax.Array,  # [B, maxp]
    lengths: jax.Array,      # [B] tokens incl. the one being generated NOW
    use_pallas: Optional[bool] = None,
    mesh=None,
) -> Tuple[jax.Array, PagePool]:
    """One decode step for the whole slot batch -> (logits [B, V], pool)."""
    return served(cfg).decode_once(params, cfg, pool, tokens, page_tables,
                                   lengths, use_pallas, mesh=mesh)[:2]


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "use_pallas",
                                             "sampling_flags", "mesh"),
                   donate_argnames=("pool",))
def decode_multi_step(
    params, cfg: LlamaConfig, pool: PagePool,
    last_tokens: jax.Array,   # [B] DEVICE-RESIDENT current token per slot
    page_tables: jax.Array,   # [B, maxp]
    lengths: jax.Array,       # [B] incl. current token
    active: jax.Array,        # [B] bool — inactive slots don't advance
    temperature: jax.Array,   # [B]
    top_p: jax.Array,         # [B]
    top_k: jax.Array,         # [B]
    rng: jax.Array,
    n_steps: int,
    use_pallas: Optional[bool] = None,
    sampling_flags: Tuple[bool, bool, bool] = (False, True, True),
    mesh=None,
) -> Tuple[jax.Array, jax.Array, PagePool]:
    """n_steps fused decode iterations with ON-DEVICE sampling and
    device-side token chaining: `last_tokens` lives on device and flows
    dispatch-to-dispatch, so the host never has to read a sampled token
    before launching the next block — the scheduler overlaps the host
    fetch of block N with the device computing block N+1.

    Returns (block [B, n_steps+1], last_tokens_out [B], pool), where
    block[:, 0] echoes the input tokens (the not-yet-emitted first token
    of a newly admitted slot) and block[:, 1:] are the sampled tokens.
    Sequences must have page capacity for n_steps more tokens."""
    from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

    sp = SamplingParams(temperature, top_p, top_k)
    all_greedy, any_top_k, any_top_p = sampling_flags
    tokens = last_tokens
    out_tokens = [tokens]
    once = served(cfg).decode_once
    loads = []  # a model with experts: the pairs each took, step by step
    for i in range(n_steps):
        logits, pool, load, _ = once(
            params, cfg, pool, tokens, page_tables, lengths, use_pallas,
            active, mesh=mesh, n_steps=n_steps)
        if load is not None:
            loads.append(load.reshape(-1))
        rng, key = jax.random.split(rng)
        nxt = sample(logits, sp, key, all_greedy=all_greedy,
                     any_top_k=any_top_k, any_top_p=any_top_p)
        tokens = jnp.where(active, nxt, tokens)
        out_tokens.append(tokens)
        lengths = jnp.where(active, lengths + 1, lengths)
    block = jnp.stack(out_tokens, axis=1)
    if loads:  # expert_load_rows(cfg) rows below the slots' (one readback)
        block = jnp.concatenate([block, jnp.stack(
            [jnp.zeros_like(loads[0])] + loads, axis=1).astype(block.dtype)])
    block, tokens = _replicate_tokens(mesh, block, tokens)
    return block, tokens, pool


# -- speculative decode (greedy self-speculation) ------------------------
#
# The NIM/TensorRT-LLM engines ship draft-based speculative decoding;
# this is the TPU-native equivalent, designed around the platform's
# actual bottleneck (HBM bandwidth: ~8 GB of int8 weights per decode
# step). One VERIFY step runs k draft tokens + the current token
# through a single forward — one weight read for up to k+1 committed
# tokens. Drafting is ON DEVICE (n-gram lookup over a device-resident
# token-history buffer), so the fused multi-step block still needs no
# host sync and the scheduler's pipelining is unchanged.
#
# Greedy-only by construction: verification compares drafts against
# argmax targets, so emitted tokens are ALWAYS exactly the sequential
# greedy continuation — acceptance only changes speed, never content
# (tests pin stream equality against the non-speculative engine).


def ngram_draft(history: jax.Array, lengths: jax.Array, t0: jax.Array,
                k: int) -> jax.Array:
    """Propose k draft tokens per row: the tokens FOLLOWING the most
    recent previous occurrence of the current token t0 in that row's
    history (prompt + generated so far). Rows without a previous
    occurrence fall back to repeating t0 (harmless: rejection costs
    nothing beyond the verify positions already paid for).

    history [B, Hcap] int32, lengths [B] (tokens incl. current; t0
    lives at history[b, lengths[b]-1]), t0 [B] -> [B, k]."""
    _, Hcap = history.shape
    pos = jnp.arange(Hcap)[None, :]
    cur = (lengths - 1)[:, None]
    m = (history == t0[:, None]) & (pos < cur)
    has = m.any(axis=1)
    last = jnp.argmax(jnp.where(m, pos, -1), axis=1)
    gidx = jnp.clip(last[:, None] + jnp.arange(1, k + 1)[None, :],
                    0, Hcap - 1)
    d = jnp.take_along_axis(history, gidx, axis=1)
    return jnp.where(has[:, None], d, t0[:, None])


def _decode_verify_once(params, cfg: LlamaConfig, pool: PagePool,
                        tokens: jax.Array,       # [B, r] t0 + drafts
                        page_tables: jax.Array,  # [B, maxp]
                        lengths: jax.Array,      # [B] incl. t0
                        use_pallas, mesh=None):
    """One verify forward over r=k+1 positions per sequence: projects
    q/k/v for all r positions in ONE weight read, writes their k/v into
    the pool pages (write-then-attend, same as _decode_once), and runs
    paged attention with the r positions FOLDED INTO THE KERNEL BATCH
    (row (b, i) attends prefix lengths[b]+i). Returns
    (logits [B, r, V], pool). Rejected positions need no cleanup: the
    sequence length never advances past the accepted prefix, so stale
    pool entries are masked now and overwritten later."""
    B, r = tokens.shape
    ps = pool.page_size
    maxp = page_tables.shape[1]
    offs = jnp.arange(r)[None, :]
    positions = (lengths - 1)[:, None] + offs          # [B, r]
    page_idx = jnp.take_along_axis(
        page_tables, jnp.clip(positions // ps, 0, maxp - 1), axis=1)  # [B,r]
    offset = positions % ps                            # [B, r]
    slots = token_slots(cfg.n_kv_heads, page_idx, offset)
    flat_tables = jnp.repeat(page_tables, r, axis=0)   # [B*r, maxp]
    flat_lengths = (lengths[:, None] + offs).reshape(-1)  # [B*r]

    # The fused multi-query kernel streams each sequence's KV pages
    # ONCE for all r positions (folding positions into the batch costs
    # r x the KV traffic and r x the kernel's DMA issues). Single-device
    # TPU with the Pallas-eligible head_dim only; everything else takes
    # the flat-batch path through the normal dispatch.
    fused_multi = pool.quantized and (
        use_pallas if use_pallas is not None
        else jax.default_backend() == "tpu")
    if fused_multi:
        why = None
        if mesh is not None:
            why = "tensor-parallel mesh; the fused kernel is single-device"
        elif cfg.head_dim % 128 or pool.page_size % 128:
            why = (f"head_dim {cfg.head_dim} and page_size "
                   f"{pool.page_size} must both be multiples of 128")
        elif os.environ.get("ENGINE_FUSED_VERIFY", "1") == "0":
            why = "ENGINE_FUSED_VERIFY=0"
        if why is not None:
            log_kernel_declined(
                "verify attention (int8 multi-query)",
                "the flat-batch route (r x the KV traffic)", why)
            fused_multi = False

    def attend(q, pool, row):
        qm = q.transpose(0, 2, 1, 3)                   # [B, r, H, Hd]
        k_pages, v_pages, k_scales, layer = pool.attention_operands(row)
        if fused_multi:
            from generativeaiexamples_tpu.serving.paged_attention_int8 \
                import paged_attention_int8

            out = paged_attention_int8(
                qm, k_pages, k_scales, page_tables, lengths, layer, q_rep=r)
        else:
            out = paged_attention_dispatch(
                qm.reshape(B * r, cfg.n_heads, cfg.head_dim), k_pages,
                v_pages, flat_tables, flat_lengths, k_scales=k_scales,
                layer=layer, use_pallas=use_pallas, mesh=mesh)
        out = out.reshape(B, r, cfg.n_heads, cfg.head_dim)
        return out.transpose(0, 2, 1, 3)               # [B, H, r, Hd]

    return _decode_rows(params, cfg, pool, tokens, positions, slots, attend)


def ngram_tree_draft(history: jax.Array, lengths: jax.Array, t0: jax.Array,
                     k: int, n_branches: int) -> jax.Array:
    """Multi-branch n-gram lattice draft: branch m proposes the k
    tokens FOLLOWING the (m+1)-th most recent previous occurrence of
    the current token t0 — branch 0 is exactly ngram_draft's single
    chain, extra branches widen the lattice with older continuations
    of the same context. The LAST branch (when n_branches >= 2) is the
    longest-suffix match instead: the k tokens after the most recent
    BIGRAM occurrence (t_{-1}, t0) — prompt-lookup style, a longer
    context match predicts the continuation better than recency alone —
    deduplicated against branch 0's site (when the best bigram site IS
    the most recent unigram site, the next-most-recent bigram site is
    used so the slot is never a wasted duplicate). Rows/branches
    without a matching occurrence fall back to repeating t0 (harmless:
    rejection costs only the verify positions already paid for).
    Returns [B, n_branches, k]."""
    B, Hcap = history.shape
    pos = jnp.arange(Hcap)[None, :]
    cur = (lengths - 1)[:, None]
    m = (history == t0[:, None]) & (pos < cur)
    occ, _ = jax.lax.top_k(jnp.where(m, pos, -1), n_branches)  # [B, M] desc
    if n_branches >= 2:
        prev = jnp.take_along_axis(history, jnp.maximum(cur - 1, 0),
                                   axis=1)                   # [B, 1] t_{-1}
        hist_prev = jnp.concatenate(
            [jnp.full((B, 1), -1, history.dtype), history[:, :-1]], axis=1)
        m2 = m & (hist_prev == prev)
        occ2, _ = jax.lax.top_k(jnp.where(m2, pos, -1), 2)   # [B, 2] desc
        best = jnp.where(occ2[:, 0] == occ[:, 0], occ2[:, 1], occ2[:, 0])
        occ = occ.at[:, n_branches - 1].set(best)
    has = occ >= 0
    gidx = jnp.clip(occ[:, :, None] + jnp.arange(1, k + 1)[None, None, :],
                    0, Hcap - 1)
    d = jnp.take_along_axis(history, gidx.reshape(B, n_branches * k),
                            axis=1).reshape(B, n_branches, k)
    return jnp.where(has[:, :, None], d, t0[:, None, None])


@functools.lru_cache(maxsize=None)
def _tree_layout(k: int, n_branches: int):
    """Static packed-tree layout for (depth-k, M-branch) n-gram lattice
    drafts: node 0 is the root (t0), node 1 + m*k + (d-1) is branch
    m's depth-d draft. Returns (depth [r], ancestor-or-self mask
    [r, r]) as plain numpy — tree shape is a compile-time constant of
    the verify step."""
    import numpy as np

    r = 1 + n_branches * k
    depth = np.zeros((r,), np.int32)
    anc = np.zeros((r, r), bool)
    anc[0, 0] = True
    for m in range(n_branches):
        for d in range(1, k + 1):
            j = 1 + m * k + (d - 1)
            depth[j] = d
            anc[j, 0] = True           # root is everyone's ancestor
            anc[j, j] = True           # self
            for d2 in range(1, d):
                anc[j, 1 + m * k + (d2 - 1)] = True
    return depth, anc


def _tree_verify_once(params, cfg: LlamaConfig, pool: PagePool,
                      tokens: jax.Array,       # [B, r] packed tree tokens
                      page_tables: jax.Array,  # [B, maxp]
                      lengths: jax.Array,      # [B] incl. t0 (root)
                      depth, anc_mask,         # static layout (_tree_layout)
                      spec_k: int, n_branches: int,  # static tree shape
                      use_pallas, mesh=None):
    """One tree-verify forward over r packed tree positions per
    sequence: node j's k/v is written (write-then-attend) at pool slot
    lengths-1+j with its ROPE position taken from its tree DEPTH
    (lengths-1+depth[j]); attention runs the packed tree-attention
    mask (prefix + ancestor chain) over the sequence's pages. Rejected
    nodes need no cleanup: the committed path is RELOCATED to the
    packed slots lengths-1 .. lengths-1+acc by _tree_relocate_commit,
    and everything past the new length is overwritten before it is
    ever attended (same contract as the linear verify path). Returns
    (logits [B, r, V], pool).

    Attention dispatch (serving/paged_attention_tree.py): on a
    single-device TPU the packed ancestor mask is applied INSIDE the
    Pallas paged flash-block loop — the bf16 tree kernel or the int8
    fused-pool kernel with q_rep=r and the tree mask folded in, so
    tree verify streams KV with linear decode's double-buffered
    multi-page strategy. Elsewhere (CPU, tensor-parallel meshes, odd
    geometries, ENGINE_TREE_KERNEL=0) the gather-based XLA references
    in paged_attention.py remain the oracle route, and
    ENGINE_TREE_KERNEL_INTERPRET=1 pins the kernels against them in
    interpret mode on CPU CI."""
    from generativeaiexamples_tpu.serving.paged_attention_tree import (
        paged_tree_attention_dispatch, paged_tree_attention_int8_dispatch)

    B, r = tokens.shape
    ps = pool.page_size
    maxp = page_tables.shape[1]
    depth = jnp.asarray(depth, jnp.int32)
    positions = (lengths - 1)[:, None] + depth[None, :]          # [B, r]
    slots = (lengths - 1)[:, None] + jnp.arange(r)[None, :]      # [B, r]
    page_idx = jnp.take_along_axis(
        page_tables, jnp.clip(slots // ps, 0, maxp - 1), axis=1)
    offset = slots % ps
    slots = token_slots(cfg.n_kv_heads, page_idx, offset)

    def attend(q, pool, row):
        k_pages, v_pages, k_scales, layer = pool.attention_operands(row)
        if pool.quantized:
            return paged_tree_attention_int8_dispatch(
                q, k_pages, k_scales, page_tables, lengths, anc_mask,
                spec_k, n_branches, layer, use_pallas=use_pallas, mesh=mesh)
        return paged_tree_attention_dispatch(
            q, k_pages, v_pages, page_tables, lengths, anc_mask,
            spec_k, n_branches, use_pallas=use_pallas, mesh=mesh)

    return _decode_rows(params, cfg, pool, tokens, positions, slots, attend)


def _tree_relocate_commit(pool: PagePool, cfg: LlamaConfig,
                          page_tables: jax.Array, lengths: jax.Array,
                          m_star: jax.Array, k: int) -> PagePool:
    """Move the accepted branch's k/v from its packed tree slots into
    the sequence's consecutive slots lengths-1 .. lengths-1+k (ONE
    gather + one scatter over all layers; quantized pools move codes +
    scales verbatim — no requantization error). Branch 0 is the
    identity relocation (its nodes already sit at the packed slots),
    and slots past the accepted prefix hold garbage that the length
    mask hides until the next step overwrites them."""
    ps = pool.page_size
    maxp = page_tables.shape[1]
    d_ar = jnp.arange(k + 1)[None, :]                       # [1, k+1]
    src_node = jnp.where(d_ar == 0, 0,
                         1 + m_star[:, None] * k + d_ar - 1)  # [B, k+1]
    src_slot = (lengths - 1)[:, None] + src_node
    dst_slot = (lengths - 1)[:, None] + d_ar
    src_pi = jnp.take_along_axis(
        page_tables, jnp.clip(src_slot // ps, 0, maxp - 1), axis=1)
    dst_pi = jnp.take_along_axis(
        page_tables, jnp.clip(dst_slot // ps, 0, maxp - 1), axis=1)
    src_off = src_slot % ps
    dst_off = dst_slot % ps
    return pool.move_tokens((src_pi, src_off), (dst_pi, dst_off))


def _spec_verify_loop(params, cfg: LlamaConfig, pool, history, last_tokens,
                      dev_lengths, page_tables, active, n_steps: int, k: int,
                      n_branches: int, use_pallas, mesh):
    """Shared body of the speculative programs: n_steps fused verify
    steps (linear chain when n_branches <= 1 — byte-identical to the
    pre-tree engine — or the packed n-gram lattice tree), chaining
    tokens/lengths/history on device. Targets/counts keep the SAME
    [B, n_steps, k+1] shape either way: tree verification widens only
    the draft lattice, never the committed-tokens contract."""
    B = last_tokens.shape[0]
    Hcap = history.shape[1]
    bi = jnp.arange(B)[:, None]
    tree = n_branches > 1
    if tree:
        depth, anc = _tree_layout(k, n_branches)
    out_t, out_c = [], []
    for _ in range(n_steps):
        if tree:
            draft = ngram_tree_draft(history, dev_lengths, last_tokens,
                                     k, n_branches)        # [B, M, k]
            tree_tokens = jnp.concatenate(
                [last_tokens[:, None], draft.reshape(B, n_branches * k)],
                axis=1)                                    # [B, r_nodes]
            logits, pool = _tree_verify_once(
                params, cfg, pool, tree_tokens, page_tables, dev_lengths,
                depth, anc, k, n_branches, use_pallas, mesh)
            node_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            t_root = node_t[:, 0]
            btarg = node_t[:, 1:].reshape(B, n_branches, k)
            ok = jnp.concatenate(
                [(draft[:, :, 0] == t_root[:, None])[..., None],
                 draft[:, :, 1:] == btarg[:, :, :-1]], axis=-1)  # [B,M,k]
            accm = jnp.cumprod(ok.astype(jnp.int32), axis=-1).sum(axis=-1)
            m_star = jnp.argmax(accm, axis=-1)             # first max
            acc = jnp.take_along_axis(accm, m_star[:, None], axis=1)[:, 0]
            sel_t = jnp.take_along_axis(
                btarg, m_star[:, None, None], axis=1)[:, 0]  # [B, k]
            # Every branch accepted at depth d agrees on the committed
            # token there (same context -> same argmax), so taking the
            # deepest-accepting branch is still exactly greedy.
            targets = jnp.concatenate([t_root[:, None], sel_t], axis=1)
            pool = _tree_relocate_commit(pool, cfg, page_tables,
                                         dev_lengths, m_star, k)
        else:
            draft = ngram_draft(history, dev_lengths, last_tokens, k)
            tokens_in = jnp.concatenate([last_tokens[:, None], draft],
                                        axis=1)
            logits, pool = _decode_verify_once(
                params, cfg, pool, tokens_in, page_tables, dev_lengths,
                use_pallas, mesh)
            targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,r]
            ok = (draft == targets[:, :-1])
            acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
        counts = jnp.where(active, acc + 1, 0)
        bonus = jnp.take_along_axis(targets, acc[:, None], axis=1)[:, 0]
        # History gains the committed continuation at positions
        # len..len+k; entries past the accepted prefix are provisional
        # garbage that the length mask hides until overwritten.
        hpos = jnp.clip(dev_lengths[:, None] + jnp.arange(k + 1)[None, :],
                        0, Hcap - 1)
        old = jnp.take_along_axis(history, hpos, axis=1)
        history = history.at[bi, hpos].set(
            jnp.where(active[:, None], targets, old))
        dev_lengths = jnp.where(active, dev_lengths + counts, dev_lengths)
        last_tokens = jnp.where(active, bonus, last_tokens)
        out_t.append(targets)
        out_c.append(counts)
    # The host reads (targets, counts) as the spec decode block, and
    # last_tokens chains into later dispatches exactly like the plain
    # path's — same replication pin as decode_multi_step (without it,
    # a cross-process mesh leaves them tensor-sharded and the
    # decode-block readback seam rejects the fetch).
    t_stack, c_stack, last_tokens = _replicate_tokens(
        mesh, jnp.stack(out_t, axis=1), jnp.stack(out_c, axis=1),
        last_tokens)
    return (t_stack, c_stack, last_tokens, dev_lengths, history, pool)


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "k",
                                             "n_branches",
                                             "use_pallas", "mesh"),
                   donate_argnames=("pool", "history", "dev_lengths",
                                    "last_tokens"))
def decode_spec_multi_step(
    params, cfg: LlamaConfig, pool: PagePool,
    history: jax.Array,       # [B, Hcap] device token history
    last_tokens: jax.Array,   # [B] device-resident current token
    dev_lengths: jax.Array,   # [B] device-resident lengths incl. current
    page_tables: jax.Array,   # [B, maxp]
    active: jax.Array,        # [B] bool
    n_steps: int, k: int,
    n_branches: int = 0,
    use_pallas: Optional[bool] = None,
    mesh=None,
):
    """n_steps fused VERIFY steps. Each step drafts from the history
    buffer (a single k-chain, or an M-branch tree lattice when
    n_branches > 1), verifies in one forward, commits the accepted
    prefix + one bonus token (>=1 token per step, exactly the greedy
    continuation), and chains tokens/lengths/history on device.

    Returns (targets [B, n_steps, k+1], counts [B, n_steps],
    last_tokens, dev_lengths, history, pool). The host emits
    targets[b, s, :counts[b, s]] per landed block; lengths are device-
    authoritative because the host cannot know acceptance in advance."""
    return _spec_verify_loop(params, cfg, pool, history, last_tokens,
                             dev_lengths, page_tables, active, n_steps, k,
                             n_branches, use_pallas, mesh)


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "use_pallas",
                                             "sampling_flags", "mesh"),
                   donate_argnames=("pool", "history", "dev_lengths",
                                    "last_tokens"))
def decode_plain_spec_state_multi_step(
    params, cfg: LlamaConfig, pool: PagePool,
    history: jax.Array,       # [B, Hcap] device token history
    last_tokens: jax.Array,   # [B] device-resident current token
    dev_lengths: jax.Array,   # [B] device-authoritative lengths
    page_tables: jax.Array,   # [B, maxp]
    active: jax.Array,        # [B] bool
    temperature: jax.Array,   # [B]
    top_p: jax.Array,         # [B]
    top_k: jax.Array,         # [B]
    rng: jax.Array,
    n_steps: int,
    use_pallas: Optional[bool] = None,
    sampling_flags: Tuple[bool, bool, bool] = (False, True, True),
    mesh=None,
):
    """Plain (non-speculative) fused decode block over a SPECULATIVE
    engine's device-authoritative state — the per-request fallback for
    sampled requests on a speculative engine: greedy verification
    cannot honor temperature > 0, so dispatches with a live sampled
    slot run this plan instead (the request serves, it just doesn't
    speculate). Exactly decode_multi_step's loop, except lengths come
    from the device (the host cannot know them while speculative
    blocks are in flight) and every sampled token is appended to the
    history buffer so later verify steps draft from fresh state.

    Returns (block [B, n_steps+1], last_tokens, dev_lengths, history,
    pool)."""
    from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

    B = last_tokens.shape[0]
    Hcap = history.shape[1]
    bi = jnp.arange(B)
    sp = SamplingParams(temperature, top_p, top_k)
    all_greedy, any_top_k, any_top_p = sampling_flags
    tokens = last_tokens
    out_tokens = [tokens]
    for _ in range(n_steps):
        logits, pool = _decode_once(
            params, cfg, pool, tokens, page_tables, dev_lengths, use_pallas,
            mesh)
        rng, key = jax.random.split(rng)
        nxt = sample(logits, sp, key, all_greedy=all_greedy,
                     any_top_k=any_top_k, any_top_p=any_top_p)
        tokens = jnp.where(active, nxt, tokens)
        out_tokens.append(tokens)
        hpos = jnp.clip(dev_lengths, 0, Hcap - 1)
        history = history.at[bi, hpos].set(
            jnp.where(active, tokens, history[bi, hpos]))
        dev_lengths = jnp.where(active, dev_lengths + 1, dev_lengths)
    # Same replication pin as decode_multi_step: the block is
    # host-read, tokens chain device-side across dispatches.
    block, tokens = _replicate_tokens(
        mesh, jnp.stack(out_tokens, axis=1), tokens)
    return (block, tokens, dev_lengths, history, pool)


@functools.partial(jax.jit, donate_argnames=("history", "dev_lengths"))
def set_history_rows(history: jax.Array, dev_lengths: jax.Array,
                     idxs: jax.Array, tokens: jax.Array,
                     lengths: jax.Array, first_toks: jax.Array):
    """Write admitted prompts + the prefill-sampled first token into
    the history buffer, and set the device length vector to
    prompt_len + 1 (token at lengths-1 is the current one). Batched
    admission twin of set_last_tokens; padding rows carry an
    out-of-bounds index and are dropped."""
    N, S = tokens.shape
    history = history.at[idxs[:, None],
                         jnp.arange(S)[None, :]].set(tokens, mode="drop")
    history = history.at[idxs, lengths].set(
        first_toks.astype(history.dtype), mode="drop")
    dev_lengths = dev_lengths.at[idxs].set(lengths + 1, mode="drop")
    return history, dev_lengths


@functools.partial(jax.jit, static_argnames=("all_greedy", "any_top_k",
                                             "any_top_p"))
def sample_token(logits: jax.Array, temperature, top_p, top_k, key,
                 all_greedy: bool = True, any_top_k: bool = False,
                 any_top_p: bool = False) -> jax.Array:
    """Sample ONE token from [V] logits on device (no host fetch) — the
    prefill path's sampler; the result feeds set_last_token and reaches
    the host only with the next decode block's fetch."""
    from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

    sp = SamplingParams(jnp.full((1,), temperature, jnp.float32),
                        jnp.full((1,), top_p, jnp.float32),
                        jnp.full((1,), top_k, jnp.int32))
    return sample(logits[None, :], sp, key, all_greedy=all_greedy,
                  any_top_k=any_top_k, any_top_p=any_top_p)[0]


@functools.partial(jax.jit, donate_argnames=("last_tokens",))
def set_last_token(last_tokens: jax.Array, idx: jax.Array,
                   tok: jax.Array) -> jax.Array:
    """last_tokens[idx] = tok, on device (admission after prefill)."""
    return last_tokens.at[idx].set(tok.astype(last_tokens.dtype))


@functools.partial(jax.jit, static_argnames=("all_greedy", "any_top_k",
                                             "any_top_p"),
                   donate_argnames=("last_tokens",))
# graftlint: hot-path
def sample_token_into(last_tokens: jax.Array, idx: jax.Array,
                      logits: jax.Array, temperature, top_p, top_k, key,
                      all_greedy: bool = True, any_top_k: bool = False,
                      any_top_p: bool = False):
    """sample_token + set_last_token in ONE dispatch (the
    engine.fused_sampling finish path): sample a first token from [V]
    logits and scatter it into the device token buffer without the
    logits ever feeding a second program. Exactly sample_token's math
    and key consumption, so greedy streams are bitwise-identical to
    the two-dispatch path. Returns (tok0 [], last_tokens)."""
    tok = sample_token(logits, temperature, top_p, top_k, key,
                       all_greedy, any_top_k, any_top_p)
    return tok, last_tokens.at[idx].set(tok.astype(last_tokens.dtype))


# ---------------------------------------------------------------------------
# Chunked prefill (long prompts: larger than the biggest prefill bucket)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas", "mesh"),
                   donate_argnames=("cache",))
def prefill_chunk_step(
    params, cfg: LlamaConfig, cache,
    tokens: jax.Array,  # [1, C] chunk (padded to the chunk bucket)
    valid: jax.Array,   # [] valid tokens in this chunk
    use_pallas: Optional[bool] = None,
    mesh=None,
) -> Tuple[jax.Array, "object"]:
    """One chunk of a long prompt through the contiguous scratch cache.
    llama.forward's cached-continuation mode does the work: k/v land at
    absolute positions cache.lengths + i, queries run with
    q_offset=cache.lengths (the flash kernel handles the shifted causal
    diagonal). Returns (last-valid-token logits [V], cache)."""
    from generativeaiexamples_tpu.models import llama

    logits, cache = llama.forward(params, cfg, tokens, kv_cache=cache,
                                  lengths=valid[None],
                                  use_pallas=use_pallas, mesh=mesh)
    last = jnp.take_along_axis(
        logits, (valid - 1).reshape(1, 1, 1).astype(jnp.int32), axis=1)
    return last[0, 0], cache


@functools.partial(jax.jit, static_argnames=("cfg", "use_pallas",
                                             "sampling_flags", "mesh"),
                   donate_argnames=("cache", "last_tokens"))
# graftlint: hot-path
def prefill_chunk_sample_step(
    params, cfg: LlamaConfig, cache,
    tokens: jax.Array,        # [1, C] FINAL chunk (padded to its bucket)
    valid: jax.Array,         # [] valid tokens in this chunk
    last_tokens: jax.Array,   # [B] device token buffer
    slot_idx: jax.Array,      # [] slot receiving the first token
    temperature, top_p, top_k,  # scalars (the finishing request's)
    key: jax.Array,
    use_pallas: Optional[bool] = None,
    sampling_flags: Tuple[bool, bool, bool] = (True, False, False),
    mesh=None,
):
    """prefill_chunk_step + first-token sampling + the last_tokens
    scatter in ONE dispatch — the engine.fused_sampling tail for the
    chunk that COMPLETES a prompt (chunked long prefills and
    prefix-cache-hit suffixes both finish here). Unfused, the finish
    costs two extra beat-gap dispatches (sample_token +
    set_last_token) whose only input is this program's own logits;
    fused, the logits never leave the program. Exactly the unfused
    math and key consumption: greedy streams bitwise-identical and
    sampled draws key-identical (pinned on CPU CI; as a distinct XLA
    program it carries the fused prefill rider's program-identity
    caveat on TPU). Returns (tok0 [], last_tokens, cache).

    The chunk half calls llama.forward directly (exactly
    prefill_chunk_step's math) rather than the jitted wrapper — same
    pattern as fused_decode_prefill_step, so the donated cache isn't
    re-donated through a nested jit."""
    from generativeaiexamples_tpu.models import llama

    logits, cache = llama.forward(params, cfg, tokens, kv_cache=cache,
                                  lengths=valid[None],
                                  use_pallas=use_pallas, mesh=mesh)
    chunk_last = jnp.take_along_axis(
        logits, (valid - 1).reshape(1, 1, 1).astype(jnp.int32),
        axis=1)[0, 0]
    tok0 = sample_token(chunk_last, temperature, top_p, top_k, key,
                        *sampling_flags)
    last_tokens = last_tokens.at[slot_idx].set(
        tok0.astype(last_tokens.dtype))
    return tok0, last_tokens, cache


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "use_pallas",
                                             "sampling_flags", "mesh"),
                   donate_argnames=("pool", "cache"))
def fused_decode_prefill_step(
    params, cfg: LlamaConfig, pool: PagePool,
    last_tokens: jax.Array,   # [B] device-resident current token per slot
    page_tables: jax.Array,   # [B, maxp]
    lengths: jax.Array,       # [B] incl. current token
    active: jax.Array,        # [B] bool — inactive slots don't advance
    temperature: jax.Array,   # [B]
    top_p: jax.Array,         # [B]
    top_k: jax.Array,         # [B]
    rng: jax.Array,
    cache,                    # scratch KVCache of the in-progress prefill
    chunk_tokens: jax.Array,  # [1, W] next prompt chunk (0-padded)
    chunk_valid: jax.Array,   # [] valid tokens in this chunk
    n_steps: int,
    use_pallas: Optional[bool] = None,
    sampling_flags: Tuple[bool, bool, bool] = (False, True, True),
    mesh=None,
):
    """Sarathi-style fused step: the decode batch's next n_steps block
    AND one chunk of an in-progress long prefill in ONE dispatch.

    The interleaved lane dispatches each prefill chunk as its own
    batch-of-1 program that serializes AHEAD of decode blocks on the
    device queue — while an 8k prefill is in flight, concurrent short
    streams' inter-token gaps degrade ~7x (read on an earlier
    attachment of the chip; no cell measures it yet). Folding the
    chunk into the decode dispatch removes the standalone program: the
    device runs one step that advances every live stream by n_steps
    tokens and the prefill by chunk_valid prompt tokens, so decode
    never waits out a whole chunk forward queued in front of it.

    The two halves touch disjoint state (decode: page pool; chunk: the
    prefill's contiguous scratch cache) and compute exactly the math of
    decode_multi_step and prefill_chunk_step — with fusing off the
    engine is byte-identical, and greedy token streams are identical
    either way. Returns (block [B, n_steps+1], last_tokens_out, pool,
    chunk_logits [V] at the last valid chunk position, cache).
    Compiles per (B, n_steps, W, S_total) — warmup() precompiles the
    variants live traffic can reach."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.sampling import SamplingParams, sample

    # Prefill rider: same math as prefill_chunk_step (llama.forward's
    # cached-continuation mode; queries offset by cache.lengths).
    logits, cache = llama.forward(params, cfg, chunk_tokens, kv_cache=cache,
                                  lengths=chunk_valid[None],
                                  use_pallas=use_pallas, mesh=mesh)
    chunk_last = jnp.take_along_axis(
        logits, (chunk_valid - 1).reshape(1, 1, 1).astype(jnp.int32),
        axis=1)[0, 0]
    # Decode half: same loop as decode_multi_step (device-side sampling
    # and token chaining; rng consumption matches one plain dispatch).
    sp = SamplingParams(temperature, top_p, top_k)
    all_greedy, any_top_k, any_top_p = sampling_flags
    tokens = last_tokens
    out_tokens = [tokens]
    direct = direct_qkv(cfg, n_steps)
    for _ in range(n_steps):
        dlogits, pool = _decode_once(
            params, cfg, pool, tokens, page_tables, lengths, use_pallas, mesh,
            direct)
        rng, key = jax.random.split(rng)
        nxt = sample(dlogits, sp, key, all_greedy=all_greedy,
                     any_top_k=any_top_k, any_top_p=any_top_p)
        tokens = jnp.where(active, nxt, tokens)
        out_tokens.append(tokens)
        lengths = jnp.where(active, lengths + 1, lengths)
    # Same replication pin as decode_multi_step: the block is
    # host-read, tokens chain device-side across dispatches.
    block, tokens = _replicate_tokens(
        mesh, jnp.stack(out_tokens, axis=1), tokens)
    return (block, tokens, pool, chunk_last, cache)


@functools.partial(jax.jit, static_argnames=("cfg", "n_steps", "k",
                                             "n_branches", "use_pallas",
                                             "mesh"),
                   donate_argnames=("pool", "history", "dev_lengths",
                                    "last_tokens", "cache"))
def fused_spec_prefill_step(
    params, cfg: LlamaConfig, pool: PagePool,
    history: jax.Array,       # [B, Hcap] device token history
    last_tokens: jax.Array,   # [B] device-resident current token
    dev_lengths: jax.Array,   # [B] device-authoritative lengths
    page_tables: jax.Array,   # [B, maxp]
    active: jax.Array,        # [B] bool
    cache,                    # scratch KVCache of the in-progress prefill
    chunk_tokens: jax.Array,  # [1, W] next prompt chunk (0-padded)
    chunk_valid: jax.Array,   # [] valid tokens in this chunk
    n_steps: int, k: int,
    n_branches: int = 0,
    use_pallas: Optional[bool] = None,
    mesh=None,
):
    """The composed StepPlan program: n_steps speculative VERIFY steps
    (linear chain or tree lattice) AND one chunk of an in-progress
    long prefill in ONE dispatch — the lattice point the lane-
    exclusive scheduler could never reach (speculative engines used to
    force every chunk through the standalone interleaved lane,
    reintroducing exactly the device-queue stall the fused rider
    closes for plain engines).

    The halves touch disjoint state (verify: page pool + history;
    chunk: the prefill's contiguous scratch cache) and compute exactly
    the math of decode_spec_multi_step and prefill_chunk_step.
    Returns (targets [B, n_steps, k+1], counts [B, n_steps],
    last_tokens, dev_lengths, history, pool, chunk_logits [V], cache).
    Compiles per (B, n_steps, W, S_total) — warmup() precompiles the
    variants live traffic can reach."""
    from generativeaiexamples_tpu.models import llama

    logits, cache = llama.forward(params, cfg, chunk_tokens, kv_cache=cache,
                                  lengths=chunk_valid[None],
                                  use_pallas=use_pallas, mesh=mesh)
    chunk_last = jnp.take_along_axis(
        logits, (chunk_valid - 1).reshape(1, 1, 1).astype(jnp.int32),
        axis=1)[0, 0]
    (targets, counts, last_tokens, dev_lengths, history,
     pool) = _spec_verify_loop(params, cfg, pool, history, last_tokens,
                               dev_lengths, page_tables, active, n_steps, k,
                               n_branches, use_pallas, mesh)
    return (targets, counts, last_tokens, dev_lengths, history, pool,
            chunk_last, cache)


@functools.partial(jax.jit, static_argnames=("cfg",))
def pool_to_cache(
    pool: PagePool, cfg: LlamaConfig,
    table_row: jax.Array,  # [S_cache // page_size] page ids (0-padded)
    n_tokens: jax.Array,   # [] valid prefix tokens
):
    """Gather cached prefix pages into a fresh contiguous scratch cache
    (batch 1, max_len = len(table_row) * page_size, model dtype) — the
    inverse of cache_to_pool, used by prefix-cache hits: the uncached
    suffix then runs through prefill_chunk_step with its queries offset
    by cache.lengths = n_tokens. The cache is built INSIDE the jit from
    the gather itself (rows past the prefix read sink page 0), so no
    zero-filled scratch is ever materialized on the hit path. int8
    pools dequantize with their narrow per-token scales — exactly the
    values decode attention reads for those pages."""
    from generativeaiexamples_tpu.models.llama import KVCache

    S = table_row.shape[0] * pool.page_size
    L, KH, Hd = cfg.cache_rows, cfg.n_kv_heads, cfg.head_dim
    k, v = pool.read_pages(table_row, jnp.dtype(cfg.dtype))
    # [L, KH, npages, ps, Hd] -> the cache's [L, B=1, KH, S, Hd]
    k = k.reshape(L, KH, S, Hd)[:, None]
    v = v.reshape(L, KH, S, Hd)[:, None]
    lengths = jnp.full((1,), n_tokens, jnp.int32)
    return KVCache(k, v, lengths)


@jax.jit
def pool_to_pages(pool: PagePool, table_row: jax.Array):
    """Gather `table_row`'s pages out of the pool VERBATIM as
    page-major arrays — the KV pager's demotion read
    (serving/kv_pager.py): one batched dispatch moves a whole
    demotion set device->host. int8 pools hand over codes AND narrow
    scales untouched (no dequantize — promotion scatters the exact
    bytes back, so a demote->promote round trip is bit-identical to
    never having left the pool). Returns (codes, scales):

      codes  [n, 2, L, KH, ps, Hd]  ([:, 0] = k, [:, 1] = v);
             pool dtype (bf16/f32) or int8 codes for quantized pools
      scales [n, 2, L, KH, ps] f32 for quantized pools, else None

    Compiles per table_row width — callers pad to a power of two with
    sink-page zeros (page 0 gathers garbage; the host side slices the
    valid prefix)."""
    return pool.export_pages(table_row)


@functools.partial(jax.jit, donate_argnames=("pool",))
def pages_to_pool(pool: PagePool, codes: jax.Array,
                  scales: Optional[jax.Array],
                  table_row: jax.Array) -> PagePool:
    """Scatter page-major KV bytes back into the pool at
    `table_row`'s page ids — pool_to_pages' promotion twin, the
    sibling of pool_to_cache on the admission path: ONE batched
    dispatch re-seats every non-resident page a prefix match needs.
    `codes`/`scales` are exactly pool_to_pages' layout (int8 codes +
    narrow scales verbatim for quantized pools — never re-quantized).
    Padding rows carry page id 0 and scatter into the garbage sink."""
    return pool.import_pages(codes, scales, table_row)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("pool",))
def cache_to_pool(
    pool: PagePool, cache, cfg: LlamaConfig,
    table_row: jax.Array,  # [S_total_bucket // page_size] page ids
) -> PagePool:
    """Scatter a finished scratch cache (batch 1) into the paged pool —
    the long-prompt twin of prefill_step's page write."""
    ps = pool.page_size
    L, _, KH, S, Hd = cache.k.shape
    npages = S // ps
    # Already in the canonical [L, KH, npages, ps, Hd] order.
    kw = cache.k[:, 0].reshape(L, KH, npages, ps, Hd)
    vw = cache.v[:, 0].reshape(L, KH, npages, ps, Hd)
    return pool.write_pages(pool.encode_pages(kw, vw), table_row)


# ---------------------------------------------------------------------------
# Composable step plans: one declarative recipe per device dispatch
# ---------------------------------------------------------------------------


class StepPlan(NamedTuple):
    """Declarative description of ONE engine device dispatch — the
    composable recipe every scheduler step is lowered from (the
    Sarathi-Serve insight: stall-free batching wants each dispatch
    built from one declarative plan, not from partially-exclusive
    lanes). Hashable: warmup() records the precompiled plan lattice as
    a set of these, and dispatch falls back to a NARROWER plan (drop
    the rider) rather than compiling a cold lattice point mid-traffic.

    decode_k       fused decode / verify outer steps (0 = no decode
                   half: a rider-only chunk dispatch on an idle lane)
    spec_k         draft tokens per verify step (0 = plain decode)
    tree_branches  n-gram lattice branches for tree-verify drafts
                   (<= 1 = the linear chain)
    rider_width    prefill-rider token width (0 = no rider)
    rider_s_total  the rider's scratch-cache length (compile key)
    spec_state     plain decode over a speculative engine's device-
                   authoritative state (the sampled-request fallback)
    rider_sample   the rider chunk COMPLETES its prompt and the
                   first-token sample + last_tokens scatter ride the
                   same dispatch (engine.fused_sampling; rider-only
                   plans, i.e. decode_k == 0)
    """

    decode_k: int = 0
    spec_k: int = 0
    tree_branches: int = 0
    rider_width: int = 0
    rider_s_total: int = 0
    spec_state: bool = False
    rider_sample: bool = False


def masks_pool_kernels(plan: StepPlan) -> bool:
    """Whether the program `_plan_step` lowers `plan` to hands its
    `active` mask to the int8 pool's two kernels (`_decode_once(active=)`,
    the hybrid body's `mask`), which then walk the live slots alone:
    decode_multi_step does, and no other. The fused rider lane, the
    spec-state lane and the verifies walk every slot, as they did. The
    engine's `decode_attn_rows_skipped` reads this, so a lane that starts
    to pass its mask changes it here
    (tests/test_kv_append_kernel.py holds the two together)."""
    return bool(plan.decode_k) and not (
        plan.spec_k or plan.spec_state or plan.rider_width)


def plan_to_record(plan: StepPlan) -> dict:
    """The plan's multihost wire form: every lattice coordinate as an
    int32 scalar, so a published `plan` dispatch record is
    self-describing — followers rebuild the exact StepPlan with
    `plan_from_record` instead of re-deriving it from scheduler state
    they don't have (the GL703 invariant)."""
    import numpy as np

    return {
        "plan_decode_k": np.int32(plan.decode_k),
        "plan_spec_k": np.int32(plan.spec_k),
        "plan_tree": np.int32(plan.tree_branches),
        "plan_rw": np.int32(plan.rider_width),
        "plan_rs": np.int32(plan.rider_s_total),
        "plan_spec_state": np.int32(plan.spec_state),
        "plan_rider_sample": np.int32(plan.rider_sample),
    }


def plan_from_record(rec: dict) -> StepPlan:
    """Inverse of `plan_to_record` (follower side)."""
    return StepPlan(
        decode_k=int(rec["plan_decode_k"]),
        spec_k=int(rec["plan_spec_k"]),
        tree_branches=int(rec["plan_tree"]),
        rider_width=int(rec["plan_rw"]),
        rider_s_total=int(rec["plan_rs"]),
        spec_state=bool(int(rec["plan_spec_state"])),
        rider_sample=bool(int(rec["plan_rider_sample"])))


def plan_step(params, cfg: LlamaConfig, plan: StepPlan, **kw) -> dict:
    """Dispatch-timestamp wrapper over _plan_step: every scheduler
    dispatch flows through here, so the flight recorder's
    `t_dispatch` stamp (taken the moment the async jitted call
    returns, BEFORE the engine folds state back) lives in the result
    dict as "t_dispatch" — one authoritative hook instead of each
    call site reading its own clock."""
    out = _plan_step(params, cfg, plan, **kw)
    out["t_dispatch"] = time.perf_counter()
    return out


def _plan_step(params, cfg: LlamaConfig, plan: StepPlan, *,
               pool=None, last_tokens=None, page_tables=None, lengths=None,
               active=None, temperature=None, top_p=None, top_k=None,
               rng=None, history=None, dev_lengths=None, cache=None,
               chunk_tokens=None, chunk_valid=None, slot_idx=None,
               use_pallas: Optional[bool] = None,
               sampling_flags: Tuple[bool, bool, bool] = (True, False, False),
               mesh=None) -> dict:
    """Lower a StepPlan to ONE jitted device program — the single
    dispatch entry point for every scheduler step. Each lattice point
    maps to exactly one fused program (the plan IS the compile key),
    so a warmed plan never recompiles and composition never costs an
    extra dispatch:

      (K, 0, -, 0)   decode_multi_step
      (K, 0, -, W)   fused_decode_prefill_step
      (K, k, -, 0)   decode_spec_multi_step       (linear or tree)
      (K, k, -, W)   fused_spec_prefill_step      (spec + rider, one jit)
      (K, 0*, -, 0)  decode_plain_spec_state_multi_step  (*spec_state)
      (0, 0, -, W)   prefill_chunk_step           (idle-lane chunk)
      (0, 0, -, W†)  prefill_chunk_sample_step    (†rider_sample: the
                     prompt-completing chunk, first token sampled +
                     scattered in the same dispatch)

    Returns a dict of exactly the state the plan touched: "block" or
    ("targets", "counts"), plus "last_tokens"/"pool" and — per plan —
    "dev_lengths"/"history", "chunk_logits"/"cache", or "tok0" for
    rider_sample plans."""
    if plan.decode_k == 0:
        if plan.rider_sample:
            tok0, last_tokens, cache = prefill_chunk_sample_step(
                params, cfg, cache, chunk_tokens, chunk_valid,
                last_tokens, slot_idx, temperature, top_p, top_k, rng,
                use_pallas, sampling_flags=sampling_flags, mesh=mesh)
            return {"tok0": tok0, "last_tokens": last_tokens,
                    "cache": cache}
        logits, cache = prefill_chunk_step(
            params, cfg, cache, chunk_tokens, chunk_valid, use_pallas,
            mesh=mesh)
        return {"chunk_logits": logits, "cache": cache}
    if plan.spec_k:
        if plan.rider_width:
            (targets, counts, last_tokens, dev_lengths, history, pool,
             chunk_logits, cache) = fused_spec_prefill_step(
                params, cfg, pool, history, last_tokens, dev_lengths,
                page_tables, active, cache, chunk_tokens, chunk_valid,
                plan.decode_k, plan.spec_k, n_branches=plan.tree_branches,
                use_pallas=use_pallas, mesh=mesh)
            return {"targets": targets, "counts": counts,
                    "last_tokens": last_tokens, "dev_lengths": dev_lengths,
                    "history": history, "pool": pool,
                    "chunk_logits": chunk_logits, "cache": cache}
        (targets, counts, last_tokens, dev_lengths, history,
         pool) = decode_spec_multi_step(
            params, cfg, pool, history, last_tokens, dev_lengths,
            page_tables, active, n_steps=plan.decode_k, k=plan.spec_k,
            n_branches=plan.tree_branches, use_pallas=use_pallas, mesh=mesh)
        return {"targets": targets, "counts": counts,
                "last_tokens": last_tokens, "dev_lengths": dev_lengths,
                "history": history, "pool": pool}
    if plan.spec_state:
        (block, last_tokens, dev_lengths, history,
         pool) = decode_plain_spec_state_multi_step(
            params, cfg, pool, history, last_tokens, dev_lengths,
            page_tables, active, temperature, top_p, top_k, rng,
            plan.decode_k, use_pallas, sampling_flags=sampling_flags,
            mesh=mesh)
        return {"block": block, "last_tokens": last_tokens,
                "dev_lengths": dev_lengths, "history": history,
                "pool": pool}
    if plan.rider_width:
        (block, last_tokens, pool, chunk_logits,
         cache) = fused_decode_prefill_step(
            params, cfg, pool, last_tokens, page_tables, lengths, active,
            temperature, top_p, top_k, rng, cache, chunk_tokens,
            chunk_valid, plan.decode_k, use_pallas,
            sampling_flags=sampling_flags, mesh=mesh)
        return {"block": block, "last_tokens": last_tokens, "pool": pool,
                "chunk_logits": chunk_logits, "cache": cache}
    block, last_tokens, pool = decode_multi_step(
        params, cfg, pool, last_tokens, page_tables, lengths, active,
        temperature, top_p, top_k, rng, plan.decode_k, use_pallas,
        sampling_flags=sampling_flags, mesh=mesh)
    return {"block": block, "last_tokens": last_tokens, "pool": pool}


# -- the Llama entry (serving/served_models.py) ---------------------------
# A looped decoder is the same config class with `n_passes` > 1: the lanes
# of engine._ONE_PASS_LANES index the page pool by layer and have no test
# against a looped model's reference (benchmark/architectures/ouro.py).


def _llama_decode_once(params, cfg, pool, tokens, page_tables, lengths,
                       use_pallas, mask=None, *, mesh=None, n_steps=1):
    """_decode_once as an entry's decode body: the q, k and v projections
    in the form a program of `n_steps` steps takes; no experts to count."""
    logits, pool = _decode_once(params, cfg, pool, tokens, page_tables,
                                lengths, use_pallas, mesh,
                                direct_qkv(cfg, n_steps), mask)
    return logits, pool, None, None


served_models.register(LlamaConfig, served_models.ServedModel(
    name="llama", prefill=llama_prefill, decode_once=_llama_decode_once,
    zeros=lambda cfg, n_pages, page_size, dtype, sharding, scale_sharding,
    slots: kv_pool_zeros(cfg, n_pages, page_size, dtype, sharding,
                         scale_sharding),
    init_params=lambda cfg, quantize: llama.init_params(
        cfg, jax.random.PRNGKey(0)),
    param_specs=llama.param_specs,
    token_bytes=lambda cfg, ecfg, axis_sizes: {
        "K and V": kv_token_bytes(cfg, cfg.cache_rows, ecfg.kv_dtype,
                                  int(axis_sizes.get("tensor", 1)))},
    caches=lambda cfg: None if cfg.n_passes == 1 else (
        f"model runs its {cfg.n_layers} blocks n_passes={cfg.n_passes} "
        f"times a token ({cfg.cache_rows} cache rows)"),
    why_not="those lanes are untested against a looped model",
    long_prompts=True, live_prefill_rows=True, direct_qkv=True))
