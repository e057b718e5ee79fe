"""Learned sparse attention (models/sparse_attn_moe.py) as served, over a
kv_cache.SparseIndexPool (`SparseAttnMoeConfig.index_row`: an index key
cached beside K and V): the two bodies serving/engine_model.py's step
programs run, and the entry serving/served_models.py hands the serving
side. A decode step, in every layer, appends the three rows, scores ALL
of the slot's cached index keys, selects, and attends over the selected.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import sparse_attn_moe
from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving.flight import EV_SPARSE_SELECT
from generativeaiexamples_tpu.serving.kv_cache import (
    SparseIndexPool, kernel_live_rows, kv_token_bytes, token_slots)
from generativeaiexamples_tpu.serving.paged_attention_sparse import (
    paged_attention_sparse, walk_counts)
from generativeaiexamples_tpu.serving.sparse_index_scores import (
    sparse_index_scores)
from generativeaiexamples_tpu.serving.sparse_select import sparse_select

_LOG = logging.getLogger(__name__)


def prefill(params, cfg, pool, tokens, lengths, table_rows, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S]: every layer's K, V and index keys go to the rows'
    pages (a padded row's to the sink). -> (last-position logits [N, V],
    pool)."""
    N, S = tokens.shape
    ps = pool.page_size
    x, (k, v, ki), _ = sparse_attn_moe.walk_prompt(params, cfg, tokens,
                                                   lengths, use_pallas)

    def paged(t):  # [L, N, KH, S, Hd] -> [L, KH, N * npages, ps, Hd]
        L, _, KH, _, Hd = t.shape
        t = t.reshape(L, N, KH, S // ps, ps, Hd).transpose(0, 2, 1, 3, 4, 5)
        return t.reshape(L, KH, N * (S // ps), ps, Hd)

    ki = ki.reshape(ki.shape[0], N * (S // ps), ps, -1)
    pool = pool.write_pages(pool.encode_pages(paged(k), paged(v), ki),
                            table_rows.reshape(-1))
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)  # [N,1,D]
    return sparse_attn_moe.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, page_tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for a model with learned sparse attention, the blocks
    unrolled: append K, V and the index key, score the slot's cached index
    keys, select, attend over the selected tokens. `mask` [B]: the live
    slots; where the kernels are on they walk those alone, so an idle slot
    costs no score and no read and its expert pairs are left out. Returns
    (logits [B, V], pool, pairs each expert took in each block [L, E], the
    router's choices [L, B, k])."""
    B = tokens.shape[0]
    ps = pool.page_size
    positions = (lengths - 1)[:, None]
    slots = token_slots(
        cfg.n_kv_heads, page_tables[jnp.arange(B), (lengths - 1) // ps],
        (lengths - 1) % ps, use_pallas,
        live=kernel_live_rows(pool, mask, use_pallas))
    x = sparse_attn_moe.embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    sliced, experts = sparse_attn_moe.split_experts(params["layers"])
    counts, choices = [], []
    for l in range(cfg.n_layers):
        w = sparse_attn_moe.take_layer(sliced, l)
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q, k, v = sparse_attn_moe.project_qkv(cfg, h, w, positions)
        qi, ki, wt = sparse_attn_moe.project_index(cfg, h, w, positions)
        pool = pool.append(l, slots, k[:, :, 0].transpose(1, 0, 2),
                           v[:, :, 0].transpose(1, 0, 2), ki[:, 0])
        with jax.named_scope("index.scores"):
            scores = sparse_index_scores(
                qi[:, 0], wt[:, 0], pool.idx, l, page_tables, lengths,
                use_pallas=use_pallas, live=slots.live)
        with jax.named_scope("index.select"):
            selected = sparse_select(scores, lengths, cfg.index_topk, ps,
                                     use_pallas=use_pallas, live=slots.live)
        with jax.named_scope("attn.sparse"):
            kv, _, kv_scales, layer = pool.attention_operands(l)
            out = paged_attention_sparse(
                q[:, :, 0], kv, kv_scales, page_tables, lengths, selected,
                layer, use_pallas=use_pallas, live=slots.live)
        x = sparse_attn_moe.attn_out(cfg, x, out[:, :, None, :], w)
        x, n, idx = sparse_attn_moe.feed_forward(cfg, x, w, experts, l,
                                                 use_pallas, mask)
        counts.append(n)
        choices.append(idx[:, 0])
    logits = sparse_attn_moe.logits_of(cfg, params, x)[:, 0]
    return logits, pool, jnp.stack(counts), jnp.stack(choices)


def _zeros(cfg, n_pages, page_size, dtype, sharding, scale_sharding, slots):
    if dtype != jnp.int8:
        raise ValueError(
            f"engine.kv_dtype {dtype.name}: the pool of a model "
            "with learned sparse attention "
            "(kv_cache.SparseIndexPool) holds K and V in int8 only")
    return SparseIndexPool.zeros(cfg, n_pages, page_size)


def _describe(metrics, cfg, ecfg, pool, n_pages):
    metrics.index_bytes_per_token = (
        pool.idx.nbytes // (n_pages * ecfg.page_size))
    metrics.sparse_topk = cfg.index_topk
    _LOG.info("index rows: %d values a token and layer, %d bytes a "
              "cached token; a query attends to %d tokens at most",
              cfg.index_row, metrics.index_bytes_per_token,
              cfg.index_topk)


def _note_decode(metrics, cfg, lengths, active_mask, K, pool, use_pallas,
                 max_pages):
    """A decode block, from the lengths the host dispatches it with:
    every live slot scores all its cached index keys in every layer and
    step (a slot is one token longer each step) and attends to
    min(length, topk) of them, walking all its pages for it in blocks
    (walk_counts). Counts them and returns the block's `sparse_select`
    event (a = keys scored a live slot, step and layer; b = rows attended
    over keys scored)."""
    live = np.asarray(lengths, np.int64)[np.asarray(active_mask, bool)]
    ctx = live[None, :] + np.arange(K)[:, None]          # [K, n_live]
    topk = cfg.index_topk
    scored = int(ctx.sum())
    attended = int(np.minimum(ctx, topk).sum())
    L = cfg.n_layers
    metrics.sparse_keys_scored += scored * L
    metrics.sparse_rows_attended += attended * L
    metrics.sparse_steps_dense += int((ctx <= topk).sum())
    pages, blocks = walk_counts(ctx, pool.page_size, max_pages)
    metrics.sparse_attn_pages_walked += pages * L
    metrics.sparse_attn_blocks_walked += blocks * L
    return (EV_SPARSE_SELECT, scored / max(ctx.size, 1),
            attended / scored if scored else 0.0)


# The index rows are written by the prefill and decode programs only:
# nothing snapshots them beside a shared page, moves or rolls them back.
sm.register(sparse_attn_moe.SparseAttnMoeConfig, sm.ServedModel(
    name="learned sparse attention",
    prefill=prefill, decode_once=decode_once, zeros=_zeros,
    kv_pages=lambda pool: pool.pages,
    init_params=lambda cfg, quantize: sparse_attn_moe.init_params_on_device(
        cfg, quantize=quantize),
    token_bytes=lambda cfg, ecfg, axis_sizes: {
        "K and V": kv_token_bytes(cfg, cfg.cache_rows, "int8"),
        "index keys": cfg.cache_rows * cfg.index_row * 2},  # bf16
    caches=lambda cfg: (
        f"model caches an index key of {cfg.index_row} values a "
        f"token and layer beside K and V (learned sparse attention, "
        f"top {cfg.index_topk})"),
    lanes=(sm.mesh_lane("tensor parallelism: the indexer's one key "
                        "head and the selection have no sharded form"),
           sm.kv_dtype_lane(False, "K and V beside the index rows in "
                            "another type than int8"),
           sm.MULTIHOST, sm.PREEMPT_PREFILL),
    why_not=("those lanes re-read, share, move or roll back cache "
             "and would have to carry the index rows too"),
    counters=("sparse_keys_scored", "sparse_rows_attended",
              "sparse_steps_dense", "sparse_attn_pages_walked",
              "sparse_attn_blocks_walked"),
    gauges=("index_bytes_per_token", "sparse_topk"),
    describe=_describe, note_decode=_note_decode))
