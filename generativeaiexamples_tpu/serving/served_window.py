"""Window and full attention in one model (models/window_attn_moe.py) as
served, over a kv_cache.WindowPool and, where every other model takes one
page table, a kv_cache.WindowTables (`WindowAttnMoeConfig.window_rows`):
the two bodies serving/engine_model.py's step programs run, and the entry
serving/served_models.py hands the serving side. The second allocator,
the second page table and the page slide are the scheduler's
(serving/engine.py, `window_allocator`).
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import window_attn_moe
from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving.kv_cache import (
    WindowPool, engine_window_table_pages, kernel_live_rows, kv_token_bytes,
    token_slots, window_pool_pages)
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_attention_dispatch)

_LOG = logging.getLogger(__name__)


def prefill(params, cfg, pool, tokens, lengths, tables, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S]: every layer's K and V go to its group's pages, a
    window layer's through `tables.win`, whose entries behind the window
    point at the sink (as a padded row's do). -> (last-position logits
    [N, V], pool)."""
    N, S = tokens.shape
    ps = pool.page_size
    x, kv, _ = window_attn_moe.walk_prompt(
        params, cfg, tokens, lengths, use_pallas,
        encode=pool.glob.encode_pages)  # [L, N, KH, S, ...] x 4

    def paged(t):  # [R, N, KH, S, ...] -> [R, KH, N * npages, ps, ...]
        R, _, KH = t.shape[:3]
        rest = t.shape[4:]
        t = t.reshape(R, N, KH, S // ps, ps, *rest)
        order = (0, 2, 1, 3, 4) + tuple(5 + i for i in range(len(rest)))
        return t.transpose(*order).reshape(R, KH, N * (S // ps), ps, *rest)

    def write(rows_pool, kind, table):
        layers = np.asarray([l for l, (k, _) in enumerate(
            window_attn_moe.layer_plan(cfg)) if k == kind])
        return rows_pool.write_pages(tuple(paged(t[layers]) for t in kv),
                                     table.reshape(-1))

    pool = dataclasses.replace(
        pool, glob=write(pool.glob, window_attn_moe.GLOBAL, tables.glob),
        win=write(pool.win, window_attn_moe.WINDOW, tables.win))
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)  # [N,1,D]
    return window_attn_moe.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for a model with window layers, the blocks unrolled in
    published order: a global layer appends and attends through
    `tables.glob` as a Llama's does; a window layer through `tables.win`,
    with the slot's length and its window's first token counted from the
    table's first page (`tables.base`): the kernel walks the pages the
    table holds and masks, inside the first, the tokens that slid out.
    The router reads the ATTENTION's input, so a layer's experts and gates
    depend on nothing its attention computes. `mask` [B]: the live slots;
    where the kernels are on they walk those alone. Returns (logits
    [B, V], pool, pairs each expert took in each block [L, E], the
    router's choices [L, B, k])."""
    B = tokens.shape[0]
    ps = pool.page_size
    rows = jnp.arange(B)
    positions = (lengths - 1)[:, None]
    live = kernel_live_rows(pool, mask, use_pallas)
    rel = lengths - tables.base  # counted from the window table's first page
    starts = jnp.maximum(lengths - cfg.window, 0) - tables.base
    slots = {
        window_attn_moe.GLOBAL: token_slots(
            cfg.n_kv_heads, tables.glob[rows, (lengths - 1) // ps],
            (lengths - 1) % ps, use_pallas, live=live),
        window_attn_moe.WINDOW: token_slots(
            cfg.n_kv_heads, tables.win[rows, (rel - 1) // ps],
            (rel - 1) % ps, use_pallas, live=live)}
    groups = {window_attn_moe.GLOBAL: pool.glob,
              window_attn_moe.WINDOW: pool.win}
    x = window_attn_moe.embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    sliced, experts = window_attn_moe.split_experts(params["layers"])
    counts, choices = [], []
    for l, (kind, row) in enumerate(window_attn_moe.layer_plan(cfg)):
        w = window_attn_moe.take_layer(sliced, l)
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        idx, gates = window_attn_moe.route(cfg, h[:, 0], w["router"])
        q, k, v = window_attn_moe.project_qkv(cfg, h, w, positions,
                                              cfg.rope_layout[l])
        pages = groups[kind].append(row, slots[kind],
                                    k[:, :, 0].transpose(1, 0, 2),
                                    v[:, :, 0].transpose(1, 0, 2))
        groups[kind] = pages
        kv, _, kv_scales, layer = pages.attention_operands(row)
        if kind == window_attn_moe.WINDOW:
            with jax.named_scope("attn.window"):
                out = paged_attention_dispatch(
                    q[:, :, 0], kv, None, tables.win, rel,
                    k_scales=kv_scales, layer=layer, use_pallas=use_pallas,
                    live=live, starts=starts)
        else:
            with jax.named_scope("attn.global"):
                out = paged_attention_dispatch(
                    q[:, :, 0], kv, None, tables.glob, lengths,
                    k_scales=kv_scales, layer=layer, use_pallas=use_pallas,
                    live=live)
        x = window_attn_moe.attn_out(cfg, x, out[:, :, None, :], w)
        x, n = window_attn_moe.feed_forward(cfg, x, w, experts, l, idx,
                                            gates, use_pallas, mask)
        counts.append(n)
        choices.append(idx)
    logits = window_attn_moe.logits_of(cfg, params, x)[:, 0]
    pool = dataclasses.replace(pool, glob=groups[window_attn_moe.GLOBAL],
                               win=groups[window_attn_moe.WINDOW])
    return logits, pool, jnp.stack(counts), jnp.stack(choices)


def _zeros(cfg, n_pages, page_size, dtype, sharding, scale_sharding, slots):
    raise ValueError(
        "a model with window layers has two pools of pages: "
        "WindowPool.zeros(cfg, n_pages, n_window_pages, page_size)")


def _second_pool(cfg, ecfg):  # the window rows': (pages, a table's width)
    window = cfg.window_rows.window
    return (window_pool_pages(window, ecfg),
            engine_window_table_pages(window, ecfg))


def _new_pool(cfg, ecfg, n_pages, sharding=None, scale_sharding=None):
    return WindowPool.zeros(cfg, n_pages, _second_pool(cfg, ecfg)[0],
                            ecfg.page_size)


def _token_bytes(cfg, ecfg, axis_sizes):  # the window rows': _fixed_pools
    return {"global rows": kv_token_bytes(cfg, cfg.window_rows.n_global,
                                          "int8")}


def _fixed_pools(cfg, ecfg):  # WindowPool.win: the slots' window tables
    per = kv_token_bytes(cfg, cfg.window_rows.n_window, "int8")
    return (("window_pool",
             _second_pool(cfg, ecfg)[0] * ecfg.page_size * per,
             f"{ecfg.max_batch_size} slots' window tables, "
             f"{per} B a cached token (the paged pool below: "
             f"{_token_bytes(cfg, ecfg, {})['global rows']} B)"),)


def _caches(cfg):
    wr = cfg.window_rows
    return (f"model has {wr.n_window} window layers ({wr.window} tokens) "
            f"beside {wr.n_global} global ones, each group of cache "
            f"rows under a page table of its own")


def _describe(metrics, cfg, ecfg, pool, n_pages):
    wr, win, ps = cfg.window_rows, pool.win, ecfg.page_size
    metrics.window_tokens = wr.window
    metrics.window_bytes_per_token = sum(
        leaf.nbytes for leaf in jax.tree.leaves(win)
    ) // (win.n_pages * ps)
    _LOG.info("window rows: %d layers see %d tokens, %d pages of %d "
              "tokens (a sequence holds %d at most), %d bytes a "
              "cached token; the %d global rows take %d bytes a "
              "cached token in the pool below",
              wr.n_window, wr.window, win.n_pages, ps,
              _second_pool(cfg, ecfg)[1],
              metrics.window_bytes_per_token, wr.n_global,
              metrics.kv_bytes_per_token)


# Every lane below knows ONE table a sequence and pages held to its end
# (kv_cache.WindowPool): nobody has said yet what a prefix hit, a
# snapshot or a rollback means for a page that slid out.
sm.register(window_attn_moe.WindowAttnMoeConfig, sm.ServedModel(
    name="window layers",
    prefill=prefill, decode_once=decode_once, zeros=_zeros,
    new_pool=_new_pool, second_pool=_second_pool,
    kv_pages=lambda pool: pool.glob,  # the global rows
    init_params=lambda cfg, quantize: window_attn_moe.init_params_on_device(
        cfg, quantize=quantize),
    token_bytes=_token_bytes, fixed_pools=_fixed_pools, caches=_caches,
    lanes=(sm.mesh_lane("tensor parallelism: the window rows' kernel "
                        "call has no sharded form"),
           sm.kv_dtype_lane(False, "window rows in another type than int8"),
           sm.MULTIHOST, sm.PREEMPT_PREFILL),
    why_not=("those lanes re-read, share, move or roll back cache "
             "through ONE table a sequence whose pages are held to its "
             "end"),
    describe=_describe))
