"""What the serving side knows of a kv_cache.WindowPool whatever block
stands on it: a model whose configuration has `window_rows` and
`window_layout` (models/window_attn_moe.py::layer_plan maps a layer to
its row in its group) keeps its global layers' rows under the page table
every model has and its window layers' rows under a second one
(kv_cache.WindowTables). The prefill's page writes into the two tables,
a decode step's append and kernel call for a layer of either kind, the
memory plan's lines, the refusal and the gauges live here ONCE;
serving/served_window.py (SmallThinker's block) and
serving/served_gated_window.py (Trinity's) bring their bodies and call
`entry`. The second allocator, the second page table, the page slide and
the `window_cache` event are the scheduler's (serving/engine.py,
`window_allocator`).
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models.window_attn_moe import (
    GLOBAL, WINDOW, layer_plan)
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving.kv_cache import (
    WindowPool, engine_window_table_pages, kernel_live_rows, kv_token_bytes,
    token_slots, window_pool_pages)
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_attention_dispatch)

_LOG = logging.getLogger(__name__)


def write_prompt_pages(cfg, pool, kv, tables):
    """A prefill's K and V into both groups' pages: `kv`, what
    `pool.glob.encode_pages` made of every layer's K and V in layer order
    ([L, N, KH, S, ...] x 4), a window layer's through `tables.win`,
    whose entries behind the window point at the sink (as a padded row's
    do). -> the pool."""
    ps = pool.page_size
    N, S = kv[0].shape[1], kv[0].shape[3]

    def paged(t):  # [R, N, KH, S, ...] -> [R, KH, N * npages, ps, ...]
        R, _, KH = t.shape[:3]
        rest = t.shape[4:]
        t = t.reshape(R, N, KH, S // ps, ps, *rest)
        order = (0, 2, 1, 3, 4) + tuple(5 + i for i in range(len(rest)))
        return t.transpose(*order).reshape(R, KH, N * (S // ps), ps, *rest)

    def write(rows_pool, kind, table):
        layers = np.asarray([l for l, (k, _) in enumerate(layer_plan(cfg))
                             if k == kind])
        return rows_pool.write_pages(tuple(paged(t[layers]) for t in kv),
                                     table.reshape(-1))

    return dataclasses.replace(
        pool, glob=write(pool.glob, GLOBAL, tables.glob),
        win=write(pool.win, WINDOW, tables.win))


class StepRows:
    """One decode step's view of both groups: where the step's new token
    goes in each and how a layer of either kind attends. A global layer
    appends and attends through `tables.glob` as a Llama's does; a window
    layer through `tables.win`, with the slot's length and its window's
    first token counted from the table's first page (`tables.base`): the
    kernel walks the pages the table holds and masks, inside the first,
    the tokens that slid out. `mask` [B]: the live slots; where the
    kernels are on they walk those alone."""

    def __init__(self, cfg, pool, tables, lengths, mask, use_pallas):
        B = lengths.shape[0]
        ps = pool.page_size
        rows = jnp.arange(B)
        self.tables, self.lengths, self.use_pallas = (tables, lengths,
                                                      use_pallas)
        self.live = kernel_live_rows(pool, mask, use_pallas)
        # counted from the window table's first page
        self.rel = lengths - tables.base
        self.starts = jnp.maximum(lengths - cfg.window, 0) - tables.base
        self.slots = {
            GLOBAL: token_slots(
                cfg.n_kv_heads, tables.glob[rows, (lengths - 1) // ps],
                (lengths - 1) % ps, use_pallas, live=self.live),
            WINDOW: token_slots(
                cfg.n_kv_heads, tables.win[rows, (self.rel - 1) // ps],
                (self.rel - 1) % ps, use_pallas, live=self.live)}
        self.groups = {GLOBAL: pool.glob, WINDOW: pool.win}

    def attend(self, kind, row, q, k, v):
        """Layer (`kind`, `row` of its group): the new token's k, v
        [B, KH, Hd] appended, then q [B, H, Hd] over the slot's pages
        -> [B, H, Hd]."""
        pages = self.groups[kind].append(row, self.slots[kind],
                                         k.transpose(1, 0, 2),
                                         v.transpose(1, 0, 2))
        self.groups[kind] = pages
        kv, _, kv_scales, layer = pages.attention_operands(row)
        if kind == WINDOW:
            with jax.named_scope("attn.window"):
                return paged_attention_dispatch(
                    q, kv, None, self.tables.win, self.rel,
                    k_scales=kv_scales, layer=layer,
                    use_pallas=self.use_pallas, live=self.live,
                    starts=self.starts)
        with jax.named_scope("attn.global"):
            return paged_attention_dispatch(
                q, kv, None, self.tables.glob, self.lengths,
                k_scales=kv_scales, layer=layer, use_pallas=self.use_pallas,
                live=self.live)

    def pool(self, pool):
        return dataclasses.replace(pool, glob=self.groups[GLOBAL],
                                   win=self.groups[WINDOW])


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
WHY_NOT = ("those lanes re-read, share, move or roll back cache "
           "through ONE table a sequence whose pages are held to its "
           "end")
LANES = (sm.mesh_lane("tensor parallelism: the window rows' kernel "
                      "call has no sharded form"),
         sm.kv_dtype_lane(False, "window rows in another type than int8"),
         sm.MULTIHOST, sm.PREEMPT_PREFILL)


def entry(name: str, prefill, decode_once, init_params) -> sm.ServedModel:
    """The ServedModel of a block that stands on a WindowPool: its two
    bodies and its seeded parameters are the block's, the rest is the
    pool's."""
    return sm.ServedModel(
        name=name, prefill=prefill, decode_once=decode_once, zeros=_zeros,
        new_pool=_new_pool, second_pool=_second_pool,
        kv_pages=lambda pool: pool.glob,  # the global rows
        init_params=init_params, token_bytes=_token_bytes,
        fixed_pools=_fixed_pools, caches=_caches, lanes=LANES,
        why_not=WHY_NOT, describe=_describe)
