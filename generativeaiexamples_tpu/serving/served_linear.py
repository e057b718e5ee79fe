"""Linear attention with a delta rule beside latent attention
(models/linear_attn_moe.py) as served, over a kv_cache.HybridPool whose
pages are a LatentPagePool: a float32 state a decode slot in the KDA
layers (`LinearAttnMoeConfig.recurrent_state`), one [c_kv ; k_r] row a
token in the others (`latent_row`). The two bodies
serving/engine_model.py's step programs run, and the entry
serving/served_models.py hands the serving side.
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import hyper_connections as residual
from generativeaiexamples_tpu.models import latent_moe
from generativeaiexamples_tpu.models import linear_attn_moe as lam
from generativeaiexamples_tpu.models.llama import attn_out, rms_norm
from generativeaiexamples_tpu.serving import kda_state_update as kda_update
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving.flight import EV_STATE_CACHE
from generativeaiexamples_tpu.serving.kv_cache import (
    HybridPool, LatentPagePool, latent_token_bytes, token_slots)

_LOG = logging.getLogger(__name__)


def prefill(params, cfg, pool, tokens, lengths, table_rows, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S] through every block's prompt form; the latent
    layers' rows go to the rows' pages in one write and each KDA layer's
    state and tail after the row's LAST REAL token (the padding does not
    advance it) to decode slots `state_slots` [N] (None: row i's to slot
    i), whole. -> (last-position logits [N, V], pool)."""
    N, S = tokens.shape
    if state_slots is None:  # row i of the group is decode slot i
        state_slots = jnp.arange(N, dtype=jnp.int32)
    ps = pool.page_size
    x, rows, states, tails, _ = lam.walk_prompt(params, cfg, tokens,
                                                lengths, use_pallas)
    pages = pool.pages.encode_pages(rows)  # [R, N, S, W]
    pages = pages.reshape(pages.shape[0], N * (S // ps), ps, -1)
    pool = dataclasses.replace(
        pool, pages=pool.pages.write_pages(pages, table_rows.reshape(-1))
    ).write_slots(state_slots.reshape(-1), states, tails)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)  # [N,1,D]
    return lam.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, page_tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for this model, the blocks unrolled: a KDA block reads
    and rewrites its slots' rows of the pool (the convolutions' tail here,
    the state in place through serving/kda_state_update.py), a latent block
    appends the new token's row and attends in the absorbed form through
    serving/paged_attention_mla.py. `mask` [B]: the live slots; an idle
    slot's state, tail and expert pairs are left alone. Returns (logits
    [B, V], pool, pairs each held expert took in each expert block
    [Lm, E], the router's choices [Lm, B, k])."""
    from generativeaiexamples_tpu.serving.paged_attention_mla import (
        paged_attention_mla_dispatch)

    B = tokens.shape[0]
    ps = pool.page_size
    C, _ = cfg.latent_row
    pages, state, tail = pool.pages, pool.state, pool.tail
    slots = token_slots(1, page_tables[jnp.arange(B), (lengths - 1) // ps],
                        (lengths - 1) % ps)
    x = lam.embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    live = None  # the state kernel's walk, taken once a step
    if mask is not None and kda_update.kernel_update(state, use_pallas):
        live = kda_update.live_slots(mask)
    counts, choices = [], []
    for l, (kind, i) in enumerate(lam.layer_plan(cfg)):
        if kind == lam.KDA:
            w = lam.take_layer(params["kda"], i)
            h = rms_norm(x[:, 0], w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            qkv, g, beta, gate = lam.kda_project(cfg, h, w)
            qkv, window = lam.conv_step(cfg, qkv, tail[i], w)
            if mask is not None:
                window = jnp.where(mask[None, :, None], window, tail[i])
            tail = tail.at[i].set(window)
            q, k, v = lam.split_qkv(cfg, qkv)
            with jax.named_scope("kda.update"):
                state, o = kda_update.kda_state_update(
                    state, i, mask, g, beta, q, k, v, use_pallas, live=live)
            x = lam.kda_out(cfg, x, o[:, None], gate[:, None], w)
        else:
            w = lam.take_layer(params["mla"], i)
            h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            q_nope, q_rope, new = latent_moe.project_latent(cfg, h, w, None)
            pages = pages.append(i, slots, new[:, 0])

            def attend(q, pages=pages, i=i):
                c, r = pages.attention_operands(i)
                q = jnp.pad(q, ((0, 0), (0, 0),
                                (0, c.shape[-1] - q.shape[-1])))
                return paged_attention_mla_dispatch(
                    q, c, r, page_tables, lengths, latent=C,
                    scale=cfg.softmax_scale, use_pallas=use_pallas)

            out = latent_moe.attend_cached(cfg, q_nope[:, 0], q_rope[:, 0],
                                           w, attend)
            x = attn_out(cfg, x, out[:, :, None, :], w)
        w, experts, e = lam.ffn_weights(cfg, params, l)
        y, n, idx = latent_moe.feed_forward(cfg, x, w, experts, e,
                                            use_pallas, mask)
        x = residual.close(cfg, x, y, None)  # one stream: x + y
        if n is not None:
            counts.append(n)
            choices.append(idx[:, 0])
    logits = lam.logits_of(cfg, params, x)[:, 0]
    pool = dataclasses.replace(pool, pages=pages, state=state, tail=tail)
    return logits, pool, jnp.stack(counts), jnp.stack(choices)


def _zeros(cfg, n_pages, page_size, dtype, sharding, scale_sharding, slots):
    if slots is None:
        raise ValueError("a model with recurrent state keeps it per "
                         "decode slot: PagePool.zeros needs `slots`")
    if dtype == jnp.int8:
        raise ValueError(
            "engine.kv_dtype int8: a latent page pool "
            "(kv_cache.LatentPagePool) has no int8 form yet")
    return HybridPool.zeros(
        cfg, n_pages, page_size, dtype, slots,
        pages=LatentPagePool.zeros(cfg, n_pages, page_size, dtype))


def _fixed_pools(cfg, ecfg):  # HybridPool.state and .tail, not paged
    rs = cfg.recurrent_state
    state = rs.layers * rs.heads * rs.head_dim * rs.state * 4
    n = ecfg.max_batch_size
    return (("state_pool", n * state,
             f"{n} slots x {state} B of float32 state, not paged"),
            ("tail_pool", n * (rs.bytes_per_slot - state),
             f"{n} slots x {rs.bytes_per_slot - state} B of convolution "
             f"inputs, not paged"))


def _caches(cfg):
    rs = cfg.recurrent_state
    return (f"model carries recurrent state ({rs.layers} linear-attention "
            f"layers, {rs.bytes_per_slot} bytes a sequence) beside a "
            f"latent row of {sum(cfg.latent_row)} values a token in "
            f"{cfg.cache_rows} layers")


def _describe(metrics, cfg, ecfg, pool, n_pages):
    rs = cfg.recurrent_state
    metrics.ssm_layers = rs.layers
    metrics.ssm_state_bytes_per_slot = rs.bytes_per_slot
    _LOG.info("state pool: %d linear-attention layers x %d slots, %d "
              "bytes a slot; %d latent rows a cached token",
              rs.layers, ecfg.max_batch_size, rs.bytes_per_slot,
              cfg.cache_rows)


def _note_decode(metrics, cfg, lengths, active_mask, K, pool, use_pallas,
                 max_pages):
    """A decode block, from the lengths the host dispatches it with:
    counts the steps whose state update is the kernel's and returns the
    block's `state_cache` event (a = the live slots' mean context over
    the block; b = a live sequence's latent-row bytes over its state +
    tail + latent-row bytes)."""
    if kda_update.kernel_update(pool.state, use_pallas):
        metrics.ssm_steps_kernel += K
    live = np.asarray(lengths, np.int64)[np.asarray(active_mask, bool)]
    if not live.size:
        return None
    context = float(live.mean()) + (K - 1) / 2.0
    rows = context * latent_token_bytes(cfg, pool.pages.c.dtype)
    return (EV_STATE_CACHE, context,
            rows / (rows + cfg.recurrent_state.bytes_per_slot))


def _note_prefill(metrics, cfg, n, tokens):
    metrics.ssm_slot_writes += n


# The per-slot rows and the latent pages are written by the prefill and
# decode programs only: nothing snapshots the state beside a shared page,
# reads a page back, moves or rolls either back.
sm.register(lam.LinearAttnMoeConfig, sm.ServedModel(
    name="linear attention beside latent attention",
    prefill=prefill, decode_once=decode_once, zeros=_zeros,
    kv_pages=lambda pool: pool.pages,
    init_params=lambda cfg, quantize: lam.init_params_on_device(
        cfg, quantize=quantize),
    token_bytes=lambda cfg, ecfg, axis_sizes: {
        "latent rows": latent_token_bytes(cfg, ecfg.kv_dtype)},
    fixed_pools=_fixed_pools, caches=_caches,
    lanes=(sm.mesh_lane("tensor parallelism: neither the state's heads "
                        "nor a latent row has a sharded form"),
           sm.kv_dtype_lane(True, "an int8 latent pool"),
           sm.MULTIHOST, sm.PREEMPT_PREFILL),
    why_not=("those lanes re-read, share, move or roll back cache, have "
             "no latent form, and would have to carry the state too"),
    state_slots=True,
    counters=("ssm_slot_writes", "ssm_steps_kernel"),
    gauges=("ssm_state_bytes_per_slot", "ssm_layers"),
    describe=_describe, note_decode=_note_decode,
    note_prefill=_note_prefill))
