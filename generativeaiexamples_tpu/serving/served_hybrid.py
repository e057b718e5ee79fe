"""State-space layers beside attention (models/hybrid_ssm.py) as served,
over a kv_cache.HybridPool (`HybridSsmConfig.recurrent_state` says what
a slot carries): the two bodies serving/engine_model.py's step programs
run, and the entry serving/served_models.py hands the serving side.
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import hybrid_ssm
from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving import ssm_state_update as ssm_update
from generativeaiexamples_tpu.serving.kv_cache import (
    HybridPool, kernel_live_rows, kv_token_bytes, token_slots)
from generativeaiexamples_tpu.serving.paged_attention import (
    paged_attention_dispatch)

_LOG = logging.getLogger(__name__)


def prefill(params, cfg, pool, tokens, lengths, table_rows, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S] through every block's prompt form; the attention
    layers' K and V go to the rows' pages and each state-space layer's
    state after the row's LAST REAL token (the padding does not advance
    it) to decode slots `state_slots` [N] (None: row i's to slot i),
    whole. -> (last-position logits [N, V], pool)."""
    N, S = tokens.shape
    if state_slots is None:  # row i of the group is decode slot i
        state_slots = jnp.arange(N, dtype=jnp.int32)
    ps = pool.page_size
    x, kv, states, tails, _ = hybrid_ssm.walk_prompt(params, cfg, tokens,
                                                     lengths, use_pallas)

    def paged(t):  # [La, N, KH, S, Hd] -> [La, KH, N * npages, ps, Hd]
        La, _, KH, _, Hd = t.shape
        t = t.reshape(La, N, KH, S // ps, ps, Hd).transpose(0, 2, 1, 3, 4, 5)
        return t.reshape(La, KH, N * (S // ps), ps, Hd)

    pages = pool.pages.write_pages(
        pool.pages.encode_pages(paged(kv[0]), paged(kv[1])),
        table_rows.reshape(-1))
    pool = dataclasses.replace(pool, pages=pages).write_slots(
        state_slots.reshape(-1), states, tails)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)  # [N,1,D]
    return hybrid_ssm.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, page_tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for a model with recurrent state, the blocks
    unrolled: a state-space block reads and rewrites its slots' rows of
    the pool (the convolution's tail here, the state in place through
    serving/ssm_state_update.py), an attention block appends K and V and
    attends through the paged kernel. `mask` [B]: the live slots; an idle
    slot's state, tail and expert pairs are left alone, and where the
    int8 pool's kernels are on they walk the live slots only. Returns
    (logits [B, V], pool, pairs each expert took in each block [L, E],
    the router's choices [L, B, k])."""
    B = tokens.shape[0]
    ps = pool.page_size
    pages, state, tail = pool.pages, pool.state, pool.tail
    slots = token_slots(
        cfg.n_kv_heads, page_tables[jnp.arange(B), (lengths - 1) // ps],
        (lengths - 1) % ps, use_pallas,
        live=kernel_live_rows(pages, mask, use_pallas))
    x = hybrid_ssm.embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    sliced, experts = hybrid_ssm.split_experts(params["ffn"])
    counts, choices = [], []
    for l, (kind, i) in enumerate(hybrid_ssm.layer_plan(cfg)):
        if kind == hybrid_ssm.MAMBA:
            w = hybrid_ssm.take_layer(params["ssm"], i)
            h = rms_norm(x[:, 0], w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            z, xbc, dt = hybrid_ssm.ssm_project(cfg, h, w)
            xbc, window = hybrid_ssm.conv_step(cfg, xbc, tail[i], w)
            if mask is not None:
                window = jnp.where(mask[None, :, None], window, tail[i])
            tail = tail.at[i].set(window)
            xs, Bv, Cv = hybrid_ssm.split_xbc(cfg, xbc)
            step, log_a = hybrid_ssm.step_and_decay(w, dt)
            with jax.named_scope("ssm.update"):
                state, y = ssm_update.ssm_state_update(
                    state, i, mask, step, log_a, xs, Bv, Cv, use_pallas)
                y = y + w["D"][:, None] * xs.astype(jnp.float32)
            x = hybrid_ssm.branch(
                cfg, x, hybrid_ssm.gate_and_project(cfg, y, z, w)[:, None])
        else:
            w = hybrid_ssm.take_layer(params["attn"], i)
            h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            q, k, v = hybrid_ssm.project_qkv(cfg, h, w)
            pages = pages.append(i, slots, k[:, :, 0].transpose(1, 0, 2),
                                 v[:, :, 0].transpose(1, 0, 2))
            k_pages, v_pages, k_scales, layer = pages.attention_operands(i)
            out = paged_attention_dispatch(
                q[:, :, 0], k_pages, v_pages, page_tables, lengths,
                scale=cfg.attention_multiplier, k_scales=k_scales,
                layer=layer, use_pallas=use_pallas, live=slots.live)
            x = hybrid_ssm.attn_out(cfg, x, out[:, :, None, :], w)
        x, n, idx = hybrid_ssm.feed_forward(
            cfg, x, hybrid_ssm.take_layer(sliced, l), experts, l, use_pallas,
            mask)
        counts.append(n)
        choices.append(idx[:, 0])
    logits = hybrid_ssm.logits_of(cfg, params, x)[:, 0]
    pool = dataclasses.replace(pool, pages=pages, state=state, tail=tail)
    return logits, pool, jnp.stack(counts), jnp.stack(choices)


def _zeros(cfg, n_pages, page_size, dtype, sharding, scale_sharding, slots):
    if slots is None:
        raise ValueError("a model with recurrent state keeps it per "
                         "decode slot: PagePool.zeros needs `slots`")
    return HybridPool.zeros(cfg, n_pages, page_size, dtype, slots)


def _fixed_pools(cfg, ecfg):  # HybridPool.state and .tail, not paged
    rs = cfg.recurrent_state
    return (("state_pool", ecfg.max_batch_size * rs.bytes_per_slot,
             f"{ecfg.max_batch_size} slots x {rs.bytes_per_slot} B, "
             f"not paged"),)


def _caches(cfg):
    rs = cfg.recurrent_state
    return (f"model carries recurrent state ({rs.layers} state-space "
            f"layers, {rs.bytes_per_slot} bytes a sequence) beside its "
            f"cache")


def _describe(metrics, cfg, ecfg, pool, n_pages):
    rs = cfg.recurrent_state
    metrics.ssm_layers = rs.layers
    metrics.ssm_state_bytes_per_slot = rs.bytes_per_slot
    _LOG.info("state pool: %d state-space layers x %d slots, %d "
              "bytes a slot", rs.layers, ecfg.max_batch_size,
              rs.bytes_per_slot)


def _note_decode(metrics, cfg, lengths, active_mask, K, pool, use_pallas,
                 max_pages):
    if ssm_update.kernel_update(pool.state, use_pallas):
        metrics.ssm_steps_kernel += K


def _note_prefill(metrics, cfg, n, tokens):
    metrics.ssm_slot_writes += n


# The per-slot rows are written by the prefill and decode programs only:
# nothing snapshots them beside a shared page, moves or rolls them back.
sm.register(hybrid_ssm.HybridSsmConfig, sm.ServedModel(
    name="recurrent state",
    prefill=prefill, decode_once=decode_once, zeros=_zeros,
    kv_pages=lambda pool: pool.pages,
    init_params=lambda cfg, quantize: hybrid_ssm.init_params_on_device(
        cfg, quantize=quantize),
    token_bytes=lambda cfg, ecfg, axis_sizes: {
        "K and V": kv_token_bytes(cfg, cfg.cache_rows, ecfg.kv_dtype)},
    fixed_pools=_fixed_pools, caches=_caches,
    lanes=(sm.mesh_lane("tensor parallelism: state-space heads have "
                        "no sharded form"),
           sm.MULTIHOST, sm.PREEMPT_PREFILL),
    why_not=("those lanes re-read, share, move or roll back cache "
             "and would have to carry the state too"),
    state_slots=True,
    counters=("ssm_slot_writes", "ssm_steps_kernel"),
    gauges=("ssm_state_bytes_per_slot", "ssm_layers"),
    describe=_describe, note_decode=_note_decode,
    note_prefill=_note_prefill))
