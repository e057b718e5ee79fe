"""Window and full attention in one model (models/window_attn_moe.py) as
served, over a kv_cache.WindowPool and, where every other model takes one
page table, a kv_cache.WindowTables (`WindowAttnMoeConfig.window_rows`):
the two bodies serving/engine_model.py's step programs run, and the entry
serving/served_models.py hands the serving side. What is the POOL's and
not this block's (the page writes into two tables, a step's append and
kernel call, the memory plan's lines, the refusal) is
serving/window_rows.py's, shared with serving/served_gated_window.py.
"""

from __future__ import annotations

import jax.numpy as jnp

from generativeaiexamples_tpu.models import window_attn_moe
from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving import window_rows


def prefill(params, cfg, pool, tokens, lengths, tables, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S]: every layer's K and V go to its group's pages
    (window_rows.write_prompt_pages). -> (last-position logits [N, V],
    pool)."""
    x, kv, _ = window_attn_moe.walk_prompt(
        params, cfg, tokens, lengths, use_pallas,
        encode=pool.glob.encode_pages)  # [L, N, KH, S, ...] x 4
    pool = window_rows.write_prompt_pages(cfg, pool, kv, tables)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)  # [N,1,D]
    return window_attn_moe.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for a model with window layers, the blocks unrolled in
    published order, each layer's append and kernel call
    window_rows.StepRows'. The router reads the ATTENTION's input, so a
    layer's experts and gates depend on nothing its attention computes.
    `mask` [B]: the live slots. Returns (logits [B, V], pool, pairs each
    expert took in each block [L, E], the router's choices [L, B, k])."""
    positions = (lengths - 1)[:, None]
    step = window_rows.StepRows(cfg, pool, tables, lengths, mask, use_pallas)
    x = window_attn_moe.embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    sliced, experts = window_attn_moe.split_experts(params["layers"])
    counts, choices = [], []
    for l, (kind, row) in enumerate(window_attn_moe.layer_plan(cfg)):
        w = window_attn_moe.take_layer(sliced, l)
        h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        idx, gates = window_attn_moe.route(cfg, h[:, 0], w["router"])
        q, k, v = window_attn_moe.project_qkv(cfg, h, w, positions,
                                              cfg.rope_layout[l])
        out = step.attend(kind, row, q[:, :, 0], k[:, :, 0], v[:, :, 0])
        x = window_attn_moe.attn_out(cfg, x, out[:, :, None, :], w)
        x, n = window_attn_moe.feed_forward(cfg, x, w, experts, l, idx,
                                            gates, use_pallas, mask)
        counts.append(n)
        choices.append(idx)
    logits = window_attn_moe.logits_of(cfg, params, x)[:, 0]
    return logits, step.pool(pool), jnp.stack(counts), jnp.stack(choices)


sm.register(window_attn_moe.WindowAttnMoeConfig, window_rows.entry(
    "window layers", prefill, decode_once,
    lambda cfg, quantize: window_attn_moe.init_params_on_device(
        cfg, quantize=quantize)))
