"""Gated attention over window and global layers beside a share of the
experts (models/gated_window_moe.py) as served, over the kv_cache.WindowPool
SmallThinker's block stands on (`GatedWindowMoeConfig.window_rows`): the
two bodies serving/engine_model.py's step programs run, and the entry
serving/served_models.py hands the serving side. Everything that is the
pool's is serving/window_rows.py's.
"""

from __future__ import annotations

import jax.numpy as jnp

from generativeaiexamples_tpu.models import gated_window_moe as gwm
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving import window_rows


def prefill(params, cfg, pool, tokens, lengths, tables, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S]: every layer's K and V (normed, a sliding layer's
    rotated) go to its group's pages. -> (last-position logits [N, V],
    pool)."""
    x, kv, _ = gwm.walk_prompt(
        params, cfg, tokens, lengths, use_pallas,
        encode=pool.glob.encode_pages)  # [L, N, KH, S, ...] x 4
    pool = window_rows.write_prompt_pages(cfg, pool, kv, tables)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)  # [N,1,D]
    return gwm.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for this model, the blocks unrolled in published
    order: the fused product, the paged kernel over the layer's group
    (window_rows.StepRows), the gate between the kernel's output and W_o,
    then the dense feed-forward or the held experts' part. `mask` [B]:
    the live slots; an idle slot's expert pairs are left out. Returns
    (logits [B, V], pool, pairs each held expert took in each expert
    layer [Lm, E], the router's choices [Lm, B, k])."""
    B = tokens.shape[0]
    positions = (lengths - 1)[:, None]
    step = window_rows.StepRows(cfg, pool, tables, lengths, mask, use_pallas)
    x = gwm.embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    sliced, experts = gwm.split_experts(params["layers"])
    counts, choices = [], []
    for l, (kind, row) in enumerate(gwm.layer_plan(cfg)):
        e = l - cfg.n_dense_layers
        w = gwm.take_layer(params["dense"], l) if e < 0 \
            else gwm.take_layer(sliced, e)
        q, k, v, g = gwm.project(cfg, x, w, positions,
                                 bool(cfg.rope_layout[l]))
        out = step.attend(kind, row, q[:, 0], k[:, 0], v[:, 0])
        x = gwm.gated_out(cfg, x, out.reshape(B, 1, -1), g, w)
        if e < 0:
            x, _, _ = gwm.feed_forward(cfg, x, w)
        else:
            x, n, idx = gwm.feed_forward(cfg, x, w, experts, e, use_pallas,
                                         mask)
            counts.append(n)
            choices.append(idx[:, 0])
    logits = gwm.logits_of(cfg, params, x)[:, 0]
    return logits, step.pool(pool), jnp.stack(counts), jnp.stack(choices)


sm.register(gwm.GatedWindowMoeConfig, window_rows.entry(
    "gated window layers", prefill, decode_once,
    lambda cfg, quantize: gwm.init_params_on_device(cfg, quantize=quantize)))
