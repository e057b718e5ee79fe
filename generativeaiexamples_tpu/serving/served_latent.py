"""Latent attention and sparse experts (models/latent_moe.py) as served:
one [c_kv ; k_rope] vector a token and row for all heads
(`LatentMoeConfig.latent_row`) in a kv_cache.LatentPagePool, the two
bodies the step programs of serving/engine_model.py run over it, and the
entry serving/served_models.py hands the serving side.
"""

from __future__ import annotations

import logging

import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import hyper_connections as residual
from generativeaiexamples_tpu.models import latent_moe
from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.serving import hc_mix
from generativeaiexamples_tpu.serving import served_models as sm
from generativeaiexamples_tpu.serving.flight import EV_RESIDUAL_MIX
from generativeaiexamples_tpu.serving.kv_cache import (
    LatentPagePool, latent_token_bytes, token_slots)

_LOG = logging.getLogger(__name__)


def prefill(params, cfg, pool, tokens, lengths, table_rows, use_pallas, *,
            mesh=None, state_slots=None):
    """Prompts [N, S] in their un-absorbed form (keys and values built
    from the prompt's own latent rows); the rows of every layer go to
    the slots' pages in one write. -> (last-position logits [N, V], pool)."""
    N, S = tokens.shape
    ps = pool.page_size
    x, rows, _ = latent_moe.walk_prompt(params, cfg, tokens, lengths,
                                        use_pallas, hc_mix.mixer(use_pallas))
    pages = pool.encode_pages(rows)  # [R, N, S, W]
    pages = pages.reshape(pages.shape[0], N * (S // ps), ps, -1)
    pool = pool.write_pages(pages, table_rows.reshape(-1))
    at = (lengths - 1)[(slice(None),) + (None,) * (x.ndim - 1)]
    last = jnp.take_along_axis(
        x, at.astype(jnp.int32), axis=1)  # [N, 1, D], or [N, 1, n, D]
    return latent_moe.logits_of(cfg, params, last)[:, 0], pool


def decode_once(params, cfg, pool, tokens, page_tables, lengths, use_pallas,
                mask=None, *, mesh=None, n_steps=1):
    """_decode_once for a latent model, write-then-attend: every block
    appends the new token's [c_kv ; k_rope] and attends in the absorbed
    form through the paged kernel (serving/paged_attention_mla.py); the
    blocks unrolled, each weight an operand of its matmul and the held
    experts' stacks read where they lie. `mask` [B]: the slots whose
    token-expert pairs count and are computed (idle slots: none).
    Returns (logits [B, V], pool, pairs each held expert took in each
    expert block [Lm, E], the router's choices [Lm, B, k])."""
    from generativeaiexamples_tpu.serving.paged_attention_mla import (
        paged_attention_mla_dispatch)

    B = tokens.shape[0]
    ps = pool.page_size
    C, _ = cfg.latent_row
    positions = (lengths - 1)[:, None]
    slots = token_slots(1, page_tables[jnp.arange(B), (lengths - 1) // ps],
                        (lengths - 1) % ps)
    x = latent_moe.embed(cfg, params, tokens)[:, None]  # [B, 1, (n,) D]
    mix = hc_mix.mixer(use_pallas)
    whole = residual.leaves(cfg)  # the mixing's: read where they lie

    def block(x, pool, w, row, l, experts=None):  # l: index in its stack
        u, carry = residual.open(cfg, x, w, "attn", mix, l)
        h = rms_norm(u, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
        q_nope, q_rope, new = latent_moe.project_latent(cfg, h, w, positions)
        pool = pool.append(row, slots, new[:, 0])

        def attend(q):
            c, r = pool.attention_operands(row)
            q = jnp.pad(q, ((0, 0), (0, 0), (0, c.shape[-1] - q.shape[-1])))
            return paged_attention_mla_dispatch(
                q, c, r, page_tables, lengths, latent=C,
                scale=cfg.softmax_scale, use_pallas=use_pallas)

        out = latent_moe.attend_cached(cfg, q_nope[:, 0], q_rope[:, 0], w,
                                       attend)
        x = residual.close(
            cfg, x, latent_moe.heads_out(out[:, :, None, :], w), carry)
        u, carry = residual.open(cfg, x, w, "ffn", mix, l)
        y, counts, idx = latent_moe.feed_forward(cfg, u, w, experts, l,
                                                 use_pallas, mask)
        return residual.close(cfg, x, y, carry), pool, counts, idx

    for l in range(cfg.n_dense_layers):
        x, pool, _, _ = block(x, pool, latent_moe.take_layer(
            params["dense"], l, skip=whole), l, l)
    counts, choices = [], []
    _, experts = latent_moe.split_experts(params["layers"])
    for l in range(cfg.n_moe_layers):
        w = latent_moe.take_layer(params["layers"], l,
                                  skip=latent_moe.EXPERT_WEIGHTS + whole)
        x, pool, n, idx = block(x, pool, w, cfg.n_dense_layers + l, l,
                                experts)
        counts.append(n)
        choices.append(idx[:, 0])
    logits = latent_moe.logits_of(cfg, params, x)[:, 0]
    return logits, pool, jnp.stack(counts), jnp.stack(choices)


def _zeros(cfg, n_pages, page_size, dtype, sharding, scale_sharding, slots):
    if dtype == jnp.int8:
        raise ValueError(
            "engine.kv_dtype int8: a latent page pool "
            "(kv_cache.LatentPagePool) has no int8 form yet")
    return LatentPagePool.zeros(cfg, n_pages, page_size, dtype, sharding)


def _mixes(cfg, tokens: int) -> int:
    """Branches mixed for `tokens` tokens through every block."""
    return tokens * 2 * cfg.n_layers


def _describe(metrics, cfg, ecfg, pool, n_pages):
    if cfg.hc_mult > 1:
        metrics.hc_streams = cfg.hc_mult
        _LOG.info("residual: %d streams, %d branches, %d passes",
                  cfg.hc_mult, _mixes(cfg, 1), cfg.hc_sinkhorn_iters)


def _note_decode(metrics, cfg, lengths, active_mask, K, pool, use_pallas,
                 max_pages):
    """A decode block of a model with several streams: counts the
    branches mixed and returns the block's `residual_mix` event (a = the
    mixes a step; b = the stream's bytes a token)."""
    if cfg.hc_mult == 1:
        return None
    a_step = _mixes(cfg, int(np.count_nonzero(active_mask)))
    metrics.hc_mixes += a_step * K
    return (EV_RESIDUAL_MIX, float(a_step), float(
        cfg.hc_mult * cfg.dim * jnp.dtype(cfg.residual_dtype).itemsize))


def _note_prefill(metrics, cfg, n, tokens):
    if cfg.hc_mult > 1:
        metrics.hc_mixes += _mixes(cfg, tokens)


# The pool is written by the prefill and decode programs only: nothing
# reads its pages back, moves or shares them. A token's bytes: ONE vector
# a row for all heads in whole 128-lane tiles, and no array for V.
sm.register(latent_moe.LatentMoeConfig, sm.ServedModel(
    name="latent attention",
    prefill=prefill, decode_once=decode_once, zeros=_zeros,
    init_params=lambda cfg, quantize: latent_moe.init_params_on_device(
        cfg, quantize=quantize),
    token_bytes=lambda cfg, ecfg, axis_sizes: {
        "latent rows": latent_token_bytes(cfg, ecfg.kv_dtype)},
    caches=lambda cfg: (
        f"model caches a latent row of {sum(cfg.latent_row)} values "
        f"a token and layer (latent attention)"),
    lanes=(sm.mesh_lane("tensor parallelism over heads: a latent "
                        "row is one vector for all heads"),
           sm.kv_dtype_lane(True, "an int8 latent pool"),
           sm.MULTIHOST),
    why_not="those lanes have no latent form",
    counters=("hc_mixes",), gauges=("hc_streams",),
    describe=_describe, note_decode=_note_decode,
    note_prefill=_note_prefill))
