"""Kimi-Linear-48B-A3B-Instruct (moonshotai, `model_type: "kimi_linear"`).
`linear_attn_config.kda_layers` names the layers (from 1) whose mixer is
Kimi Delta Attention: 32 heads of 128, a depthwise causal convolution of
`short_conv_kernel_size` taps on each of q, k and v, then per head on a
float32 state S [key, value]

    S_t = Diag(a_t) S_(t-1) + k_t u_t^T,  u_t = b_t (v_t - (Diag(a_t) S_(t-1))^T k_t)
    o_t = S_t^T q_t

with a decay a_t per head and key channel and a step b_t per head; the
layers of `full_attn_layers` are DeepSeek-V3's latent attention with
`q_lora_rank` null and no rotation (`mla_use_nope`). Layer 1's
feed-forward is a dense SwiGLU; every later one routes a token to
`num_experts_per_token` of the experts (sigmoid scores, chosen by score +
a correction bias, weighed by their scores renormalised and scaled by
`routed_scaling_factor`) and adds one shared expert.

The configuration file runs ONE chip's share of a stated deployment
(model-configs guide, section 4): `num_experts` in the file counts the
experts HELD HERE (`expert_offset` on), `published.num_experts` is the
router's width; the vocabulary is a slice; every layer is kept. What the
experts elsewhere would add is left out of program and reference alike.

The same three parts as `llama.py`, sharing nothing between them: (1, 2,
6) how the PROGRAM builds this model; (3) the plain reference, from the
parameter tree's leaves; (4, 5) the work of a step on THIS chip, counted
from the file's shapes with no JAX. `weight` and `rms_norm` come from
`llama.py`, as benchmark/README.md allows.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.architectures.llama import BYTES, rms_norm, weight

KDA, MLA = "kda", "mla"
LOW_RANK = 128  # the decay's and the gate's: the config has no key (assumed)


def layer_types(c: Dict[str, Any]):
    la = c["linear_attn_config"]
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    n = int(c["num_hidden_layers"])
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError("kimilinear: kda_layers and full_attn_layers must "
                         f"name each of the {n} layers once")
    return [KDA if l in kda else MLA for l in range(1, n + 1)]


def kda_layers(c: Dict[str, Any]) -> int:
    return layer_types(c).count(KDA)


def mla_layers(c: Dict[str, Any]) -> int:
    return layer_types(c).count(MLA)


def d_inner(c: Dict[str, Any]) -> int:
    la = c["linear_attn_config"]
    return int(la["num_heads"]) * int(la["head_dim"])


def router_width(c: Dict[str, Any]) -> int:
    return int(c["published"]["num_experts"])


def held(c: Dict[str, Any]) -> int:
    return int(c["num_experts"])


def dense_layers(c: Dict[str, Any]) -> int:
    return int(c["first_k_dense_replace"])


def moe_layers(c: Dict[str, Any]) -> int:
    return int(c["num_hidden_layers"]) - dense_layers(c)


def latent_width(c: Dict[str, Any]) -> int:
    return int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"])


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models.linear_attn_moe import (
            LinearAttnMoeConfig)
    except ImportError as e:  # a program from before linear attention
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'kimilinear' (no linear attention with a delta "
                         f"rule, no state beside a latent row): {e}")
    if config["q_lora_rank"] is not None or not config["mla_use_nope"] \
            or config["moe_router_activation_func"] != "sigmoid" \
            or int(config["num_expert_group"]) != 1 \
            or int(config["topk_group"]) != 1 \
            or int(config["num_shared_experts"]) != 1 \
            or int(config["moe_layer_freq"]) != 1 \
            or int(config["num_nextn_predict_layers"]) != 0:
        raise ValueError("kimilinear: direct queries, an unrotated row, "
                         "sigmoid scores, one expert group, one shared "
                         "expert and experts in every layer past the dense "
                         "ones are what is written")
    la, s = config["linear_attn_config"], config["serving"]
    return LinearAttnMoeConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        layer_types=tuple(layer_types(config)),
        kda_heads=int(la["num_heads"]), kda_head_dim=int(la["head_dim"]),
        kda_conv=int(la["short_conv_kernel_size"]),
        kda_rank=min(LOW_RANK, int(la["head_dim"])),
        kda_chunk=int(s.get("kda_chunk", 64)),
        kda_sub=int(s.get("kda_sub", 16)),
        n_heads=int(config["num_attention_heads"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        mlp_dim=int(config["intermediate_size"]),
        moe_mlp_dim=int(config["moe_intermediate_size"]),
        n_dense_layers=dense_layers(config),
        n_routed_experts=router_width(config),
        n_experts_per_tok=int(config["num_experts_per_token"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["moe_renormalize"]),
        experts_held=held(config), expert_offset=int(config["expert_offset"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["model_max_length"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(s.get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device -----------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    from generativeaiexamples_tpu.models import linear_attn_moe

    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'kimilinear' is one "
                         "chip's share of its group; it takes one device")
    quantize = config["serving"]["quantize_weights"] == "int8"
    return linear_attn_moe.init_params_on_device(mcfg, seed,
                                                 quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# The equations of ISSUE 48 in float32 `jax.numpy` under `highest`
# precision: the delta-rule recurrence as the plain loop over tokens (no
# chunks, no cache, no kernel), un-absorbed causal attention (every head's
# keys and values built from the latent), one expert's weights in float32
# at a time. It reads only the parameter tree's leaves (four stacks in
# layer order: `kda`, `mla`, `dense`, `layers`) and shares no code with
# the program. The same share as the program: the held experts (the
# router still scores all `published.num_experts` and normalises over all
# selected), the sliced vocabulary.

def _piece(w, index=None, rows=None, cols=None):
    """A float32 piece of a leaf (codes times per-column scales, or a
    plain array): `index` (traced) on the leading axis, then static rows
    and columns."""
    q, s = (w.q, w.s) if hasattr(w, "q") else (w, None)
    if index is not None:
        q, s = q[index], None if s is None else s[index]
    rows, cols = rows or slice(None), cols or slice(None)
    q = q[rows, cols].astype(jnp.float32)
    return q if s is None else q * s[cols].astype(jnp.float32)[None, :]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("shape",))
def _kda_mixer(x, w, *, shape):
    """One KDA layer's branch for a sequence x [S, D] -> (branch output
    [S, D], the state after the last token [H, d, d])."""
    H, d, r, eps = shape
    S, di = x.shape[0], H * d
    h = rms_norm(x, weight(w["ln1"]), eps)
    qkv = h @ weight(w["w_qkv"])
    taps = weight(w["conv_w"])                       # [K, 3 * di], oldest first
    K = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, 3 * di)), qkv])
    conv = jax.nn.silu(sum(padded[j:j + S] * taps[j] for j in range(K)))
    q, k, v = (conv[:, i * di:(i + 1) * di].reshape(S, H, d)
               for i in range(3))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    ab = h @ weight(w["w_ab"])
    f = ab[:, :r] @ weight(w["w_fb"]) + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f.reshape(S, H, d))
    gate = jax.nn.sigmoid((ab[:, r:2 * r] @ weight(w["w_gb"])
                           ).reshape(S, H, d))
    beta = jax.nn.sigmoid(ab[:, 2 * r:])             # [S, H]

    def token(s, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hc,hcv->hv", k_t, s))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hc,hcv->hv", q_t, s)

    state, o = jax.lax.scan(token, jnp.zeros((H, d, d)), (q, k, v, g, beta))
    o = rms_norm(o, weight(w["o_norm"]), eps) * gate
    return o.reshape(S, di) @ weight(w["wo"]), state


@functools.partial(jax.jit, static_argnames=("shape",))
def _mla_mixer(x, w, *, shape):
    H, Dn, R, Dv, C, eps = shape
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = (h @ weight(w["w_q"])).reshape(S, H, Dn + R)
    ckv = h @ weight(w["w_kva"])
    c = rms_norm(ckv[:, :C], weight(w["kv_norm"]), eps)
    kv = (c @ weight(w["w_kvb"])).reshape(S, H, Dn + Dv)
    k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(
        ckv[:, None, C:], (S, H, R))], -1)           # k_r: one head, unrotated
    s = jnp.einsum("qhd,khd->hqk", q, k) * (Dn + R) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., Dn:])
    return a.reshape(S, H * Dv) @ weight(w["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, ln, *, eps):
    return rms_norm(x, weight(ln), eps)


@functools.partial(jax.jit, static_argnames=("a", "b"))
def _dense_columns(h, w, *, a, b):
    """The feed-forward's hidden columns a..b: an exact summand."""
    cols = slice(a, b)
    return _swiglu(h, _piece(w["w_gate"], cols=cols),
                   _piece(w["w_up"], cols=cols),
                   _piece(w["w_down"], rows=cols))


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "norm"))
def _route_and_share(h, w, *, top_k, scaling, norm):
    """-> (the shared expert's output, the router's choice [S, top_k] by
    score + bias, its weights [S, top_k] from the scores alone)."""
    s = jax.nn.sigmoid(h @ weight(w["router"]))               # [S, all]
    _, idx = jax.lax.top_k(s + w["router_bias"], top_k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    wts = scaling * (top / jnp.sum(top, -1, keepdims=True) if norm else top)
    y = _swiglu(h, weight(w["w_gate"]), weight(w["w_up"]),
                weight(w["w_down"]))
    return y, idx, wts


@jax.jit
def _held_expert(h, idx, wts, gate_up, down, e, expert):
    """Held expert `e` (the model's expert `expert`), weighted."""
    me = down.shape[-2]
    we = jnp.sum(jnp.where(idx == expert, wts, 0.0), -1)       # [S]
    ye = _swiglu(h, _piece(gate_up, e, cols=slice(0, me)),
                 _piece(gate_up, e, cols=slice(me, 2 * me)), _piece(down, e))
    return we[:, None] * ye


DENSE_COLUMN_PIECES = 4


@jax.jit
def _head(x, ln_f, out_w, eps):
    return rms_norm(x, weight(ln_f), eps) @ weight(out_w)


def reference_forward(config: Dict[str, Any], params, token_ids):
    """-> (logits [S, vocab] float32, the KDA layers' states after the
    last token [Lk, H, d, d], the router's choices [expert layers, S,
    top_k])."""
    eps = float(config["rms_norm_eps"])
    la = config["linear_attn_config"]
    kda_shape = (int(la["num_heads"]), int(la["head_dim"]),
                 min(LOW_RANK, int(la["head_dim"])), eps)
    mla_shape = (int(config["num_attention_heads"]),
                 int(config["qk_nope_head_dim"]),
                 int(config["qk_rope_head_dim"]), int(config["v_head_dim"]),
                 int(config["kv_lora_rank"]), eps)
    seen = {KDA: 0, MLA: 0}
    states, choices = [], []

    def layer(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for l, kind in enumerate(layer_types(config)):
            i = seen[kind]
            seen[kind] += 1
            if kind == KDA:
                y, state = _kda_mixer(x, layer(params["kda"], i),
                                      shape=kda_shape)
                states.append(state)
            else:
                y = _mla_mixer(x, layer(params["mla"], i), shape=mla_shape)
            x = x + y
            if l < dense_layers(config):
                w = layer(params["dense"], l)
                h = _normed(x, w["ln2"], eps=eps)
                m = int(config["intermediate_size"])
                step = -(-m // DENSE_COLUMN_PIECES)
                for a in range(0, m, step):
                    x = x + _dense_columns(h, w, a=a, b=min(a + step, m))
                continue
            w = layer(params["layers"], l - dense_layers(config))
            h = _normed(x, w["ln2"], eps=eps)
            y, idx, wts = _route_and_share(
                h, w, top_k=int(config["num_experts_per_token"]),
                scaling=float(config["routed_scaling_factor"]),
                norm=bool(config["moe_renormalize"]))
            for e in range(held(config)):  # the experts that live here
                y = y + _held_expert(h, idx, wts, w["we_gate_up"],
                                     w["we_down"], e,
                                     int(config["expert_offset"]) + e)
            x = x + y
            choices.append(idx)
        logits = _head(x, params["ln_f"], params["lm_head"], eps)
    return logits, jnp.stack(states), jnp.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step on THIS chip -----------------------------------
# The algorithm's work for this chip's share: every weight outside the
# experts is read once a program; of the held experts, those that some
# token chose (EXPECTED under uniform routing); a KDA layer reads and
# writes each live sequence's float32 state once; a latent layer reads
# 576 values a cached token whatever the array's padding.

def kda_params(c: Dict[str, Any]) -> int:
    d, di = int(c["hidden_size"]), d_inner(c)
    heads = int(c["linear_attn_config"]["num_heads"])
    r = min(LOW_RANK, int(c["linear_attn_config"]["head_dim"]))
    return 4 * d * di + 2 * (d * r + r * di) + d * heads


def mla_params(c: Dict[str, Any]) -> int:
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    dn, r, dv = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                 int(c["v_head_dim"]))
    cw = int(c["kv_lora_rank"])
    return d * h * (dn + r) + d * (cw + r) + cw * h * (dn + dv) + h * dv * d


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def always_read_params(c: Dict[str, Any]) -> int:
    """Weights every program reads whatever the routing: the mixers, the
    dense feed-forward, the shared experts, the head (int8)."""
    d = int(c["hidden_size"])
    return (kda_layers(c) * kda_params(c) + mla_layers(c) * mla_params(c)
            + dense_layers(c) * 3 * d * int(c["intermediate_size"])
            + moe_layers(c) * expert_params(c) + head_params(c))


def small_bytes(c: Dict[str, Any]) -> float:
    """The router (bf16) and its bias, the convolutions' taps (bf16)."""
    taps = int(c["linear_attn_config"]["short_conv_kernel_size"])
    return float(moe_layers(c) * router_width(c)
                 * (int(c["hidden_size"]) * BYTES["bfloat16"] + 4)
                 + kda_layers(c) * taps * 3 * d_inner(c) * BYTES["bfloat16"])


def local_share(c: Dict[str, Any]) -> float:
    """The share of a token's routed pairs that falls on held experts."""
    return held(c) / router_width(c)


def experts_hit(c: Dict[str, Any], tokens: float) -> float:
    """Held experts some token of `tokens` chose, expected, uniform
    routing: E * (1 - (1 - k / all) ** tokens)."""
    p = int(c["num_experts_per_token"]) / router_width(c)
    return held(c) * (1.0 - (1.0 - p) ** max(tokens, 0.0))


def state_bytes_per_sequence(c: Dict[str, Any]) -> float:
    """One KDA layer's float32 state: [heads, key, value]."""
    return 4.0 * d_inner(c) * int(c["linear_attn_config"]["head_dim"])


def tail_bytes_per_sequence(c: Dict[str, Any]) -> float:
    taps = int(c["linear_attn_config"]["short_conv_kernel_size"])
    return float((taps - 1) * 3 * d_inner(c) * BYTES["bfloat16"])


def kv_bytes_per_token(c: Dict[str, Any]) -> float:
    return float(mla_layers(c) * latent_width(c)
                 * BYTES[c["serving"]["kv_dtype"]])


def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def _routed_flops(c: Dict[str, Any], tokens: float) -> float:
    return 2.0 * tokens * int(c["num_experts_per_token"]) * local_share(c) \
        * expert_params(c) * moe_layers(c)


def _expert_bytes(c: Dict[str, Any], tokens: float) -> float:
    return float(moe_layers(c) * experts_hit(c, tokens) * expert_params(c)
                 * _weight_bytes(c))


def _state_flops_per_token(c: Dict[str, Any]) -> float:
    """a * S, k^T S, + k (x) u, S^T q: about 7 operations an element."""
    return 7.0 * d_inner(c) * int(c["linear_attn_config"]["head_dim"])


def _absorbed_flops_per_cached_token(c: Dict[str, Any]) -> float:
    h, cw = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    return 2.0 * h * latent_width(c) + 2.0 * h * cw  # scores, then values


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each, on this chip."""
    h, cw = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    flops = 2.0 * batch * always_read_params(c) + _routed_flops(c, batch)
    flops += batch * kda_layers(c) * _state_flops_per_token(c)
    flops += batch * context * mla_layers(c) \
        * _absorbed_flops_per_cached_token(c)
    flops += batch * mla_layers(c) * 2.0 * h * cw * (
        int(c["qk_nope_head_dim"]) + int(c["v_head_dim"]))
    bytes_ = float(always_read_params(c) * _weight_bytes(c))
    bytes_ += small_bytes(c) + _expert_bytes(c, batch)
    bytes_ += batch * kda_layers(c) * 2.0 * (
        state_bytes_per_sequence(c) + tail_bytes_per_sequence(c))
    bytes_ += batch * (context + 1) * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions. The KDA layers' work
    is counted as the recurrence's (7 operations a state element and
    token), not the chunked form's extra matmuls; un-absorbed attention."""
    h = int(c["num_attention_heads"])
    qk = int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
    body = always_read_params(c) - head_params(c)
    flops = 2.0 * prompt_tokens * body + _routed_flops(c, prompt_tokens)
    flops += prompt_tokens * kda_layers(c) * _state_flops_per_token(c)
    flops += prompt_tokens * mean_prompt * h * (qk + int(c["v_head_dim"])) \
        * mla_layers(c)  # causal: half of 2 * (qk + dv) * S
    sequences = prompt_tokens / max(mean_prompt, 1.0)
    flops += 2.0 * sequences * head_params(c)
    per_program = prompt_tokens / max(programs, 1.0)
    bytes_ = programs * (float(always_read_params(c) * _weight_bytes(c))
                         + small_bytes(c) + _expert_bytes(c, per_program))
    bytes_ += prompt_tokens * kv_bytes_per_token(c)
    bytes_ += sequences * kda_layers(c) * (state_bytes_per_sequence(c)
                                           + tail_bytes_per_sequence(c))
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def attention_kernel(c: Dict[str, Any], calls: float, batch: float,
                     context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the absorbed paged kernel (one call
    reads ONE latent layer's rows of `batch` sequences of `context`
    tokens): the rows in, scores and values; q in and o_lat back."""
    h, cw = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    b = BYTES[c["serving"]["kv_dtype"]]
    bytes_ = calls * batch * (context * latent_width(c) * b
                              + h * (latent_width(c) + cw) * BYTES["bfloat16"])
    flops = calls * batch * context * _absorbed_flops_per_cached_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


MOE_KERNEL_CALLS_PER_LAYER = 2  # gate-and-up, then down


def moe_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the grouped int8 matmul in decode
    steps of `batch` tokens (two calls an expert layer: gate-and-up,
    down): the weights of the held experts that are hit, expected, and
    the pairs' rows in and out."""
    d, me = int(c["hidden_size"]), int(c["moe_intermediate_size"])
    layer_steps = calls / MOE_KERNEL_CALLS_PER_LAYER
    pairs = batch * int(c["num_experts_per_token"]) * local_share(c)
    flops = layer_steps * 2.0 * pairs * expert_params(c)
    bytes_ = layer_steps * (
        experts_hit(c, batch) * expert_params(c) * _weight_bytes(c)
        + pairs * (d + 2 * me + me + d) * BYTES["bfloat16"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def ssm_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the state-update kernel
    (`kda_state_update`: one call is ONE KDA layer's step for `batch`
    live sequences; the name is the one readers/trace_ssm_kernel.py asks
    an entry for): each sequence's float32 state read and written once,
    about 7 operations an element; the rows a, k, q, v, b in and o out."""
    rows = 4.0 * 6 * d_inner(c)
    bytes_ = calls * batch * (2.0 * state_bytes_per_sequence(c) + rows)
    flops = calls * batch * _state_flops_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """`paged_attention_mla` runs once a latent layer a step."""
    return mla_layers(config)


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and BOTH pools (the latent
    layers' pages, the per-slot state and tails) as `ShapeDtypeStruct`s
    on ONE described device; mesh is None."""
    from jax.sharding import SingleDeviceSharding

    from generativeaiexamples_tpu.models import linear_attn_moe
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    if len(devices) > 1:
        raise ValueError("kimilinear: one chip's share of its group")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        linear_attn_moe.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype), slots=ecfg.max_batch_size))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
