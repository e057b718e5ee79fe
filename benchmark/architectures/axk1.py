"""A.X-K1 (skt/A.X-K1, `model_type: "axk1"`): the DeepSeek-V3 family's
block under A.X-K1's keys. Latent attention (MLA): a token caches ONE
vector `[c_kv ; k_rope]` of kv_lora_rank + qk_rope_head_dim values a
layer, shared by all heads. `first_k_dense_replace` leading dense
layers, then layers of `n_routed_experts` experts (sigmoid scores, the
`num_experts_per_tok` largest, weights normalised over the selected and
scaled by `routed_scaling_factor`) plus one shared expert.

The configuration file runs ONE chip's share of a stated deployment
(model-configs guide, section 4): `n_routed_experts` in the file counts
the experts HELD HERE (`expert_offset` on), `published.n_routed_experts`
is the router's width; the vocabulary is a slice; what the experts
elsewhere would add is left out of program and reference alike.

The same three parts as `llama.py`, sharing nothing between them: (1, 2,
6) how the PROGRAM builds this model; (3) the plain reference, from the
parameter tree's leaves; (4, 5) the work of a step on THIS chip, counted
from the file's shapes with no JAX. `weight` and `rms_norm` come from
`llama.py`, as benchmark/README.md allows.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.architectures.llama import BYTES, rms_norm, weight


def router_width(c: Dict[str, Any]) -> int:
    return int(c["published"]["n_routed_experts"])


def held(c: Dict[str, Any]) -> int:
    return int(c["n_routed_experts"])


def dense_layers(c: Dict[str, Any]) -> int:
    return int(c["first_k_dense_replace"])


def moe_layers(c: Dict[str, Any]) -> int:
    return int(c["num_hidden_layers"]) - dense_layers(c)


def latent_width(c: Dict[str, Any]) -> int:
    return int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"])


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models.latent_moe import LatentMoeConfig
        from generativeaiexamples_tpu.models.llama import YarnScaling
    except ImportError as e:  # a program from before latent attention
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'axk1' (no latent attention, no sparse experts): "
                         f"{e}")
    rs = config["rope_scaling"]
    if rs["type"] != "yarn" or config["topk_method"] != "none" \
            or config["scoring_func"] != "sigmoid":
        raise ValueError("axk1: YaRN, sigmoid scores and topk_method "
                         "'none' are what is written")
    return LatentMoeConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_dense_layers=dense_layers(config),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        mlp_dim=int(config["intermediate_size"]),
        moe_mlp_dim=int(config["moe_intermediate_size"]),
        n_routed_experts=router_width(config),
        n_experts_per_tok=int(config["num_experts_per_tok"]),
        n_shared_experts=int(config["n_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=held(config),
        expert_offset=int(config["expert_offset"]),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]), beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"])),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device -----------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    from generativeaiexamples_tpu.models import latent_moe

    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'axk1' is one chip's "
                         "share of its group; it takes one device")
    quantize = config["serving"]["quantize_weights"] == "int8"
    return latent_moe.init_params_on_device(mcfg, seed,
                                            quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# The equations of ISSUE 33 (DeepSeek-V3's, with A.X-K1's keys) in float32
# `jax.numpy` under `highest` precision: UN-absorbed attention (every
# head's keys and values built from the latent), a Python loop over the
# held experts, no cache, no kernel, no batching, one layer's weights in
# float32 at a time. It reads only the parameter tree's leaves and shares
# no code with the program. The same share as the program: the held
# experts (the router still scores all `published.n_routed_experts` and
# normalises over all selected), the sliced vocabulary.

def yarn_inv_freq(dim: int, theta: float, rs: Dict[str, Any]):
    """Per-dimension blend of the interpolated (/ factor) and the
    original frequencies, along the linear ramp between the correction
    dimensions of beta_fast and beta_slow rotations at the original
    length (the family's `DeepseekV3YarnRotaryEmbedding`)."""
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    extra = theta ** (-2.0 * i / dim)
    return extra / float(rs["factor"]) * (1.0 - keep) + extra * keep


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(c: Dict[str, Any]) -> float:
    rs = c["rope_scaling"]
    m = yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])) ** -0.5 \
        * m * m


def _rope(x, inv_freq, gain):
    """x [S, n, R]: rotate the two halves (the program's pair layout;
    under seeded weights any fixed pairing is the same model)."""
    S, _, R = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * gain, jnp.sin(ang)[:, None, :] * gain
    x1, x2 = x[..., : R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, w, *, H, Dn, R, Dv, C, eps, scale, inv_freq, gain):
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    cq = rms_norm(h @ weight(w["w_qa"]), weight(w["q_norm"]), eps)
    q = (cq @ weight(w["w_qb"])).reshape(S, H, Dn + R)
    q = jnp.concatenate([q[..., :Dn], _rope(q[..., Dn:], inv_freq, gain)], -1)
    ckv = h @ weight(w["w_kva"])
    c = rms_norm(ckv[:, :C], weight(w["kv_norm"]), eps)
    k_rope = _rope(ckv[:, None, C:], inv_freq, gain)          # one head
    kv = (c @ weight(w["w_kvb"])).reshape(S, H, Dn + Dv)
    k = jnp.concatenate([kv[..., :Dn],
                         jnp.broadcast_to(k_rope, (S, H, R))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., Dn:])
    return x + a.reshape(S, H * Dv) @ weight(w["wo"])


def _slice(w, index=None, rows=None, cols=None):
    """A float32 piece of a leaf (codes times per-column scales, or a
    plain array): `index` on the leading axis, then rows and columns."""
    q, s = (w.q, w.s) if hasattr(w, "q") else (w, None)
    if index is not None:
        q, s = q[index], None if s is None else s[index]
    rows, cols = rows or slice(None), cols or slice(None)
    q = q[rows, cols].astype(jnp.float32)
    return q if s is None else q * s[cols].astype(jnp.float32)[None, :]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


# One jitted piece at a time, so that the float32 copy of ONE piece
# exists beside the served model: the attention (0.4 GB at the published
# widths), a quarter of the dense feed-forward's columns, the router and
# the shared expert, one held expert.

@functools.partial(jax.jit, static_argnames=("attn",))
def _attention_block(x, w, inv_freq, *, attn):
    return _attention(x, w, inv_freq=inv_freq, **dict(attn))


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, ln, *, eps):
    return rms_norm(x, weight(ln), eps)


@functools.partial(jax.jit, static_argnames=("a", "b"))
def _dense_columns(h, w, *, a, b):
    """The feed-forward's hidden columns a..b: an exact summand."""
    cols = slice(a, b)
    return _swiglu(h, _slice(w["w_gate"], cols=cols),
                   _slice(w["w_up"], cols=cols),
                   _slice(w["w_down"], rows=cols))


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "norm"))
def _route_and_share(h, w, *, top_k, scaling, norm):
    """-> (the shared expert's output, the router's choice [S, top_k],
    its weights [S, top_k])."""
    s = jax.nn.sigmoid(h @ weight(w["router"]))               # [S, all]
    top, idx = jax.lax.top_k(s, top_k)
    wts = scaling * (top / jnp.sum(top, -1, keepdims=True) if norm else top)
    y = _swiglu(h, weight(w["w_gate"]), weight(w["w_up"]),
                weight(w["w_down"]))
    return y, idx, wts


@functools.partial(jax.jit, static_argnames=("e", "expert"))
def _held_expert(h, idx, wts, gate_up, down, *, e, expert):
    """Held expert `e` (the model's expert `expert`), weighted."""
    me = down.shape[-2]
    we = jnp.sum(jnp.where(idx == expert, wts, 0.0), -1)       # [S]
    ye = _swiglu(h, _slice(gate_up, e, cols=slice(0, me)),
                 _slice(gate_up, e, cols=slice(me, 2 * me)), _slice(down, e))
    return we[:, None] * ye


DENSE_COLUMN_PIECES = 4


@jax.jit
def _head(x, ln_f, out_w, eps):
    return rms_norm(x, weight(ln_f), eps) @ weight(out_w)


def _attn_statics(c: Dict[str, Any]):
    rs = c["rope_scaling"]
    gain = yarn_mscale(float(rs["factor"]), float(rs["mscale"])) \
        / yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return tuple(dict(
        H=int(c["num_attention_heads"]), Dn=int(c["qk_nope_head_dim"]),
        R=int(c["qk_rope_head_dim"]), Dv=int(c["v_head_dim"]),
        C=int(c["kv_lora_rank"]), eps=float(c["rms_norm_eps"]),
        scale=softmax_scale(c), gain=gain).items())


def reference_forward(config: Dict[str, Any], params, token_ids):
    """-> (logits [S, vocab] float32, the router's choices
    [expert layers, S, top_k])."""
    eps = float(config["rms_norm_eps"])
    attn = _attn_statics(config)
    inv_freq = yarn_inv_freq(int(config["qk_rope_head_dim"]),
                             float(config["rope_theta"]),
                             config["rope_scaling"])
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for i in range(dense_layers(config)):
            w = jax.tree.map(lambda a: a[i], params["dense"])
            x = _attention_block(x, w, inv_freq, attn=attn)
            h = _normed(x, w["ln2"], eps=eps)
            m = int(config["intermediate_size"])
            for a in range(0, m, -(-m // DENSE_COLUMN_PIECES)):
                x = x + _dense_columns(
                    h, w, a=a, b=min(a + -(-m // DENSE_COLUMN_PIECES), m))
        for i in range(moe_layers(config)):
            w = jax.tree.map(lambda a: a[i], params["layers"])
            x = _attention_block(x, w, inv_freq, attn=attn)
            h = _normed(x, w["ln2"], eps=eps)
            y, idx, wts = _route_and_share(
                h, w, top_k=int(config["num_experts_per_tok"]),
                scaling=float(config["routed_scaling_factor"]),
                norm=bool(config["norm_topk_prob"]))
            for e in range(held(config)):  # the experts that live here
                y = y + _held_expert(
                    h, idx, wts, w["we_gate_up"], w["we_down"], e=e,
                    expert=int(config["expert_offset"]) + e)
            x = x + y
            choices.append(idx)
        logits = _head(x, params["ln_f"], params["lm_head"], eps)
    return logits, jnp.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step on THIS chip -----------------------------------
# The algorithm's work for this chip's share: every weight outside the
# experts is read once a program; of the held experts, those that some
# token chose (EXPECTED number under uniform routing, not all by fiat);
# the latent cache is 576 values a token and layer whatever the array's
# padding.

def attention_params(c: Dict[str, Any]) -> int:
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    qr, cw = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    dn, r, dv = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                 int(c["v_head_dim"]))
    return (d * qr + qr * h * (dn + r) + d * (cw + r) + cw * h * (dn + dv)
            + h * dv * d)


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def always_read_params(c: Dict[str, Any]) -> int:
    """Weights every program reads whatever the routing: attention, the
    dense feed-forward, the shared experts, the head (int8)."""
    d = int(c["hidden_size"])
    return (int(c["num_hidden_layers"]) * attention_params(c)
            + dense_layers(c) * 3 * d * int(c["intermediate_size"])
            + moe_layers(c) * expert_params(c) + head_params(c))


def router_bytes(c: Dict[str, Any]) -> float:
    return float(moe_layers(c) * int(c["hidden_size"]) * router_width(c)
                 * BYTES["bfloat16"])


def local_share(c: Dict[str, Any]) -> float:
    """The share of a token's routed pairs that falls on held experts."""
    return held(c) / router_width(c)


def experts_hit(c: Dict[str, Any], tokens: float) -> float:
    """Held experts some token of `tokens` chose, expected, uniform
    routing: E * (1 - (1 - k / all) ** tokens)."""
    p = int(c["num_experts_per_tok"]) / router_width(c)
    return held(c) * (1.0 - (1.0 - p) ** max(tokens, 0.0))


def kv_bytes_per_token(c: Dict[str, Any]) -> float:
    return float(int(c["num_hidden_layers"]) * latent_width(c)
                 * BYTES[c["serving"]["kv_dtype"]])


def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def _routed_flops(c: Dict[str, Any], tokens: float) -> float:
    return 2.0 * tokens * int(c["num_experts_per_tok"]) * local_share(c) \
        * expert_params(c) * moe_layers(c)


def _expert_bytes(c: Dict[str, Any], tokens: float) -> float:
    return float(moe_layers(c) * experts_hit(c, tokens) * expert_params(c)
                 * _weight_bytes(c))


def _absorbed_flops_per_cached_token(c: Dict[str, Any]) -> float:
    h, cw = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    return 2.0 * h * latent_width(c) + 2.0 * h * cw  # scores, then values


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each, on this chip."""
    h, cw = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    layers = int(c["num_hidden_layers"])
    flops = 2.0 * batch * always_read_params(c) + _routed_flops(c, batch)
    flops += batch * context * layers * _absorbed_flops_per_cached_token(c)
    # absorbing the up-projection: q_nope W_kb and o_lat W_vb, every head
    flops += batch * layers * 2.0 * h * cw * (
        int(c["qk_nope_head_dim"]) + int(c["v_head_dim"]))
    bytes_ = float(always_read_params(c) * _weight_bytes(c))
    bytes_ += router_bytes(c) + _expert_bytes(c, batch)
    bytes_ += batch * (context + 1) * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions; un-absorbed
    attention (keys of qk_nope + qk_rope, values of v_head_dim)."""
    h = int(c["num_attention_heads"])
    layers = int(c["num_hidden_layers"])
    qk = int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
    body = always_read_params(c) - head_params(c)
    flops = 2.0 * prompt_tokens * body + _routed_flops(c, prompt_tokens)
    flops += prompt_tokens * mean_prompt * h * (qk + int(c["v_head_dim"])) \
        * layers  # causal: half of 2 * (qk + dv) * S
    flops += 2.0 * (prompt_tokens / max(mean_prompt, 1.0)) * head_params(c)
    per_program = prompt_tokens / max(programs, 1.0)
    bytes_ = programs * (float(always_read_params(c) * _weight_bytes(c))
                         + router_bytes(c) + _expert_bytes(c, per_program))
    bytes_ += prompt_tokens * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def attention_kernel(c: Dict[str, Any], calls: float, batch: float,
                     context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the absorbed paged kernel (one call
    reads ONE layer's latent rows of `batch` sequences of `context`
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
    pairs = batch * int(c["num_experts_per_tok"]) * local_share(c)
    flops = layer_steps * 2.0 * pairs * expert_params(c)
    bytes_ = layer_steps * (
        experts_hit(c, batch) * expert_params(c) * _weight_bytes(c)
        + pairs * (d + 2 * me + me + d) * BYTES["bfloat16"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """`paged_attention_mla` runs once a layer a step."""
    return int(config["num_hidden_layers"])


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and the latent page pool
    as `ShapeDtypeStruct`s on ONE described device; mesh is None."""
    from jax.sharding import SingleDeviceSharding

    from generativeaiexamples_tpu.models import latent_moe
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    if len(devices) > 1:
        raise ValueError("axk1: one chip's share of its group")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        latent_moe.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype)))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
