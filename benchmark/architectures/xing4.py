"""Xing4.0-29B-A4B (XingChen-AGI, `model_type: "xing4_0"`): the
DeepSeek-V3 family's block (latent attention, `first_k_dense_replace`
dense layers, then sigmoid-scored experts chosen by score + a correction
bias, `topk_method` "noaux_tc", plus one shared expert: `axk1.py` writes
those out) around which FOUR residual streams are mixed by
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606).

The mixing. n = hc_mult, C = hidden_size; the stream of a token is
x in R^{n x C}. Entering: x_0 = (e, .., e), e the token's embedding. For
each of the 2 x num_hidden_layers branches F (attention with its ln1,
feed-forward or expert layer with its ln2: the branch keeps its own
RMSNorm), with the branch's own phi in R^{nC x (n + n + n^2)},
b in R^{n + n + n^2}, alpha_pre, alpha_post, alpha_res in R:

    x~      = RMSNorm(vec(x))                      over all nC values, no learned gain, eps rms_norm_eps
    H~_pre  = alpha_pre  . x~ phi[:, 0:n]      + b[0:n]
    H~_post = alpha_post . x~ phi[:, n:2n]     + b[n:2n]
    H~_res  = alpha_res  . mat(x~ phi[:, 2n:]) + mat(b[2n:])
    H_pre   = sigmoid(H~_pre)          H_post = 2 sigmoid(H~_post)
    M^0     = exp(clamp(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M^t     = cols(rows(M^{t-1})),  t = 1 .. hc_sinkhorn_iters,
              rows(M)_ij = M_ij / (sum_j M_ij + hc_eps),  cols(M)_ij = M_ij / (sum_i M_ij + hc_eps)
    H_res   = M^iters
    u       = sum_i H_pre,i . x_i          y = F(u)
    x'_i    = sum_j H_res,ij . x_j + H_post,i . y

Leaving: h = sum_i x_i, then the final norm and the head. The served
leaves hold phi TRANSPOSED (`hc_<branch>_phi [L, n + n + n^2, nC]`).
`num_nextn_predict_layers` is kept in the file and NOT read: the
multi-token prediction module is a draft head, off the path of the next
token's logits.

The configuration file runs ONE chip's share of a stated deployment
(model-configs guide, section 4): `n_routed_experts` in the file counts
the experts HELD HERE (`expert_offset` on), `published.n_routed_experts`
is the router's width; every layer and the whole vocabulary are kept.
What the experts elsewhere would add is left out of program and reference
alike.

The same three parts as `llama.py`: (1, 2, 6) how the PROGRAM builds this
model; (3) the plain reference, from the parameter tree's leaves, sharing
no code with the program (it reuses `axk1.py`'s plain rope, expert and
head pieces: the same equations); (4, 5) the work of a step on THIS chip,
counted from the file's shapes with no JAX: `axk1.py`'s terms plus the
mixing's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.architectures import axk1
from benchmark.architectures.llama import BYTES, rms_norm, weight

BRANCHES_A_LAYER = 2   # attention, feed-forward
HC_CALLS_A_BRANCH = 2  # hc_pre, hc_post
COEF_LANES = 128       # the kernels' packed coefficients a token, float32


def streams(c: Dict[str, Any]) -> int:
    return int(c["hc_mult"])


def coefficients(c: Dict[str, Any]) -> int:
    n = streams(c)
    return 2 * n + n * n


def branches(c: Dict[str, Any]) -> int:
    return BRANCHES_A_LAYER * int(c["num_hidden_layers"])


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models import hyper_connections  # noqa: F401
        from generativeaiexamples_tpu.models.latent_moe import LatentMoeConfig
        from generativeaiexamples_tpu.models.llama import YarnScaling
    except ImportError as e:  # a program from before several streams
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'xing4' (no residual path but x + y): {e}")
    rs = config["rope_scaling"]
    if rs["type"] != "yarn" or config["topk_method"] != "noaux_tc" \
            or config["scoring_func"] != "sigmoid" \
            or int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("xing4: YaRN, sigmoid scores, a correction bias "
                         "(noaux_tc) and one expert group are what is "
                         "written")
    return LatentMoeConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_dense_layers=axk1.dense_layers(config),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        mlp_dim=int(config["intermediate_size"]),
        moe_mlp_dim=int(config["moe_intermediate_size"]),
        n_routed_experts=axk1.router_width(config),
        n_experts_per_tok=int(config["num_experts_per_tok"]),
        n_shared_experts=int(config["n_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=axk1.held(config),
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
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")),
        router_bias=True,
        hc_mult=streams(config),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_res_clamp=(float(config["mhc_h_res_clamp_min"]),
                      float(config["mhc_h_res_clamp_max"])))


# -- 2. seeded parameters on the device -----------------------------------

def _init(config: Dict[str, Any], mcfg, seed: int = 0):
    from generativeaiexamples_tpu.models import latent_moe

    return latent_moe.init_params_on_device(
        mcfg, seed, quantize=config["serving"]["quantize_weights"] == "int8",
        depth_gain=True)  # 40 layers: the file's `assumed.weights`


def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'xing4' is one chip's "
                         "share of its host; it takes one device")
    return _init(config, mcfg, seed), None


# -- 3. the plain reference -----------------------------------------------
# The equations above and DeepSeek-V3's (axk1.py) in float32 `jax.numpy`
# under `highest` precision: the stream [S, n, C] in float32, UN-absorbed
# attention, a Python loop over the held experts, no cache, no kernel, no
# batching, one piece's weights in float32 at a time. It reads only the
# parameter tree's leaves. The same share as the program: the held
# experts (the router still scores all `published.n_routed_experts`).

@functools.partial(jax.jit, static_argnames=("n", "eps", "iters", "hc_eps",
                                             "clamp"))
def _mix_coefficients(x, phi_t, b, alpha, *, n, eps, iters, hc_eps, clamp):
    """x [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    S = x.shape[0]
    flat = x.reshape(S, -1)
    normed = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    raw = normed @ phi_t.astype(jnp.float32).T                  # [S, K]
    h_pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        (alpha[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(S, n, n),
        clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + hc_eps)    # rows
        m = m / (jnp.sum(m, axis=1, keepdims=True) + hc_eps)    # columns
    return h_pre, h_post, m


@jax.jit
def _mix_in(x, h_pre):
    return jnp.einsum("si,sic->sc", h_pre, x)


@jax.jit
def _mix_out(x, y, h_post, h_res):
    return jnp.einsum("sij,sjc->sic", h_res, x) \
        + h_post[:, :, None] * y[:, None, :]


def _attention_out(u, w, *, H, Dn, R, Dv, C, eps, scale, inv_freq, gain):
    """The attention branch's OUTPUT for its input u [S, D] (axk1.py's
    `_attention` adds it to its input; here the mixing does)."""
    S = u.shape[0]
    h = rms_norm(u, weight(w["ln1"]), eps)
    cq = rms_norm(h @ weight(w["w_qa"]), weight(w["q_norm"]), eps)
    q = (cq @ weight(w["w_qb"])).reshape(S, H, Dn + R)
    q = jnp.concatenate(
        [q[..., :Dn], axk1._rope(q[..., Dn:], inv_freq, gain)], -1)
    ckv = h @ weight(w["w_kva"])
    c = rms_norm(ckv[:, :C], weight(w["kv_norm"]), eps)
    k_rope = axk1._rope(ckv[:, None, C:], inv_freq, gain)      # one head
    kv = (c @ weight(w["w_kvb"])).reshape(S, H, Dn + Dv)
    k = jnp.concatenate([kv[..., :Dn],
                         jnp.broadcast_to(k_rope, (S, H, R))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., Dn:])
    return a.reshape(S, H * Dv) @ weight(w["wo"])


@functools.partial(jax.jit, static_argnames=("attn",))
def _attention_branch(u, w, inv_freq, *, attn):
    return _attention_out(u, w, inv_freq=inv_freq, **dict(attn))


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "norm"))
def _route_and_share(h, w, *, top_k, scaling, norm):
    """-> (the shared expert's output, the router's choice [S, top_k] by
    score + bias, its weights [S, top_k] from the scores alone)."""
    s = jax.nn.sigmoid(h @ weight(w["router"]))               # [S, all]
    _, idx = jax.lax.top_k(s + w["router_bias"], top_k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    wts = scaling * (top / jnp.sum(top, -1, keepdims=True) if norm else top)
    y = axk1._swiglu(h, weight(w["w_gate"]), weight(w["w_up"]),
                     weight(w["w_down"]))
    return y, idx, wts


HEAD_COLUMN_PIECES = 8  # the whole vocabulary in float32 is 1.9 GB


@functools.partial(jax.jit, static_argnames=("a", "b"))
def _head_columns(h, out_w, *, a, b):
    return h @ axk1._slice(out_w, cols=slice(a, b))


def reference_forward(config: Dict[str, Any], params, token_ids):
    """-> (logits [S, vocab] float32, the router's choices
    [expert layers, S, top_k])."""
    eps = float(config["rms_norm_eps"])
    n = streams(config)
    attn = axk1._attn_statics(config)
    inv_freq = axk1.yarn_inv_freq(int(config["qk_rope_head_dim"]),
                                  float(config["rope_theta"]),
                                  config["rope_scaling"])
    mix = dict(n=n, eps=eps, iters=int(config["hc_sinkhorn_iters"]),
               hc_eps=float(config["hc_eps"]),
               clamp=(float(config["mhc_h_res_clamp_min"]),
                      float(config["mhc_h_res_clamp_max"])))
    m = int(config["intermediate_size"])
    piece = -(-m // axk1.DENSE_COLUMN_PIECES)

    def dense(h, w):
        y = 0.0
        for a in range(0, m, piece):
            y = y + axk1._dense_columns(h, w, a=a, b=min(a + piece, m))
        return y, None

    def experts(h, w):
        y, idx, wts = _route_and_share(
            h, w, top_k=int(config["num_experts_per_tok"]),
            scaling=float(config["routed_scaling_factor"]),
            norm=bool(config["norm_topk_prob"]))
        for e in range(axk1.held(config)):  # the experts that live here
            y = y + axk1._held_expert(
                h, idx, wts, w["we_gate_up"], w["we_down"], e=e,
                expert=int(config["expert_offset"]) + e)
        return y, idx

    def block(x, w, feed_forward):
        """Both branches of one layer around the streams x [S, n, C]."""
        chosen = None
        for name in ("attn", "ffn"):
            h_pre, h_post, h_res = _mix_coefficients(
                x, w[f"hc_{name}_phi"], w[f"hc_{name}_b"],
                w[f"hc_{name}_alpha"], **mix)
            u = _mix_in(x, h_pre)
            if name == "attn":
                y = _attention_branch(u, w, inv_freq, attn=attn)
            else:
                y, chosen = feed_forward(
                    axk1._normed(u, w["ln2"], eps=eps), w)
            x = _mix_out(x, y, h_post, h_res)
        return x, chosen

    choices = []
    with jax.default_matmul_precision("highest"):
        e = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        x = jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))
        for i in range(axk1.dense_layers(config)):
            x, _ = block(x, jax.tree.map(lambda a: a[i], params["dense"]),
                         dense)
        for i in range(axk1.moe_layers(config)):
            x, idx = block(x, jax.tree.map(lambda a: a[i], params["layers"]),
                           experts)
            choices.append(idx)
        h = axk1._normed(jnp.sum(x, axis=1), params["ln_f"], eps=eps)
        V = int(config["vocab_size"])
        cols = -(-V // HEAD_COLUMN_PIECES)
        logits = jnp.concatenate(
            [_head_columns(h, params["lm_head"], a=a, b=min(a + cols, V))
             for a in range(0, V, cols)], axis=-1)
    return logits, jnp.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step on THIS chip -----------------------------------
# axk1.py's terms (every weight outside the experts read once a program,
# the held experts some token chose, 576 values a cached token and layer)
# plus the mixing's LEAST work: a branch reads a token's streams once and
# writes them once and reads its phi once a program; the coefficients'
# arithmetic is counted, their bytes (a few hundred a token) are not.

def stream_bytes(c: Dict[str, Any]) -> float:
    """One token's streams, once."""
    return float(streams(c) * int(c["hidden_size"]) * BYTES["bfloat16"])


def phi_bytes(c: Dict[str, Any]) -> float:
    """One branch's projection (bf16)."""
    return float(coefficients(c)) * stream_bytes(c)


def mix_params(c: Dict[str, Any]) -> int:
    """The mixing's parameters: phi, b and three gains a branch."""
    k = coefficients(c)
    return branches(c) * (k * streams(c) * int(c["hidden_size"]) + k + 3)


def _mix_flops_a_token(c: Dict[str, Any]) -> float:
    """One branch for one token: the norm's squares, the projection, the
    passes, the weighted sum in and the mix out."""
    n, d = streams(c), int(c["hidden_size"])
    return (2.0 * n * d + 2.0 * coefficients(c) * n * d
            + int(c["hc_sinkhorn_iters"]) * 4.0 * n * n
            + 2.0 * n * d + 2.0 * n * (n + 1) * d)


def _mix(c: Dict[str, Any], tokens: float, programs: float):
    return {"flops": branches(c) * tokens * _mix_flops_a_token(c),
            "bytes": branches(c) * (tokens * 2.0 * stream_bytes(c)
                                    + programs * phi_bytes(c))}


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each, on this chip."""
    work = axk1.decode_step(c, batch, context, chips)
    mix = _mix(c, batch, 1.0)
    return {k: work[k] + mix[k] / chips for k in work}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    work = axk1.prefill(c, prompt_tokens, mean_prompt, programs, chips)
    mix = _mix(c, prompt_tokens, programs)
    return {k: work[k] + mix[k] / chips for k in work}


attention_kernel = axk1.attention_kernel
moe_kernel = axk1.moe_kernel


def hc_kernel(c: Dict[str, Any], calls: float, batch: float,
              chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the two mixing kernels in decode
    steps of `batch` tokens (a branch is one `hc_pre` and one `hc_post`):
    `hc_pre` reads the streams and the branch's phi and writes the
    branch's input and the packed coefficients; `hc_post` reads the
    streams, the branch's output and the coefficients and writes the
    streams."""
    d = int(c["hidden_size"])
    pairs = calls / HC_CALLS_A_BRANCH
    one = d * BYTES["bfloat16"]
    coef = COEF_LANES * BYTES["float32"]
    bytes_ = pairs * (batch * (3.0 * stream_bytes(c) + 2.0 * one
                               + 2.0 * coef) + phi_bytes(c))
    flops = pairs * batch * _mix_flops_a_token(c)
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

    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    if len(devices) > 1:
        raise ValueError("xing4: one chip's share of its host")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(_init, config, mcfg))
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype)))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
