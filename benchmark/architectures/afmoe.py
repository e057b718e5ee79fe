"""Trinity-Large-Preview (Arcee, `model_type: "afmoe"`): GATED ATTENTION
over window and global layers beside sparse experts with a shared one.
`layer_types[l]` "sliding_attention": token t attends s <= t with t - s <
`sliding_window` only and q and k take the rotary embedding;
"full_attention": every s <= t, and NOTHING positional is read. The first
`num_dense_layers` layers' feed-forward is a dense SwiGLU; every later
layer routes a token to `num_experts_per_tok` of the experts (sigmoid
scores, chosen by score + `expert_bias`, weighed by their scores
renormalised under `route_norm` and scaled by `route_scale`) and adds one
shared expert. For layer l, eps `rms_norm_eps`, no bias anywhere:

    x_0 = E[token] * sqrt(hidden_size)            mup_enabled
    h   = RMSNorm(x; n1)
    q, k, v, g = h W_q, h W_k, h W_v, h W_g
    q, k = RMSNorm_hd(q; qn), RMSNorm_hd(k; kn)   a head at a time, one weight for all heads, BEFORE any rotation
    sliding: q, k <- rope(q, k; position)         rotate-half over the whole head
    a   = softmax(q k^T / sqrt(head_dim) + mask) v
    a   = a * sigmoid(g)                          before W_o
    x   = x + RMSNorm(a W_o; n2)
    h2  = RMSNorm(x; n3)
    f   = SwiGLU(h2)  |  shared(h2) + sum_{e in S} w_e expert_e(h2)
          s = sigmoid(h2 W_r); S = the k largest of s + b; w_e = route_scale * s_e / (sum_S s + 1e-20)
    x   = x + RMSNorm(f; n4)
    logits = RMSNorm(x; nf) W_head

DEPARTURES from the published modelling file, each because there is no
network here to hold the reading against (the configuration's `assumed`
lists them one by one): the gate's input (the attention's normed input h)
and place (on the heads' output, before W_o); q/k norm before the
rotation; no rotation at all on full layers; the window's boundary
(4,096 tokens with the token's own); the embedding's multiplier; the
`1e-20` under the weights' sum. The program stores W_q, W_k, W_v, W_g as
ONE leaf `w_qkvg` (columns in that order): this reference cuts it by
columns.

The configuration file runs ONE chip's share of a stated deployment
(model-configs guide, section 4): `num_experts` in the file counts the
experts HELD HERE (`expert_offset` on), `published.num_experts` is the
router's width; the vocabulary is a slice; the depth is the dense layer
once and two whole periods of expert layers. What the experts elsewhere
would add is left out of program and reference alike.

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
import numpy as np

from benchmark.architectures.llama import BYTES, rms_norm, weight

SLIDING, FULL = "sliding_attention", "full_attention"


def layer_kinds(c: Dict[str, Any]):
    """1 a sliding layer, 0 a full one, of the layers the file runs."""
    types = c["layer_types"]
    if len(types) != int(c["num_hidden_layers"]) \
            or set(types) - {SLIDING, FULL}:
        raise ValueError(f"afmoe: {c['num_hidden_layers']} layers, "
                         f"layer_types {types}")
    return tuple(int(t == SLIDING) for t in types)


def router_width(c: Dict[str, Any]) -> int:
    return int(c["published"]["num_experts"])


def held(c: Dict[str, Any]) -> int:
    return int(c["num_experts"])


def dense_layers(c: Dict[str, Any]) -> int:
    return int(c["num_dense_layers"])


def moe_layers(c: Dict[str, Any]) -> int:
    return int(c["num_hidden_layers"]) - dense_layers(c)


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models.gated_window_moe import (
            GatedWindowMoeConfig)
    except ImportError as e:  # a program from before this block
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'afmoe' (no gated attention over window and "
                         f"global rows, no norm on both sides of a branch "
                         f"beside a share of the experts): {e}")
    c = config
    if c["tie_word_embeddings"] or c["rope_scaling"] is not None \
            or c["score_func"] != "sigmoid" or not c["route_norm"] \
            or not c["mup_enabled"] or c["hidden_act"] != "silu" \
            or int(c["num_shared_experts"]) != 1 \
            or (int(c["n_group"]), int(c["topk_group"])) != (1, 1):
        raise ValueError("afmoe: an untied head, no rope scaling, sigmoid "
                         "scores renormalised, one group, one shared expert, "
                         "SiLU and the embedding's multiplier are what is "
                         "written")
    return GatedWindowMoeConfig(
        vocab_size=int(c["vocab_size"]), dim=int(c["hidden_size"]),
        n_layers=int(c["num_hidden_layers"]),
        n_dense_layers=dense_layers(c),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), window=int(c["sliding_window"]),
        window_layout=layer_kinds(c), mlp_dim=int(c["intermediate_size"]),
        moe_mlp_dim=int(c["moe_intermediate_size"]),
        n_routed_experts=router_width(c),
        n_experts_per_tok=int(c["num_experts_per_tok"]),
        routed_scaling_factor=float(c["route_scale"]),
        experts_held=held(c), expert_offset=int(c.get("expert_offset", 0)),
        embed_scale=float(c["hidden_size"]) ** 0.5,
        init_depth=int(c["published"].get("num_hidden_layers",
                                          c["num_hidden_layers"])),
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]),
        dtype=jnp.dtype(c["serving"].get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device -----------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    from generativeaiexamples_tpu.models import gated_window_moe

    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'afmoe' is one chip's "
                         "share of its group; it takes one device")
    quantize = config["serving"]["quantize_weights"] == "int8"
    return gated_window_moe.init_params_on_device(mcfg, seed,
                                                  quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# The equations above in float32 `jax.numpy` under `highest` precision,
# layer by layer from the parameter tree's leaves. The reference runs
# BESIDE the served model: weights and pools are 14.4 GB of the chip's
# 16.9 and the runtime keeps the largest step program's 2.0 GB of
# temporaries reserved, so 0.4 GB is what is left (my chip run, PR 52: a
# copy of one layer's held experts, 0.6 GB, did not fit). So the stream,
# q, k, v, the gate's input and the heads' output live ON THE HOST as
# numpy arrays and the device sees blocks: the token-wise parts
# TOKEN_BLOCK rows at a time with ONE projection's, one FF_BLOCK columns'
# or ONE expert's weights in float32 at a time, a dense masked softmax
# over the whole sequence ROW_BLOCK query rows and one KV head at a time,
# the head in blocks of rows. No cache, no pages, no kernel, and no code
# shared with the program.

ROW_BLOCK = 512      # query rows of the attention and of the head at once
TOKEN_BLOCK = 1024   # rows of a projection or a feed-forward at once
FF_BLOCK = 3072      # columns of a SwiGLU's hidden width at once


def _cols(w, a, b):
    """Columns a..b of a leaf (an int8 one stays codes and scales)."""
    if hasattr(w, "q"):
        return type(w)(w.q[..., a:b], w.s[..., a:b])
    return w[..., a:b]


def _rows(w, a, b):
    """Rows a..b (the contraction's) of a leaf."""
    if hasattr(w, "q"):
        return type(w)(w.q[..., a:b, :], w.s)
    return w[..., a:b, :]


def _piece(w, i, j):
    """Float32 expert (i, j) of a stack [layers, experts, in, out] at
    traced indices."""
    if hasattr(w, "q"):
        return w.q[i, j].astype(jnp.float32) \
            * w.s[i, j].astype(jnp.float32)[None, :]
    return w[i, j].astype(jnp.float32)


def _rope(x, pos0, theta):
    """x [R, n, Hd] at positions pos0..: rotate the two halves of each
    head (the HF `rotate_half` convention): x * cos + rotate_half(x) * sin
    with rotate_half(x) = [-x2 ; x1], written as a product with the
    constant matrix that swaps and signs the halves (the chip's compiler
    stops on the two halves joined by a concatenation inside this jit: my
    CPU compile for a described v5e, PR 52)."""
    Hd = x.shape[-1]
    half = Hd // 2
    swap = np.zeros((Hd, Hd), np.float32)
    swap[np.arange(half) + half, np.arange(half)] = -1.0   # out[j] = -x2[j]
    swap[np.arange(half), np.arange(half) + half] = 1.0    # out[j+h] = x1[j]
    inv = theta ** (-(jnp.arange(Hd) % half).astype(jnp.float32) * 2 / Hd)
    ang = (pos0 + jnp.arange(x.shape[0], dtype=jnp.float32))[:, None] \
        * inv[None, :]
    return x * jnp.cos(ang)[:, None, :] \
        + (x @ jnp.asarray(swap)) * jnp.sin(ang)[:, None, :]


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, *, eps):
    return rms_norm(x, weight(w), eps)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "theta",
                                             "eps", "rotate", "norm"))
def _heads(h, w, norm_w, pos0, *, heads, head_dim, theta, eps, rotate,
           norm):
    """One projection of rows pos0.. as heads [R, heads, head_dim]: normed
    a head at a time, THEN rotated, where asked."""
    y = (h @ weight(w)).reshape(h.shape[0], heads, head_dim)
    if norm:
        y = rms_norm(y, weight(norm_w), eps)
    return _rope(y, pos0, theta) if rotate else y


@jax.jit
def _product(h, w):
    return h @ weight(w)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, row0, *, window):
    """Query rows row0.. of the query heads of ONE KV head: q [R, G, Hd],
    k, v [S, Hd]; row t sees s <= t, and under a window only t - s <
    window."""
    t = row0 + jnp.arange(q.shape[0])[:, None]
    s = jnp.arange(k.shape[0])[None, :]
    mask = s <= t
    if window is not None:
        mask &= t - s < window
    sc = jnp.einsum("qgd,kd->gqk", q, k) * q.shape[-1] ** -0.5
    sc = jnp.where(mask[None], sc, -jnp.inf)
    return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(sc, -1), v)


def _attention(q, k, v, window):
    """[S, H * Hd] on the host from q [S, H, Hd], k, v [S, KH, Hd] on the
    host: a KV head's group of query heads and ROW_BLOCK rows at a time."""
    S, H, Hd = q.shape
    KH = k.shape[1]
    q = q.reshape(S, KH, H // KH, Hd)
    out = np.empty((S, KH, H // KH, Hd), np.float32)
    for g in range(KH):
        kg, vg = jnp.asarray(k[:, g]), jnp.asarray(v[:, g])
        for r in range(0, S, ROW_BLOCK):
            out[r:r + ROW_BLOCK, g] = np.asarray(_attend(
                jnp.asarray(q[r:r + ROW_BLOCK, g]), kg, vg, r,
                window=window))
    return out.reshape(S, H * Hd)


@functools.partial(jax.jit, static_argnames=("eps", "gate", "post"))
def _attn_branch(x, a, g, wo, post_w, *, eps, gate, post):
    """x + RMSNorm((a * sigmoid(g)) W_o; n2)."""
    if gate:
        a = a * jax.nn.sigmoid(g)
    y = a @ weight(wo)
    return x + (rms_norm(y, weight(post_w), eps) if post else y)


@jax.jit
def _swiglu_cols(h, w_gate, w_up, w_down):
    """Some columns of a SwiGLU's hidden width: their part of the sum."""
    return (jax.nn.silu(h @ weight(w_gate)) * (h @ weight(w_up))) \
        @ weight(w_down)


def _swiglu(h, w):
    width = w["w_gate"].shape[-1]
    return sum(_swiglu_cols(h, _cols(w["w_gate"], a, b),
                            _cols(w["w_up"], a, b), _rows(w["w_down"], a, b))
               for a, b in _blocks(width, FF_BLOCK))


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "bias"))
def _route(h, router, router_bias, *, top_k, scale, bias):
    """sigmoid scores over ALL experts; the top_k largest of score + bias
    (a tie to the lower index); weights: their SCORES, renormalised and
    scaled."""
    s = jax.nn.sigmoid(h @ weight(router))
    _, idx = jax.lax.top_k(s + router_bias.astype(jnp.float32) if bias
                           else s, top_k)
    top = jnp.take_along_axis(s, idx, -1)
    return idx, scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)


@jax.jit
def _expert(h, idx, weights, gate_up, down, i, j, e):
    """Held expert `j` (expert `e` of the router's) of expert layer `i`
    ([layers, experts, ...] stacks, read where they lie)."""
    g = jnp.sum(jnp.where(idx == e, weights, 0.0), -1)          # [R]
    gu = h @ _piece(gate_up, i, j)
    m = gu.shape[-1] // 2
    return g[:, None] * ((jax.nn.silu(gu[:, :m]) * gu[:, m:])
                         @ _piece(down, i, j))


@functools.partial(jax.jit, static_argnames=("eps", "post"))
def _ffn_branch(x, f, post_w, *, eps, post):
    return x + (rms_norm(f, weight(post_w), eps) if post else f)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_rows(x, ln_f, head, *, eps):
    return rms_norm(x, weight(ln_f), eps) @ weight(head)


def _blocks(n, size):
    return [(a, min(a + size, n)) for a in range(0, n, size)]


def reference_forward(config: Dict[str, Any], params, token_ids, *,
                      gate: bool = True, qk_norm: bool = True,
                      post_norms: bool = True, rotate: bool = True,
                      bias: bool = True, windowed: bool = True,
                      experts=None):
    """-> (logits [S, vocab] float32 ON THE HOST, the router's choices
    [expert layers, S, top_k] on the host). The negative controls, each a
    model that is NOT this one: `gate=False` (no sigmoid product),
    `qk_norm=False`, `post_norms=False` (a norm before each branch only),
    `rotate=False` (no sliding layer is rotated), `bias=False` (the
    selection by the scores alone), `windowed=False` (every layer sees
    everything). `experts` = (offset, count): another share than the
    file's."""
    c = config
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    H, KH, Hd = (int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]), int(c["head_dim"]))
    qa, ka, va = H * Hd, (H + KH) * Hd, (H + 2 * KH) * Hd
    kinds = layer_kinds(c)
    offset, n_held = experts or (int(c.get("expert_offset", 0)), held(c))
    top_k, scale = int(c["num_experts_per_tok"]), float(c["route_scale"])
    Ld = dense_layers(c)
    choices = []
    with jax.default_matmul_precision("highest"):
        x = np.array(params["tok_emb"][jnp.asarray(token_ids)].astype(
            jnp.float32) * float(c["hidden_size"]) ** 0.5)
        S = x.shape[0]
        rows = _blocks(S, TOKEN_BLOCK)
        for l in range(int(c["num_hidden_layers"])):
            stack = params["dense"] if l < Ld else params["layers"]
            i = l if l < Ld else l - Ld
            w = {k: jax.tree.map(lambda t: t[i], v) for k, v in stack.items()
                 if k not in ("we_gate_up", "we_down")}
            turn = bool(kinds[l]) and rotate
            q = np.empty((S, H, Hd), np.float32)
            k, v = (np.empty((S, KH, Hd), np.float32) for _ in range(2))
            g = np.empty((S, H * Hd), np.float32)
            cut = {name: _cols(w["w_qkvg"], a, b) for name, a, b in (
                ("q", 0, qa), ("k", qa, ka), ("v", ka, va),
                ("g", va, None))}
            for a, b in rows:
                h = _normed(jnp.asarray(x[a:b]), w["ln1"], eps=eps)
                kw = dict(head_dim=Hd, theta=theta, eps=eps)
                q[a:b] = np.asarray(_heads(
                    h, cut["q"], w["q_norm"], float(a), heads=H,
                    rotate=turn, norm=qk_norm, **kw))
                k[a:b] = np.asarray(_heads(
                    h, cut["k"], w["k_norm"], float(a), heads=KH,
                    rotate=turn, norm=qk_norm, **kw))
                v[a:b] = np.asarray(_heads(
                    h, cut["v"], w["k_norm"], float(a), heads=KH,
                    rotate=False, norm=False, **kw))
                g[a:b] = np.asarray(_product(h, cut["g"]))
            window = int(c["sliding_window"]) \
                if kinds[l] and windowed else None
            att = _attention(q, k, v, window)
            del q, k, v
            idx_rows = []
            for a, b in rows:
                xb = _attn_branch(
                    jnp.asarray(x[a:b]), jnp.asarray(att[a:b]),
                    jnp.asarray(g[a:b]), w["wo"], w["ln1_post"], eps=eps,
                    gate=gate, post=post_norms)
                h2 = _normed(xb, w["ln2"], eps=eps)
                f = _swiglu(h2, w)
                if l >= Ld:
                    idx, weights = _route(h2, w["router"], w["router_bias"],
                                          top_k=top_k, scale=scale,
                                          bias=bias)
                    for j in range(n_held):
                        f = f + _expert(h2, idx, weights,
                                        stack["we_gate_up"],
                                        stack["we_down"], i, j, offset + j)
                    idx_rows.append(np.asarray(idx))
                x[a:b] = np.asarray(_ffn_branch(xb, f, w["ln2_post"],
                                                eps=eps, post=post_norms))
            if l >= Ld:
                choices.append(np.concatenate(idx_rows))
        logits = np.concatenate([np.asarray(_head_rows(
            jnp.asarray(x[a:b]), params["ln_f"], params["lm_head"], eps=eps))
            for a, b in _blocks(S, ROW_BLOCK)], axis=0)
    return logits, np.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids):
    """[S] token ids -> [S, vocab] float32 logits (a host array)."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step on this chip -----------------------------------
# The algorithm's work, whatever form the program gives it: every weight
# outside the routed experts is read once a program; of the held experts,
# those that some token chose (EXPECTED under uniform routing in
# `decode_step`, `prefill` and `moe_kernel`; the ones the traced blocks
# DID hit in `moe_kernel_hit`); a full layer reads the K and V of every
# cached token of a live sequence, a sliding layer of the last
# min(context, window).

def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def attention_params(c: Dict[str, Any]) -> int:
    """q, g, o and k, v of one layer."""
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    h, kh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return d * hd * 2 * (h + kh) + h * hd * d


def dense_ffn_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert, or the shared one."""
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def router_bytes(c: Dict[str, Any]) -> float:
    """The router, bf16, and its float32 bias, every expert layer."""
    return float(moe_layers(c) * router_width(c)
                 * (BYTES["bfloat16"] * int(c["hidden_size"])
                    + BYTES["float32"]))


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def always_read_params(c: Dict[str, Any]) -> int:
    """int8 weights every program reads whatever the routing."""
    return int(c["num_hidden_layers"]) * attention_params(c) \
        + dense_layers(c) * dense_ffn_params(c) \
        + moe_layers(c) * expert_params(c) + head_params(c)


def local_share(c: Dict[str, Any]) -> float:
    """The share of a token's pairs that fall on held experts, expected."""
    return held(c) / router_width(c)


def experts_hit(c: Dict[str, Any], tokens: float) -> float:
    """Held experts some token of `tokens` chose, expected, uniform."""
    p = int(c["num_experts_per_tok"]) / router_width(c)
    return held(c) * (1.0 - (1.0 - p) ** max(tokens, 0.0))


def rows_by_kind(c: Dict[str, Any]):
    """(full layers, sliding layers) of the layers the file runs."""
    n_window = sum(layer_kinds(c))
    return int(c["num_hidden_layers"]) - n_window, n_window


def kv_bytes_per_token_layer(c: Dict[str, Any]) -> float:
    """K and V of one token in one layer: int8 codes and a float32 scale
    a (kv head, token), K and V each (2,112 B at 8 heads of 128)."""
    per = int(c["head_dim"]) * BYTES[c["serving"]["kv_dtype"]]
    if c["serving"]["kv_dtype"] == "int8":
        per += 4
    return float(2 * int(c["num_key_value_heads"]) * per)


def cached_rows(c: Dict[str, Any], context: float) -> float:
    """Cached tokens ONE sequence's attention reads in one step over all
    layers: `context` a full layer, min(context, window) a sliding one."""
    n_global, n_window = rows_by_kind(c)
    return n_global * context + n_window * min(
        context, float(c["sliding_window"]))


def _attention_work(c: Dict[str, Any], rows: float, queries: float):
    """`rows` cached tokens read in all by `queries` (call, sequence)
    pairs: K and V in, q in and o back, 4 x heads x head_dim operations a
    token."""
    h, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    return {"flops": rows * 4.0 * h * hd,
            "bytes": rows * kv_bytes_per_token_layer(c)
            + queries * 2 * h * hd * BYTES["bfloat16"]}


def _pages_work(c, pages, calls, batch, chips):
    tokens = pages * int(c["serving"]["engine"]["page_size"])
    work = _attention_work(c, tokens, calls * batch)
    return {k: v / chips for k, v in work.items()}


def window_attention_pages(c: Dict[str, Any], pages: float, calls: float,
                           batch: float, chips: int = 1) -> Dict[str, float]:
    """The work of the window rows' kernel calls that WALKED `pages` pages
    in all (`calls` calls of `batch` sequences; a page is page_size tokens
    of ONE layer): what the engine's `window_cache` events count, turned
    into bytes and operations here."""
    return _pages_work(c, pages, calls, batch, chips)


def global_attention_pages(c: Dict[str, Any], pages: float, calls: float,
                           batch: float, chips: int = 1) -> Dict[str, float]:
    """The same for the GLOBAL rows' kernel calls (`global_pages`,
    `global_calls` of the `window_cache` events)."""
    return _pages_work(c, pages, calls, batch, chips)


def attention_kernel(c: Dict[str, Any], calls: float, batch: float,
                     context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of either paged kernel (a layer a call,
    in the file's ratio of kinds) for `batch` sequences of `context`
    cached tokens."""
    steps = calls / int(c["num_hidden_layers"])
    work = _attention_work(c, steps * batch * cached_rows(c, context),
                           calls * batch)
    return {k: v / chips for k, v in work.items()}


MOE_KERNEL_CALLS_PER_LAYER = 2  # gate-and-up, then down


def _moe_kernel_work(c, calls, batch, hit, chips):
    d, me = int(c["hidden_size"]), int(c["moe_intermediate_size"])
    layer_steps = calls / MOE_KERNEL_CALLS_PER_LAYER
    pairs = batch * int(c["num_experts_per_tok"]) * local_share(c)
    flops = layer_steps * 2.0 * pairs * expert_params(c)
    bytes_ = layer_steps * (
        hit * expert_params(c) * _weight_bytes(c)
        + pairs * (d + 2 * me + me + d) * BYTES["bfloat16"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def moe_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the grouped int8 matmul in decode
    steps of `batch` tokens (two calls an expert layer): the weights of
    the held experts that are hit, EXPECTED under uniform routing, and the
    pairs' rows in and out."""
    return _moe_kernel_work(c, calls, batch, experts_hit(c, batch), chips)


def moe_kernel_hit(c: Dict[str, Any], calls: float, hit_share: float,
                   batch: float, chips: int = 1) -> Dict[str, float]:
    """The same calls with the weights of the held experts the traced
    blocks DID hit: `hit_share` of them a (layer, step), from the engine's
    `moe_load` events (`hit=<n> of=<m>`)."""
    return _moe_kernel_work(c, calls, batch, hit_share * held(c), chips)


def _routed_flops(c: Dict[str, Any], tokens: float) -> float:
    return 2.0 * tokens * int(c["num_experts_per_tok"]) * local_share(c) \
        * expert_params(c) * moe_layers(c)


def _expert_bytes(c: Dict[str, Any], tokens: float) -> float:
    return float(moe_layers(c) * experts_hit(c, tokens) * expert_params(c)
                 * _weight_bytes(c))


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each: the layer's parts, summed; a sliding layer reads
    min(context + 1, window) tokens."""
    attn = _attention_work(c, batch * cached_rows(c, context + 1),
                           batch * int(c["num_hidden_layers"]))
    flops = 2.0 * batch * always_read_params(c) + _routed_flops(c, batch) \
        + attn["flops"]
    bytes_ = float(always_read_params(c) * _weight_bytes(c)) \
        + router_bytes(c) + _expert_bytes(c, batch) + attn["bytes"]
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions: a row attends the
    keys before it (half the prompt, on average), a sliding layer's no
    more than the window."""
    layers = int(c["num_hidden_layers"])
    body = always_read_params(c) - head_params(c)
    flops = 2.0 * prompt_tokens * body + _routed_flops(c, prompt_tokens)
    flops += _attention_work(
        c, prompt_tokens * cached_rows(c, mean_prompt / 2.0), 0)["flops"]
    sequences = prompt_tokens / max(mean_prompt, 1.0)
    flops += 2.0 * sequences * head_params(c)
    per_program = prompt_tokens / max(programs, 1.0)
    bytes_ = programs * (float(always_read_params(c) * _weight_bytes(c))
                         + router_bytes(c) + _expert_bytes(c, per_program))
    bytes_ += prompt_tokens * layers * kv_bytes_per_token_layer(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """A paged-attention kernel runs once a layer a step
    (`paged_attention_int8` a full layer, `paged_attention_int8_window` a
    sliding layer: `paged_attention` matches both)."""
    return int(config["num_hidden_layers"])


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and both groups of the pool
    as `ShapeDtypeStruct`s on ONE described device; mesh is None."""
    from jax.sharding import SingleDeviceSharding

    from generativeaiexamples_tpu.models import gated_window_moe
    from generativeaiexamples_tpu.serving.kv_cache import (
        WindowPool, window_pool_pages)

    if len(devices) > 1:
        raise ValueError("afmoe: one chip's share of its group")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        gated_window_moe.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: WindowPool.zeros(
        mcfg, config["serving"]["n_pages"],
        window_pool_pages(mcfg.window, ecfg), ecfg.page_size))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
