"""granite-4.0-h-small (ibm-granite, `model_type: "granitemoehybrid"`): the
`granitemoehybrid` / `bamba` family's block. `layer_types` names each
layer's mixer: "mamba" is a Mamba-2 state-space mixer (a depthwise causal
convolution of `mamba_d_conv` taps, then per head the recurrence
S_t = a_t S_(t-1) + D_t x_t (x) B_t, y_t = S_t C_t + D x_t, a gate and a
norm over all of d_inner), "attention" is grouped-query attention with no
positional term and softmax scale `attention_multiplier`. EVERY layer's
feed-forward is `num_local_experts` routed experts (the
`num_experts_per_tok` largest router logits, gates = softmax over those)
plus a shared MLP. Three multipliers: `embedding_multiplier`,
`residual_multiplier` on every branch, 1 / `logits_scaling` on the tied
head.

The configuration file runs one period of the layer pattern with every
expert and the whole vocabulary on one chip (model-configs guide,
section 4): only `num_hidden_layers` is cut.

The same three parts as `llama.py`, sharing nothing between them: (1, 2,
6) how the PROGRAM builds this model; (3) the plain reference, from the
parameter tree's leaves; (4, 5) the work of a step, counted from the
file's shapes with no JAX. `weight` and `rms_norm` come from `llama.py`,
as benchmark/README.md allows.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.architectures.llama import BYTES, rms_norm, weight

MAMBA, ATTENTION = "mamba", "attention"


def layer_types(c: Dict[str, Any]):
    kinds = list(c["layer_types"])
    if len(kinds) != int(c["num_hidden_layers"]):
        raise ValueError("granitemoehybrid: layer_types names "
                         f"{len(kinds)} layers, num_hidden_layers "
                         f"{c['num_hidden_layers']}")
    return kinds


def ssm_layers(c: Dict[str, Any]) -> int:
    return layer_types(c).count(MAMBA)


def attention_layers(c: Dict[str, Any]) -> int:
    return layer_types(c).count(ATTENTION)


def d_inner(c: Dict[str, Any]) -> int:
    return int(c["mamba_n_heads"]) * int(c["mamba_d_head"])


def conv_width(c: Dict[str, Any]) -> int:
    return d_inner(c) + 2 * int(c["mamba_n_groups"]) * int(c["mamba_d_state"])


def attn_head_dim(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) // int(c["num_attention_heads"])


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models.hybrid_ssm import HybridSsmConfig
    except ImportError as e:  # a program from before state-space layers
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'granitemoehybrid' (no state-space layers, no "
                         f"recurrent state beside the cache): {e}")
    if int(config["mamba_n_groups"]) != 1 or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] or config["attention_bias"] \
            or config["position_embedding_type"] != "nope" \
            or d_inner(config) != int(config["mamba_expand"]) \
            * int(config["hidden_size"]):
        raise ValueError("granitemoehybrid: one group, a bias on the "
                         "convolution alone, no positional term and "
                         "d_inner = expand x hidden are what is written")
    return HybridSsmConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        layer_types=tuple(layer_types(config)),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=attn_head_dim(config),
        ssm_heads=int(config["mamba_n_heads"]),
        ssm_head_dim=int(config["mamba_d_head"]),
        ssm_state=int(config["mamba_d_state"]),
        ssm_conv=int(config["mamba_d_conv"]),
        ssm_chunk=int(config["serving"].get("ssm_chunk", 128)),
        n_experts=int(config["num_local_experts"]),
        n_experts_per_tok=int(config["num_experts_per_tok"]),
        moe_mlp_dim=int(config["intermediate_size"]),
        shared_mlp_dim=int(config["shared_intermediate_size"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device -----------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    from generativeaiexamples_tpu.models import hybrid_ssm

    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'granitemoehybrid' has no "
                         "sharded form; it takes one device")
    quantize = config["serving"]["quantize_weights"] == "int8"
    return hybrid_ssm.init_params_on_device(mcfg, seed,
                                            quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# The equations of ISSUE 35 (the family's, with this source's keys) in
# float32 `jax.numpy` under `highest` precision: the recurrence as the
# plain sequential loop over tokens (no chunks, no cache, no kernel), full
# causal attention, a loop over the experts with ONE expert's weights in
# float32 at a time. It reads only the parameter tree's leaves (three
# stacks in layer order: `ssm`, `attn`, `ffn`) and shares no code with the
# program.

def _piece(w, index):
    """A float32 slice of a stacked leaf (codes times per-column scales,
    or a plain array) at a traced index of its leading axis."""
    if hasattr(w, "q"):
        return w.q[index].astype(jnp.float32) \
            * w.s[index].astype(jnp.float32)[None, :]
    return w[index].astype(jnp.float32)


def _glu(h, w_in, w_out):
    gu = h @ w_in
    m = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :m]) * gu[:, m:]) @ w_out


@functools.partial(jax.jit, static_argnames=("shape",))
def _ssm_mixer(x, w, *, shape):
    """One state-space layer's branch for a sequence x [S, D] ->
    (branch output [S, D], the state after the last token [H, P, N])."""
    H, P, N, eps = shape
    S, d = x.shape[0], H * P
    h = rms_norm(x, weight(w["ln1"]), eps)
    zxd = h @ weight(w["w_in"])
    z, xbc, dt = zxd[:, :d], zxd[:, d:2 * d + 2 * N], zxd[:, 2 * d + 2 * N:]
    taps = weight(w["conv_w"])                       # [K, W], oldest first
    K = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[j:j + S] * taps[j] for j in range(K)) \
        + weight(w["conv_b"])
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d].reshape(S, H, P)
    Bm, Cm = xbc[:, d:d + N], xbc[:, d + N:]
    step = jax.nn.softplus(dt + w["dt_bias"])        # [S, H]
    A = -jnp.exp(w["A_log"])
    D = w["D"]

    def token(state, t):
        x_t, b_t, c_t, s_t = t
        state = jnp.exp(s_t * A)[:, None, None] * state \
            + (s_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y = jnp.sum(state * c_t[None, None, :], axis=-1) + D[:, None] * x_t
        return state, y

    state, y = jax.lax.scan(token, jnp.zeros((H, P, N)), (xs, Bm, Cm, step))
    y = rms_norm(y.reshape(S, d) * jax.nn.silu(z), weight(w["norm"]), eps)
    return y @ weight(w["w_out"]), state


@functools.partial(jax.jit, static_argnames=("shape",))
def _attention_mixer(x, w, *, shape):
    H, KH, Hd, scale, eps = shape
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = (h @ weight(w["wq"])).reshape(S, H, Hd)
    k = jnp.repeat((h @ weight(w["wk"])).reshape(S, KH, Hd), H // KH, axis=1)
    v = jnp.repeat((h @ weight(w["wv"])).reshape(S, KH, Hd), H // KH, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale      # no positional term
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return a.reshape(S, H * Hd) @ weight(w["wo"])


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _route_and_share(x, w, *, top_k, eps):
    """-> (the normed stream, the shared MLP's output, the router's
    choice [S, top_k], its gates [S, top_k])."""
    h = rms_norm(x, weight(w["ln2"]), eps)
    top, idx = jax.lax.top_k(h @ weight(w["router"]), top_k)
    return (h, _glu(h, weight(w["ws_in"]), weight(w["ws_out"])), idx,
            jax.nn.softmax(top, axis=-1))


@jax.jit
def _expert(h, idx, gates, gate_up, down, e):
    """Expert `e` of one layer ([E, ...] stacks), gated."""
    g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)          # [S]
    return g[:, None] * _glu(h, _piece(gate_up, e), _piece(down, e))


HEAD_COLUMN_PIECES = 4  # a float32 copy of a quarter of the head at a time


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "a", "b"))
def _head_columns(x, ln_f, head, *, eps, scaling, a, b):
    """Vocabulary rows a..b of the logits. `head` is the int8 tied head
    [D, V], or the embedding [V, D] itself."""
    if hasattr(head, "q"):
        w = head.q[:, a:b].astype(jnp.float32) \
            * head.s[a:b].astype(jnp.float32)[None, :]
    else:
        w = head[a:b].astype(jnp.float32).T
    return rms_norm(x, weight(ln_f), eps) @ w / scaling


def reference_forward(config: Dict[str, Any], params, token_ids):
    """-> (logits [S, vocab] float32, the state-space layers' states
    after the last token [Ls, H, P, N], the router's choices
    [layers, S, top_k])."""
    eps = float(config["rms_norm_eps"])
    mult = float(config["residual_multiplier"])
    ssm_shape = (int(config["mamba_n_heads"]), int(config["mamba_d_head"]),
                 int(config["mamba_d_state"]), eps)
    attn_shape = (int(config["num_attention_heads"]),
                  int(config["num_key_value_heads"]), attn_head_dim(config),
                  float(config["attention_multiplier"]), eps)
    seen = {MAMBA: 0, ATTENTION: 0}
    states, choices = [], []

    def layer(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32) \
            * float(config["embedding_multiplier"])
        for l, kind in enumerate(layer_types(config)):
            i = seen[kind]
            seen[kind] += 1
            if kind == MAMBA:
                y, state = _ssm_mixer(x, layer(params["ssm"], i),
                                      shape=ssm_shape)
                states.append(state)
            else:
                y = _attention_mixer(x, layer(params["attn"], i),
                                     shape=attn_shape)
            x = x + mult * y
            ffn = params["ffn"]
            h, y, idx, gates = _route_and_share(
                x, layer({k: ffn[k] for k in ("ln2", "router", "ws_in",
                                              "ws_out")}, l),
                top_k=int(config["num_experts_per_tok"]), eps=eps)
            gate_up = layer(ffn["we_gate_up"], l)
            down = layer(ffn["we_down"], l)
            for e in range(int(config["num_local_experts"])):
                y = y + _expert(h, idx, gates, gate_up, down, e)
            x = x + mult * y
            choices.append(idx)
        head = params.get("lm_head", params["tok_emb"])
        V = int(config["vocab_size"])
        piece = -(-V // HEAD_COLUMN_PIECES)
        logits = jnp.concatenate([_head_columns(
            x, params["ln_f"], head, eps=eps,
            scaling=float(config["logits_scaling"]), a=a,
            b=min(a + piece, V)) for a in range(0, V, piece)], axis=1)
    return logits, jnp.stack(states), jnp.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step ------------------------------------------------
# The algorithm's work: every weight outside the experts is read once a
# program; of the experts, those that some token chose (EXPECTED number
# under uniform routing); a state-space layer reads and writes each live
# sequence's float32 state once; the one attention layer reads its K and V.

def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def ssm_params(c: Dict[str, Any]) -> int:
    d = int(c["hidden_size"])
    in_width = d_inner(c) + conv_width(c) + int(c["mamba_n_heads"])
    return d * in_width + d_inner(c) * d


def attention_params(c: Dict[str, Any]) -> int:
    d, hd = int(c["hidden_size"]), attn_head_dim(c)
    h, kh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return d * hd * (h + 2 * kh) + h * hd * d


def shared_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["shared_intermediate_size"])


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def always_read_params(c: Dict[str, Any]) -> int:
    """Weights every program reads whatever the routing (int8)."""
    return (ssm_layers(c) * ssm_params(c)
            + attention_layers(c) * attention_params(c)
            + int(c["num_hidden_layers"]) * shared_params(c)
            + head_params(c))


def small_bytes(c: Dict[str, Any]) -> float:
    """The router and the convolution's taps, bf16, every layer."""
    return float(BYTES["bfloat16"] * (
        int(c["num_hidden_layers"]) * int(c["hidden_size"])
        * int(c["num_local_experts"])
        + ssm_layers(c) * (int(c["mamba_d_conv"]) + 1) * conv_width(c)))


def experts_hit(c: Dict[str, Any], tokens: float) -> float:
    """Experts some token of `tokens` chose, expected, uniform routing."""
    e = int(c["num_local_experts"])
    p = int(c["num_experts_per_tok"]) / e
    return e * (1.0 - (1.0 - p) ** max(tokens, 0.0))


def state_bytes_per_sequence(c: Dict[str, Any]) -> float:
    """One state-space layer's float32 state."""
    return 4.0 * d_inner(c) * int(c["mamba_d_state"])


def tail_bytes_per_sequence(c: Dict[str, Any]) -> float:
    return float((int(c["mamba_d_conv"]) - 1) * conv_width(c)
                 * BYTES["bfloat16"])


def kv_bytes_per_token(c: Dict[str, Any]) -> float:
    per = attn_head_dim(c) * BYTES[c["serving"]["kv_dtype"]]
    if c["serving"]["kv_dtype"] == "int8":
        per += 4  # one float32 scale a (kv head, token), K and V each
    return float(attention_layers(c) * 2 * int(c["num_key_value_heads"])
                 * per)


def _routed_flops(c: Dict[str, Any], tokens: float) -> float:
    return 2.0 * tokens * int(c["num_experts_per_tok"]) * expert_params(c) \
        * int(c["num_hidden_layers"])


def _expert_bytes(c: Dict[str, Any], tokens: float) -> float:
    return float(int(c["num_hidden_layers"]) * experts_hit(c, tokens)
                 * expert_params(c) * _weight_bytes(c))


def _state_flops_per_token(c: Dict[str, Any]) -> float:
    """a * S, + D_t x (x) B, S . C: about 6 operations a state element."""
    return 6.0 * d_inner(c) * int(c["mamba_d_state"])


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each."""
    h, hd = int(c["num_attention_heads"]), attn_head_dim(c)
    flops = 2.0 * batch * always_read_params(c) + _routed_flops(c, batch)
    flops += batch * ssm_layers(c) * _state_flops_per_token(c)
    flops += 4.0 * batch * context * h * hd * attention_layers(c)
    bytes_ = float(always_read_params(c) * _weight_bytes(c))
    bytes_ += small_bytes(c) + _expert_bytes(c, batch)
    bytes_ += batch * ssm_layers(c) * 2.0 * (
        state_bytes_per_sequence(c) + tail_bytes_per_sequence(c))
    bytes_ += batch * (context + 1) * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions. The scan's work is
    counted as the recurrence's (6 operations a state element and token),
    not the chunked form's extra matmuls."""
    h, hd = int(c["num_attention_heads"]), attn_head_dim(c)
    body = always_read_params(c) - head_params(c)
    flops = 2.0 * prompt_tokens * body + _routed_flops(c, prompt_tokens)
    flops += prompt_tokens * ssm_layers(c) * _state_flops_per_token(c)
    flops += 2.0 * prompt_tokens * mean_prompt * h * hd * attention_layers(c)
    sequences = prompt_tokens / max(mean_prompt, 1.0)
    flops += 2.0 * sequences * head_params(c)
    per_program = prompt_tokens / max(programs, 1.0)
    bytes_ = programs * (float(always_read_params(c) * _weight_bytes(c))
                         + small_bytes(c) + _expert_bytes(c, per_program))
    bytes_ += prompt_tokens * kv_bytes_per_token(c)
    bytes_ += sequences * ssm_layers(c) * (state_bytes_per_sequence(c)
                                           + tail_bytes_per_sequence(c))
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def attention_kernel(c: Dict[str, Any], calls: float, batch: float,
                     context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the int8 paged-attention kernel (one
    call reads ONE attention layer's K and V of `batch` sequences of
    `context` tokens): codes and scales in, q in and o back."""
    h, hd = int(c["num_attention_heads"]), attn_head_dim(c)
    per_token = kv_bytes_per_token(c) / attention_layers(c)
    bytes_ = calls * batch * (context * per_token
                              + 2 * h * hd * BYTES["bfloat16"])
    flops = calls * batch * context * 4.0 * h * hd
    return {"flops": flops / chips, "bytes": bytes_ / chips}


MOE_KERNEL_CALLS_PER_LAYER = 2  # gate-and-up, then down


def moe_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the grouped int8 matmul in decode
    steps of `batch` tokens (two calls a layer: gate-and-up, down): the
    weights of the experts that are hit, expected, and the pairs' rows in
    and out."""
    d, me = int(c["hidden_size"]), int(c["intermediate_size"])
    layer_steps = calls / MOE_KERNEL_CALLS_PER_LAYER
    pairs = batch * int(c["num_experts_per_tok"])
    flops = layer_steps * 2.0 * pairs * expert_params(c)
    bytes_ = layer_steps * (
        experts_hit(c, batch) * expert_params(c) * _weight_bytes(c)
        + pairs * (d + 2 * me + me + d) * BYTES["bfloat16"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def ssm_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the state-update kernel (one call is
    ONE state-space layer's step for `batch` live sequences): each
    sequence's float32 state read and written once, about 6 operations an
    element; the decay rows, D_t x, B, C in and y out."""
    heads, p, n = (int(c["mamba_n_heads"]), int(c["mamba_d_head"]),
                   int(c["mamba_d_state"]))
    rows = 4.0 * (heads * n + 2 * heads * p + 2 * n)
    bytes_ = calls * batch * (2.0 * state_bytes_per_sequence(c) + rows)
    flops = calls * batch * _state_flops_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """The paged-attention kernel runs once an ATTENTION layer a step."""
    return attention_layers(config)


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and BOTH pools (the
    attention layers' pages, the per-slot state and tails) as
    `ShapeDtypeStruct`s on ONE described device; mesh is None."""
    from jax.sharding import SingleDeviceSharding

    from generativeaiexamples_tpu.models import hybrid_ssm
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    if len(devices) > 1:
        raise ValueError("granitemoehybrid: no sharded form")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        hybrid_ssm.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype), slots=ecfg.max_batch_size))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
