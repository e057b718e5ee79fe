"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, `model_type:
"KeyeVL2"`): the Qwen3-MoE block (grouped-query attention with a per-head
RMSNorm on q and on k BEFORE the rotary embedding; in EVERY layer
`num_experts` routed experts, the `num_experts_per_tok` largest router
logits with gates = softmax over those, no shared expert; an untied head)
with DeepSeek's lightning indexer beside the attention (`sa_config`:
`indexer_num_heads` query heads of `indexer_head_dim`, ONE key head, a
weight a head; a query attends to the `topk` tokens of largest index
score, a tie going to the earlier one, and to all of them while there are
no more than `topk`).

The configuration file runs one pipeline stage of twelve layers with
every expert and the whole vocabulary on one chip (model-configs guide,
section 4): only `num_hidden_layers` is cut. The vision tower is no part
of it: the traffic is text.

The same three parts as `llama.py`, sharing nothing between them: (1, 2,
6) how the PROGRAM builds this model; (3) the plain reference, from the
parameter tree's leaves; (4, 5) the work of a step, counted from the
file's shapes with no JAX. `weight`, `rms_norm` and `rope` come from
`llama.py`, as benchmark/README.md allows.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.architectures.llama import BYTES, rms_norm, rope, weight


def _sa(c: Dict[str, Any]) -> Dict[str, Any]:
    sa = c["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("keyevl2: one index key head is what is written")
    return sa


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models.sparse_attn_moe import (
            SparseAttnMoeConfig)
    except ImportError as e:  # a program from before the indexer
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'keyevl2' (no indexer, no index rows beside the "
                         f"cache, no selection inside paged attention): {e}")
    if config["attention_bias"] or config["tie_word_embeddings"] \
            or config["mlp_only_layers"] or config["use_sliding_window"] \
            or int(config["decoder_sparse_step"]) != 1 \
            or not config["norm_topk_prob"] \
            or int(config["num_local_experts"]) != int(config["num_experts"]):
        raise ValueError("keyevl2: no bias, an untied head, experts in every "
                         "layer, no window, renormalised gates and every "
                         "expert held are what is written")
    sa = _sa(config)
    return SparseAttnMoeConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_head_dim=int(sa["indexer_head_dim"]),
        index_topk=int(sa["topk"]),
        n_experts=int(config["num_experts"]),
        n_experts_per_tok=int(config["num_experts_per_tok"]),
        moe_mlp_dim=int(config["moe_intermediate_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        prefill_tile=int(sa["q_chunk_size"]),
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device -----------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    from generativeaiexamples_tpu.models import sparse_attn_moe

    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'keyevl2' has no sharded "
                         "form; it takes one device")
    quantize = config["serving"]["quantize_weights"] == "int8"
    return sparse_attn_moe.init_params_on_device(mcfg, seed,
                                                 quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# The equations of ISSUE 42 in float32 `jax.numpy` under `highest`
# precision, layer by layer from the parameter tree's leaves: the WHOLE
# [S, S] index-score matrix, `top_k` a row, a dense masked softmax; a loop
# over the experts with ONE expert's weights in float32 at a time; the
# head in blocks of rows, the logits handed back on the host. No tiles, no
# cache, no kernel, and no code shared with the program.

def _piece(w, index):
    """A float32 slice of a stacked leaf at a traced index."""
    if hasattr(w, "q"):
        return w.q[index].astype(jnp.float32) \
            * w.s[index].astype(jnp.float32)[None, :]
    return w[index].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shape",))
def _index_scores(x, w, *, shape):
    """I[t, s] of one layer for a sequence x [S, D], minus infinity above
    the diagonal."""
    Hi, Di, theta, eps = shape
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = rope((h @ weight(w["wq_idx"])).reshape(S, Hi, Di), theta)
    k = h @ weight(w["wk_idx"])
    mu = jnp.mean(k, -1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(jnp.mean((k - mu) ** 2, -1, keepdims=True)
                                 + eps)
    k = k * weight(w["k_idx_norm_w"]) + weight(w["k_idx_norm_b"])
    k = rope(k[:, None, :], theta)[:, 0]
    wt = (h @ weight(w["w_idx"])) * (Hi ** -0.5 * Di ** -0.5)
    def head(j, acc):  # a head at a time: one [S, S] product is held
        return acc + wt[:, j, None] * jax.nn.relu(q[:, j] @ k.T)

    scores = jax.lax.fori_loop(0, Hi, head, jnp.zeros((S, S))) + 0.0
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("topk", "sparse"))
def _selection(scores, *, topk, sparse):
    """Row t's set: every s <= t while t + 1 <= topk, else the topk
    positions of largest score (top_k's order: a tie to the earlier)."""
    S = scores.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    if not sparse or S <= topk:
        return causal
    _, idx = jax.lax.top_k(scores, topk)
    picked = jnp.zeros((S, S), bool).at[jnp.arange(S)[:, None], idx].set(True)
    return picked & causal


@functools.partial(jax.jit, static_argnames=("shape",))
def _qkv(x, w, *, shape):
    H, KH, Hd, theta, eps = shape
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = (h @ weight(w["wq"])).reshape(S, H, Hd)
    k = (h @ weight(w["wk"])).reshape(S, KH, Hd)
    v = (h @ weight(w["wv"])).reshape(S, KH, Hd)
    return (rope(rms_norm(q, weight(w["q_norm"]), eps), theta),
            rope(rms_norm(k, weight(w["k_norm"]), eps), theta), v)


@jax.jit
def _attend(q, k, v, mask):
    """The query heads of ONE KV head: q [S, G, Hd], k, v [S, Hd]."""
    s = jnp.einsum("qgd,kd->gqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, -1), v)


def _attention(x, w, mask, *, shape):
    """x + Attn(RMSNorm(x)) under `mask` [S, S]; a KV head's group of
    query heads at a time, so that 8 and not 32 [S, S] products are held
    beside the served model."""
    H, KH, Hd = shape[:3]
    S = x.shape[0]
    q, k, v = _qkv(x, w, shape=shape)
    q = q.reshape(S, KH, H // KH, Hd)
    a = jnp.stack([_attend(q[:, g], k[:, g], v[:, g], mask)
                   for g in range(KH)], axis=1)
    return x + a.reshape(S, H * Hd) @ weight(w["wo"])


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _route(x, w, *, top_k, eps):
    h = rms_norm(x, weight(w["ln2"]), eps)
    top, idx = jax.lax.top_k(h @ weight(w["router"]), top_k)
    return h, idx, jax.nn.softmax(top, axis=-1)


@jax.jit
def _expert(h, idx, gates, gate_up, down, e):
    """Expert `e` of one layer ([E, ...] stacks), gated."""
    g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)          # [S]
    gu = h @ _piece(gate_up, e)
    m = gu.shape[-1] // 2
    return g[:, None] * ((jax.nn.silu(gu[:, :m]) * gu[:, m:])
                         @ _piece(down, e))


HEAD_ROW_BLOCK = 512  # rows of the logits computed at once on the device


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_rows(x, ln_f, head, *, eps):
    return rms_norm(x, weight(ln_f), eps) @ weight(head)


def reference_forward(config: Dict[str, Any], params, token_ids, *,
                      sparse: bool = True, keep_layers=()):
    """-> (logits [S, vocab] float32 ON THE HOST, {layer: (index scores
    [S, S], selection [S, S])} for `keep_layers`, the router's choices
    [layers, S, top_k]). `sparse=False` is the negative control: the same
    model with dense causal attention."""
    sa = _sa(config)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    idx_shape = (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
                 theta, eps)
    attn_shape = (int(config["num_attention_heads"]),
                  int(config["num_key_value_heads"]),
                  int(config["head_dim"]), theta, eps)
    layers = params["layers"]
    kept, choices = {}, []
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for l in range(int(config["num_hidden_layers"])):
            w = {k: jax.tree.map(lambda a: a[l], v) for k, v in layers.items()
                 if k not in ("we_gate_up", "we_down")}
            scores = _index_scores(x, w, shape=idx_shape)
            mask = _selection(scores, topk=int(sa["topk"]), sparse=sparse)
            if l in keep_layers:
                kept[l] = (scores, mask)
            x = _attention(x, w, mask, shape=attn_shape)
            h, idx, gates = _route(
                x, w, top_k=int(config["num_experts_per_tok"]), eps=eps)
            gate_up = jax.tree.map(lambda a: a[l], layers["we_gate_up"])
            down = jax.tree.map(lambda a: a[l], layers["we_down"])
            y = jnp.zeros_like(x)
            for e in range(int(config["num_experts"])):
                y = y + _expert(h, idx, gates, gate_up, down, e)
            x = x + y
            choices.append(idx)
        S = x.shape[0]
        logits = np.concatenate([np.asarray(_head_rows(
            x[a:a + HEAD_ROW_BLOCK], params["ln_f"], params["lm_head"],
            eps=eps)) for a in range(0, S, HEAD_ROW_BLOCK)], axis=0)
    return logits, kept, jnp.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids):
    """[S] token ids -> [S, vocab] float32 logits (a host array)."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step ------------------------------------------------
# The algorithm's work, whatever form the program gives it: every weight
# outside the experts is read once a program; of the experts, those that
# some token chose (EXPECTED under uniform routing); every cached index key
# of a live sequence is scored; the K and V of the tokens selected (no
# more than `topk`) are read.

def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def attention_params(c: Dict[str, Any]) -> int:
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    h, kh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return d * hd * (h + 2 * kh) + h * hd * d


def indexer_int8_params(c: Dict[str, Any]) -> int:
    sa = _sa(c)
    return int(c["hidden_size"]) * int(sa["indexer_num_heads"]) \
        * int(sa["indexer_head_dim"])


def small_bytes(c: Dict[str, Any]) -> float:
    """The router and the indexer's key and head-weight projections,
    bf16, every layer."""
    sa = _sa(c)
    return float(BYTES["bfloat16"] * int(c["num_hidden_layers"])
                 * int(c["hidden_size"]) * (
                     int(c["num_experts"]) + int(sa["indexer_head_dim"])
                     + int(sa["indexer_num_heads"])))


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["moe_intermediate_size"])


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def always_read_params(c: Dict[str, Any]) -> int:
    """int8 weights every program reads whatever the routing."""
    return int(c["num_hidden_layers"]) * (
        attention_params(c) + indexer_int8_params(c)) + head_params(c)


def experts_hit(c: Dict[str, Any], tokens: float) -> float:
    """Experts some token of `tokens` chose, expected, uniform routing."""
    e = int(c["num_experts"])
    p = int(c["num_experts_per_tok"]) / e
    return e * (1.0 - (1.0 - p) ** max(tokens, 0.0))


def kv_bytes_per_token_layer(c: Dict[str, Any]) -> float:
    """K and V of one token in one layer: int8 codes and a float32 scale
    a (kv head, token), K and V each (1,056 B at 4 heads of 128)."""
    per = int(c["head_dim"]) * BYTES[c["serving"]["kv_dtype"]]
    if c["serving"]["kv_dtype"] == "int8":
        per += 4
    return float(2 * int(c["num_key_value_heads"]) * per)


def index_bytes_per_token_layer(c: Dict[str, Any]) -> float:
    """The index key of one token in one layer, bf16 (128 B at 64)."""
    return float(int(_sa(c)["indexer_head_dim"]) * BYTES["bfloat16"])


def attention_kernel(c: Dict[str, Any], calls: float, batch: float,
                     context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the selected attention (one call is
    ONE layer's attention of `batch` sequences of `context` cached
    tokens): the K and V of the min(context, topk) selected tokens, q in
    and o back, 4 x heads x head_dim operations a selected token."""
    h, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    rows = min(context, float(_sa(c)["topk"]))
    bytes_ = calls * batch * (rows * kv_bytes_per_token_layer(c)
                              + 2 * h * hd * BYTES["bfloat16"])
    flops = calls * batch * rows * 4.0 * h * hd
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def index_kernel(c: Dict[str, Any], calls: float, batch: float,
                 context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the index scores (one call is ONE
    layer's scores of `batch` sequences of `context` cached tokens):
    every cached key read, 2 x heads x head_dim operations a key, a
    float32 score a key out."""
    sa = _sa(c)
    hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    bytes_ = calls * batch * context * (index_bytes_per_token_layer(c) + 4.0)
    flops = calls * batch * context * 2.0 * hi * di
    return {"flops": flops / chips, "bytes": bytes_ / chips}


MOE_KERNEL_CALLS_PER_LAYER = 2  # gate-and-up, then down


def moe_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the grouped int8 matmul in decode
    steps of `batch` tokens (two calls a layer): the weights of the
    experts that are hit, expected, and the pairs' rows in and out."""
    d, me = int(c["hidden_size"]), int(c["moe_intermediate_size"])
    layer_steps = calls / MOE_KERNEL_CALLS_PER_LAYER
    pairs = batch * int(c["num_experts_per_tok"])
    flops = layer_steps * 2.0 * pairs * expert_params(c)
    bytes_ = layer_steps * (
        experts_hit(c, batch) * expert_params(c) * _weight_bytes(c)
        + pairs * (d + 2 * me + me + d) * BYTES["bfloat16"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def _routed_flops(c: Dict[str, Any], tokens: float) -> float:
    return 2.0 * tokens * int(c["num_experts_per_tok"]) * expert_params(c) \
        * int(c["num_hidden_layers"])


def _expert_bytes(c: Dict[str, Any], tokens: float) -> float:
    return float(int(c["num_hidden_layers"]) * experts_hit(c, tokens)
                 * expert_params(c) * _weight_bytes(c))


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each: the layer's parts, summed."""
    layers = int(c["num_hidden_layers"])
    attn = attention_kernel(c, layers, batch, context + 1)
    index = index_kernel(c, layers, batch, context + 1)
    flops = 2.0 * batch * always_read_params(c) + _routed_flops(c, batch) \
        + attn["flops"] + index["flops"]
    bytes_ = float(always_read_params(c) * _weight_bytes(c)) \
        + small_bytes(c) + _expert_bytes(c, batch) \
        + attn["bytes"] + index["bytes"]
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions: a row scores the
    keys before it (half the prompt, on average) and attends to no more
    than `topk` of them."""
    sa = _sa(c)
    layers = int(c["num_hidden_layers"])
    h, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    body = always_read_params(c) - head_params(c)
    flops = 2.0 * prompt_tokens * body + _routed_flops(c, prompt_tokens)
    flops += prompt_tokens * (mean_prompt / 2.0) * 2.0 * hi * di * layers
    flops += prompt_tokens * min(mean_prompt / 2.0, float(sa["topk"])) \
        * 4.0 * h * hd * layers
    sequences = prompt_tokens / max(mean_prompt, 1.0)
    flops += 2.0 * sequences * head_params(c)
    per_program = prompt_tokens / max(programs, 1.0)
    bytes_ = programs * (float(always_read_params(c) * _weight_bytes(c))
                         + small_bytes(c) + _expert_bytes(c, per_program))
    bytes_ += prompt_tokens * layers * (kv_bytes_per_token_layer(c)
                                        + index_bytes_per_token_layer(c))
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """The selected attention (`paged_attention_sparse`) runs once a
    layer a step."""
    return int(config["num_hidden_layers"])


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and the pool (K and V, and
    the index rows) as `ShapeDtypeStruct`s on ONE described device; mesh
    is None."""
    from jax.sharding import SingleDeviceSharding

    from generativeaiexamples_tpu.models import sparse_attn_moe
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    if len(devices) > 1:
        raise ValueError("keyevl2: no sharded form")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        sparse_attn_moe.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype), slots=ecfg.max_batch_size))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
