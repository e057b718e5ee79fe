"""SmallThinker-21BA3B-Instruct (PowerInfer, `model_name:
"smallthinker_21b_instruct"`): WINDOW AND FULL ATTENTION IN ONE MODEL over
whole sparse experts. Every layer has a kind, read from two published
layouts (both `[0, 1, 1, 1]` thirteen times): `sliding_window_layout[l]`
1 means token t attends s <= t with t - s < `sliding_window_size` only,
0 means every s <= t; `rope_layout[l]` 1 means q and k take the rotary
embedding, 0 means nothing positional is read at all. The router reads
the ATTENTION's normed input (`moe_primary_router_apply_softmax`: softmax
over all `moe_num_primary_experts`, the `moe_num_active_primary_experts`
largest, renormalised under `norm_topk_prob`), the experts are ReGLU
(relu(gate) * up, computed dense), there is no shared expert and the
head is untied.

The configuration file runs one pipeline stage of twelve layers (three
whole periods) with every expert and the whole vocabulary on one chip
(model-configs guide, section 4): `num_hidden_layers` and the two layouts
are cut to their first twelve entries.

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


def _layouts(c: Dict[str, Any]):
    """(window layout, rope layout) of the layers the file runs."""
    n = int(c["num_hidden_layers"])
    win, rot = c["sliding_window_layout"], c["rope_layout"]
    if len(win) != n or len(rot) != n:
        raise ValueError(f"smallthinker: {n} layers, layouts of "
                         f"{len(win)} and {len(rot)}")
    return tuple(int(k) for k in win), tuple(int(k) for k in rot)


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    try:
        from generativeaiexamples_tpu.models.window_attn_moe import (
            WindowAttnMoeConfig)
    except ImportError as e:  # a program from before window layers
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'smallthinker' (no window layers beside global "
                         f"ones, no second page table for their rows, no "
                         f"router before the attention): {e}")
    if config["tie_word_embeddings"] or config["rope_scaling"] is not None \
            or not config["moe_primary_router_apply_softmax"] \
            or not config["norm_topk_prob"]:
        raise ValueError("smallthinker: an untied head, no rope scaling, a "
                         "softmax over every expert and renormalised gates "
                         "are what is written")
    win, rot = _layouts(config)
    return WindowAttnMoeConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        window=int(config["sliding_window_size"]),
        window_layout=win, rope_layout=rot,
        n_experts=int(config["moe_num_primary_experts"]),
        n_experts_per_tok=int(config["moe_num_active_primary_experts"]),
        moe_mlp_dim=int(config["moe_ffn_hidden_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device -----------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    from generativeaiexamples_tpu.models import window_attn_moe

    if len(devices) > 1:
        raise SystemExit("benchmark: architecture 'smallthinker' has no "
                         "sharded form; it takes one device")
    quantize = config["serving"]["quantize_weights"] == "int8"
    return window_attn_moe.init_params_on_device(mcfg, seed,
                                                 quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# The equations of ISSUE 44 in float32 `jax.numpy` under `highest`
# precision, layer by layer from the parameter tree's leaves: a dense
# masked softmax over the whole sequence, ROW_BLOCK query rows at a time
# (so that a prompt past the window fits beside the served model); a loop
# over the experts with ONE expert's weights in float32 at a time; the
# head in blocks of rows, the logits handed back on the host. No cache, no
# pages, no kernel, and no code shared with the program.

ROW_BLOCK = 512  # query rows of the attention and of the head at once


def _piece(w, index):
    """A float32 slice of a stacked leaf at a traced index."""
    if hasattr(w, "q"):
        return w.q[index].astype(jnp.float32) \
            * w.s[index].astype(jnp.float32)[None, :]
    return w[index].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shape", "rotate"))
def _qkv(x, w, *, shape, rotate):
    """(the attention's normed input h, q, k, v): q and k rotated at the
    token's index where the layer's `rope_layout` says so, else exactly as
    projected."""
    H, KH, Hd, theta, eps = shape
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = (h @ weight(w["wq"])).reshape(S, H, Hd)
    k = (h @ weight(w["wk"])).reshape(S, KH, Hd)
    v = (h @ weight(w["wv"])).reshape(S, KH, Hd)
    if rotate:
        q, k = rope(q, theta), rope(k, theta)
    return h, q, k, v


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


def _attention(x, q, k, v, wo, window):
    """x + Attn W_o; a KV head's group of query heads and ROW_BLOCK rows
    at a time."""
    S, H, Hd = q.shape
    KH = k.shape[1]
    q = q.reshape(S, KH, H // KH, Hd)
    a = jnp.concatenate([jnp.stack(
        [_attend(q[r:r + ROW_BLOCK, g], k[:, g], v[:, g], r, window=window)
         for g in range(KH)], axis=1) for r in range(0, S, ROW_BLOCK)])
    return x + a.reshape(S, H * Hd) @ weight(wo)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _route(h, router, *, top_k):
    """softmax over ALL experts, the top_k largest (a tie to the lower
    index), renormalised."""
    top, idx = jax.lax.top_k(jax.nn.softmax(h @ weight(router), -1), top_k)
    return idx, top / jnp.sum(top, -1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("act",))
def _expert(h, idx, gates, gate_up, down, e, *, act):
    """Expert `e` of one layer ([E, ...] stacks), gated: ReGLU."""
    g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)          # [S]
    gu = h @ _piece(gate_up, e)
    m = gu.shape[-1] // 2
    return g[:, None] * ((getattr(jax.nn, act)(gu[:, :m]) * gu[:, m:])
                         @ _piece(down, e))


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, *, eps):
    return rms_norm(x, weight(w), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_rows(x, ln_f, head, *, eps):
    return rms_norm(x, weight(ln_f), eps) @ weight(head)


def reference_forward(config: Dict[str, Any], params, token_ids, *,
                      router_reads: str = "attention", act: str = "relu",
                      windowed: bool = True):
    """-> (logits [S, vocab] float32 ON THE HOST, the router's choices
    [layers, S, top_k]). The negative controls: `router_reads="ffn"` (the
    router on the feed-forward's normed input, where every other block of
    the repo has it), `act="silu"`, `windowed=False` (every layer sees
    everything)."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    shape = (int(config["num_attention_heads"]),
             int(config["num_key_value_heads"]), int(config["head_dim"]),
             theta, eps)
    win, rot = _layouts(config)
    top_k = int(config["moe_num_active_primary_experts"])
    layers = params["layers"]
    choices = []
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for l in range(int(config["num_hidden_layers"])):
            w = {k: jax.tree.map(lambda a: a[l], v) for k, v in layers.items()
                 if k not in ("we_gate_up", "we_down")}
            h, q, k, v = _qkv(x, w, shape=shape, rotate=bool(rot[l]))
            window = int(config["sliding_window_size"]) \
                if win[l] and windowed else None
            x = _attention(x, q, k, v, w["wo"], window)
            h2 = _normed(x, w["ln2"], eps=eps)
            idx, gates = _route(h if router_reads == "attention" else h2,
                                w["router"], top_k=top_k)
            gate_up = jax.tree.map(lambda a: a[l], layers["we_gate_up"])
            down = jax.tree.map(lambda a: a[l], layers["we_down"])
            y = jnp.zeros_like(x)
            for e in range(int(config["moe_num_primary_experts"])):
                y = y + _expert(h2, idx, gates, gate_up, down, e, act=act)
            x = x + y
            choices.append(idx)
        S = x.shape[0]
        logits = np.concatenate([np.asarray(_head_rows(
            x[a:a + ROW_BLOCK], params["ln_f"], params["lm_head"],
            eps=eps)) for a in range(0, S, ROW_BLOCK)], axis=0)
    return logits, jnp.stack(choices)


def reference_logits(config: Dict[str, Any], params, token_ids):
    """[S] token ids -> [S, vocab] float32 logits (a host array)."""
    return reference_forward(config, params, token_ids)[0]


# -- 4. the work of a step ------------------------------------------------
# The algorithm's work, whatever form the program gives it: every weight
# outside the experts is read once a program; of the experts, those that
# some token chose (EXPECTED under uniform routing); a global layer reads
# the K and V of every cached token of a live sequence, a window layer of
# the last min(context, window).

def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def attention_params(c: Dict[str, Any]) -> int:
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    h, kh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return d * hd * (h + 2 * kh) + h * hd * d


def router_bytes(c: Dict[str, Any]) -> float:
    """The router, bf16, every layer."""
    return float(BYTES["bfloat16"] * int(c["num_hidden_layers"])
                 * int(c["hidden_size"]) * int(c["moe_num_primary_experts"]))


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * int(c["hidden_size"]) * int(c["moe_ffn_hidden_size"])


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def always_read_params(c: Dict[str, Any]) -> int:
    """int8 weights every program reads whatever the routing."""
    return int(c["num_hidden_layers"]) * attention_params(c) + head_params(c)


def experts_hit(c: Dict[str, Any], tokens: float) -> float:
    """Experts some token of `tokens` chose, expected, uniform routing."""
    e = int(c["moe_num_primary_experts"])
    p = int(c["moe_num_active_primary_experts"]) / e
    return e * (1.0 - (1.0 - p) ** max(tokens, 0.0))


def rows_by_kind(c: Dict[str, Any]):
    """(global layers, window layers) of the layers the file runs."""
    n_window = sum(_layouts(c)[0])
    return int(c["num_hidden_layers"]) - n_window, n_window


def kv_bytes_per_token_layer(c: Dict[str, Any]) -> float:
    """K and V of one token in one layer: int8 codes and a float32 scale
    a (kv head, token), K and V each (1,056 B at 4 heads of 128)."""
    per = int(c["head_dim"]) * BYTES[c["serving"]["kv_dtype"]]
    if c["serving"]["kv_dtype"] == "int8":
        per += 4
    return float(2 * int(c["num_key_value_heads"]) * per)


def cached_rows(c: Dict[str, Any], context: float) -> float:
    """Cached tokens ONE sequence's attention reads in one step over all
    layers: `context` a global layer, min(context, window) a window
    layer."""
    n_global, n_window = rows_by_kind(c)
    return n_global * context + n_window * min(
        context, float(c["sliding_window_size"]))


def _attention_work(c: Dict[str, Any], rows: float, queries: float):
    """`rows` cached tokens read in all by `queries` (call, sequence)
    pairs: K and V in, q in and o back, 4 x heads x head_dim operations a
    token."""
    h, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    return {"flops": rows * 4.0 * h * hd,
            "bytes": rows * kv_bytes_per_token_layer(c)
            + queries * 2 * h * hd * BYTES["bfloat16"]}


def window_attention_pages(c: Dict[str, Any], pages: float, calls: float,
                           batch: float, chips: int = 1) -> Dict[str, float]:
    """The work of the window rows' kernel calls that WALKED `pages` pages
    in all (`calls` calls of `batch` sequences; a page is page_size tokens
    of ONE layer): what the engine's `window_cache` events count, turned
    into bytes and operations here."""
    tokens = pages * int(c["serving"]["engine"]["page_size"])
    work = _attention_work(c, tokens, calls * batch)
    return {k: v / chips for k, v in work.items()}


MOE_KERNEL_CALLS_PER_LAYER = 2  # gate-and-up, then down


def moe_kernel(c: Dict[str, Any], calls: float, batch: float,
               chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the grouped int8 matmul in decode
    steps of `batch` tokens (two calls a layer): the weights of the
    experts that are hit, expected, and the pairs' rows in and out."""
    d, me = int(c["hidden_size"]), int(c["moe_ffn_hidden_size"])
    layer_steps = calls / MOE_KERNEL_CALLS_PER_LAYER
    pairs = batch * int(c["moe_num_active_primary_experts"])
    flops = layer_steps * 2.0 * pairs * expert_params(c)
    bytes_ = layer_steps * (
        experts_hit(c, batch) * expert_params(c) * _weight_bytes(c)
        + pairs * (d + 2 * me + me + d) * BYTES["bfloat16"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def _routed_flops(c: Dict[str, Any], tokens: float) -> float:
    return 2.0 * tokens * int(c["moe_num_active_primary_experts"]) \
        * expert_params(c) * int(c["num_hidden_layers"])


def _expert_bytes(c: Dict[str, Any], tokens: float) -> float:
    return float(int(c["num_hidden_layers"]) * experts_hit(c, tokens)
                 * expert_params(c) * _weight_bytes(c))


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each: the layer's parts, summed; a window layer reads
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
    keys before it (half the prompt, on average), a window layer's no
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
    (`paged_attention_int8` a global layer, `paged_attention_int8_window`
    a window layer: `paged_attention` matches both)."""
    return int(config["num_hidden_layers"])


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and both groups of the pool
    as `ShapeDtypeStruct`s on ONE described device; mesh is None."""
    from jax.sharding import SingleDeviceSharding

    from generativeaiexamples_tpu.models import window_attn_moe
    from generativeaiexamples_tpu.serving.kv_cache import (
        WindowPool, window_pool_pages)

    if len(devices) > 1:
        raise ValueError("smallthinker: no sharded form")
    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        window_attn_moe.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: WindowPool.zeros(
        mcfg, config["serving"]["n_pages"],
        window_pool_pages(mcfg.window, ecfg), ecfg.page_size))
    one = SingleDeviceSharding(devices[0])

    def on_device(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    return mcfg, on_device(pshape), on_device(pool_shape), None
