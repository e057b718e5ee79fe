"""The Ouro looped decoder (ByteDance/Ouro-2.6B, `model_type: "ouro"`):
the SAME `num_hidden_layers` blocks run `total_ut_steps` times for every
token; every (pass, block) keeps a cache row of its own; a block norms
each branch on BOTH sides (input and output); the final norm closes
every pass, its output opens the next pass and, after the last, feeds
the head.

The same three parts as `llama.py`, sharing nothing between them: (1, 2,
6) how the PROGRAM builds this model; (3) the plain reference, from the
parameter tree's leaves; (4, 5) the work of a step, counted from the
configuration file's shapes with no JAX. Helpers that know nothing of the
loop (`weight`, `rms_norm`, `rope`, the per-block parameter count) come
from `llama.py`, as benchmark/README.md allows.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.architectures import llama as _llama
from benchmark.architectures.llama import (
    BYTES, head_size, layer_matmul_params, rms_norm, rope, weight)


def passes(c: Dict[str, Any]) -> int:
    return int(c["total_ut_steps"])


def cache_rows(c: Dict[str, Any]) -> int:
    """One row per (pass, block)."""
    return passes(c) * int(c["num_hidden_layers"])


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    """The Llama block's keys, plus what this family adds: the passes
    and the norm on each branch's output."""
    import dataclasses

    try:
        return dataclasses.replace(_llama.model_config(config),
                                   n_passes=passes(config), post_norms=True)
    except TypeError as e:  # a program from before the looped decoder
        raise SystemExit(f"benchmark: this program cannot run architecture "
                         f"'ouro' (its model configuration has no passes): "
                         f"{e}")


# -- 2. seeded parameters on the device(s) --------------------------------

init_params = _llama.init_params  # the leaves follow the model config


# -- 3. the plain reference -----------------------------------------------
# Written from the equations of ISSUE 29 / the published modelling file,
# in float32 `jax.numpy`, block by block, with no kernel, no cache and no
# batching: every pass recomputes attention over the whole sequence from
# that pass's own keys and values, which is what "a cache row per (pass,
# block)" means for a model that is never cached.
#
# Departure from the published model: the exit gate (a Linear(d, 1) on
# each pass's normed output) is left out. At the published
# `early_exit_threshold` of 1 no token leaves before the last pass and
# the gate's value is no part of the logits.

@functools.partial(jax.jit, static_argnames=("n_heads", "head_dim", "theta",
                                             "eps"))
def _block(x, w, *, n_heads, head_dim, theta, eps):
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = (h @ weight(w["wq"])).reshape(S, n_heads, head_dim)
    k = (h @ weight(w["wk"])).reshape(S, -1, head_dim)
    v = (h @ weight(w["wv"])).reshape(S, -1, head_dim)
    q, k = rope(q, theta), rope(k, theta)  # the same positions in every pass
    rep = n_heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    a = a.reshape(S, n_heads * head_dim) @ weight(w["wo"])
    x = x + rms_norm(a, weight(w["ln1_post"]), eps)  # HF input_layernorm_2
    h = rms_norm(x, weight(w["ln2"]), eps)
    m = (jax.nn.silu(h @ weight(w["w_gate"])) * (h @ weight(w["w_up"]))) \
        @ weight(w["w_down"])
    return x + rms_norm(m, weight(w["ln2_post"]), eps)  # post_attention_layernorm_2


@functools.partial(jax.jit, static_argnames=("eps",))
def _close(x, ln_f, *, eps):
    return rms_norm(x, weight(ln_f), eps)


@jax.jit
def _head(x, out_w):
    return x @ weight(out_w)


def reference_logits(config: Dict[str, Any], params, token_ids) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    eps = float(config["rms_norm_eps"])
    if config.get("tie_word_embeddings", False):
        raise ValueError("the Ouro reference has an output head of its own")
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for _ in range(passes(config)):
            for i in range(int(config["num_hidden_layers"])):
                w = jax.tree.map(lambda a: a[i], params["layers"])
                x = _block(x, w, n_heads=int(config["num_attention_heads"]),
                           head_dim=head_size(config),
                           theta=float(config["rope_theta"]), eps=eps)
            x = _close(x, params["ln_f"], eps=eps)  # closes EVERY pass
        return _head(x, params["lm_head"])  # ln_f is not applied again


# -- 4. the work of a step ------------------------------------------------
# The algorithm's work, as in llama.py. The block weights are read once A
# PASS, `total_ut_steps` times a program: the 2.5 GB of blocks do not
# stay on the chip between passes (its fast memory holds megabytes), so
# no implementation of this model can read them fewer times; the head is
# read once. The cache has a row per (pass, block): all of them are read
# and all are appended to.

def block_params(c: Dict[str, Any]) -> int:
    return int(c["num_hidden_layers"]) * layer_matmul_params(c)


def head_params(c: Dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def kv_bytes_per_token(c: Dict[str, Any]) -> float:
    """K and V of one token over all rows (llama's count is one row a
    block), in the served KV type, with an int8 cache's float32 scale
    per token, head and row."""
    return _llama.kv_bytes_per_token(c) * passes(c)


_weight_bytes = _llama._weight_bytes


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each, per chip of a tensor-parallel group of `chips`."""
    h, hd = int(c["num_attention_heads"]), head_size(c)
    t = passes(c)
    flops = 2.0 * batch * (t * block_params(c) + head_params(c))
    flops += 4.0 * batch * context * h * hd * cache_rows(c)  # QK^T and PV
    bytes_ = float((t * block_params(c) + head_params(c)) * _weight_bytes(c))
    bytes_ += batch * context * kv_bytes_per_token(c)  # read the cache
    bytes_ += batch * kv_bytes_per_token(c)            # append one token
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions (each reads the
    block weights once a pass and the head once)."""
    h, hd = int(c["num_attention_heads"]), head_size(c)
    t = passes(c)
    flops = 2.0 * prompt_tokens * t * block_params(c)
    flops += 2.0 * prompt_tokens * mean_prompt * h * hd * cache_rows(c)
    flops += 2.0 * (prompt_tokens / max(mean_prompt, 1.0)) \
        * head_params(c)  # the head, last position only
    bytes_ = programs * float((t * block_params(c) + head_params(c))
                              * _weight_bytes(c))
    bytes_ += prompt_tokens * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def attention_kernel(c: Dict[str, Any], calls: float, batch: float,
                     context: float, chips: int = 1) -> Dict[str, float]:
    """The work of `calls` calls of the decode step's attention kernel
    (one call reads ONE cache row of `batch` sequences of `context`
    tokens each): K and V with their scales in, QK^T and PV; the
    queries in and the output back are counted too (bf16)."""
    h, hd = int(c["num_attention_heads"]), head_size(c)
    row = kv_bytes_per_token(c) / cache_rows(c)
    bytes_ = calls * batch * (context * row + 2 * h * hd * BYTES["bfloat16"])
    flops = calls * 4.0 * batch * context * h * hd
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """The paged-attention kernel runs once per cache row per step."""
    return cache_rows(config)


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and page pool (with its
    `cache_rows` rows, from the program's PagePool) as
    `ShapeDtypeStruct`s with their shardings on `devices` (described,
    not attached); mesh is None on one device."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import sharding as shd
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    mcfg = model_config(config)
    pshape = jax.eval_shape(functools.partial(
        llama.init_params_on_device, mcfg,
        quantize=ecfg.quantize_weights == "int8"))
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype)))
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices).reshape(1, 1, len(devices)),
                    ("data", "fsdp", "tensor"))
        psh = shd.param_shardings(pshape, mcfg, mesh)
        pool_specs = ((shd.KV_FUSED_SPEC, shd.KV_FUSED_SCALE_SPEC)
                      if pool_shape.quantized
                      else (shd.KV_POOL_SPEC, shd.KV_POOL_SPEC))
        pool_sh = jax.tree.unflatten(
            jax.tree.structure(pool_shape),
            [NamedSharding(mesh, spec) for spec in pool_specs])
    else:
        mesh = None
        one = SingleDeviceSharding(devices[0])
        psh = jax.tree.map(lambda _: one, pshape)
        pool_sh = jax.tree.map(lambda _: one, pool_shape)

    def with_sh(shape_tree, sh_tree):
        return jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=h), shape_tree, sh_tree)

    return mcfg, with_sh(pshape, psh), with_sh(pool_shape, pool_sh), mesh
