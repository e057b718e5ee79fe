"""The Llama/Mistral decoder: RMSNorm before each branch, rotary
embedding on split halves, grouped-query causal attention, SwiGLU, one
pass over `num_hidden_layers` blocks. The entry every accepted
configuration resolves to (benchmark/architectures/__init__.py).

Three parts that share nothing: (1, 2, 6) how the PROGRAM builds this
model, which import the program; (3) the plain reference, which reads
only the parameter tree's leaves; (4, 5) the work of a step, counted from
the configuration file's shapes, with no JAX.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}


def head_size(c: Dict[str, Any]) -> int:
    return int(c.get("head_dim")
               or c["hidden_size"] // c["num_attention_heads"])


# -- 1. the program's model configuration ---------------------------------

def model_config(config: Dict[str, Any]):
    """The published config.json keys as the program's `LlamaConfig`."""
    from generativeaiexamples_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=head_size(config),
        mlp_dim=int(config["intermediate_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")))


# -- 2. seeded parameters on the device(s) --------------------------------

def init_params(config: Dict[str, Any], mcfg, seed: int, devices):
    """(params, mesh): weights from the seed, made on the device in the
    type they are served in. One device: mesh is None. Several: a mesh
    whose tensor axis the model's dims divide, every leaf born sharded."""
    from generativeaiexamples_tpu.models import llama

    quantize = config["serving"]["quantize_weights"] == "int8"
    if len(devices) > 1:
        from generativeaiexamples_tpu.parallel.mesh import build_mesh
        from generativeaiexamples_tpu.serving import sharding as shd

        mesh = shd.compatible_mesh(mcfg, build_mesh(devices=devices))
        return shd.init_sharded_params(mcfg, mesh, seed,
                                       quantize=quantize), mesh
    return llama.init_params_on_device(mcfg, seed, quantize=quantize), None


# -- 3. the plain reference -----------------------------------------------
# A decoder-only transformer forward pass in float32 `jax.numpy`, written
# from the published Mistral/Llama equations, with no kernel, no cache and
# no batching. It shares no code with the program under test; it reads
# only the parameter tree's leaves (int8 codes times their per-column
# scales are the weights). Run layer by layer, so that only one layer's
# weights exist in float32 at a time beside the int8 model.

def weight(w) -> jax.Array:
    """A leaf as float32: a plain array, or int8 codes `q` [.., in, out]
    with per-output-column scales `s` [.., out]."""
    if hasattr(w, "q"):
        return w.q.astype(jnp.float32) * w.s.astype(jnp.float32)[..., None, :]
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [S, n, Hd]; rotate the two halves of each head (the HF
    `rotate_half` convention the checkpoints are published in)."""
    S, _, Hd = x.shape
    inv = theta ** (-jnp.arange(0, Hd, 2, dtype=jnp.float32) / Hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : Hd // 2], x[..., Hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv_heads",
                                             "head_dim", "theta", "eps"))
def _layer(x, w, *, n_heads, n_kv_heads, head_dim, theta, eps):
    S = x.shape[0]
    h = rms_norm(x, weight(w["ln1"]), eps)
    q = (h @ weight(w["wq"])).reshape(S, n_heads, head_dim)
    k = (h @ weight(w["wk"])).reshape(S, n_kv_heads, head_dim)
    v = (h @ weight(w["wv"])).reshape(S, n_kv_heads, head_dim)
    q, k = rope(q, theta), rope(k, theta)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + att.reshape(S, n_heads * head_dim) @ weight(w["wo"])
    h = rms_norm(x, weight(w["ln2"]), eps)
    gate = jax.nn.silu(h @ weight(w["w_gate"])) * (h @ weight(w["w_up"]))
    return x + gate @ weight(w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, ln_f, out_w, *, eps, tied):
    w = weight(out_w)
    return rms_norm(x, weight(ln_f), eps) @ (w.T if tied else w)


def reference_logits(config: Dict[str, Any], params, token_ids) -> jax.Array:
    """[S] token ids -> [S, vocab] float32 logits."""
    eps = float(config["rms_norm_eps"])
    tied = bool(config.get("tie_word_embeddings", False))
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][jnp.asarray(token_ids)].astype(jnp.float32)
        for i in range(int(config["num_hidden_layers"])):
            w = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer(x, w, n_heads=int(config["num_attention_heads"]),
                       n_kv_heads=int(config["num_key_value_heads"]),
                       head_dim=head_size(config),
                       theta=float(config["rope_theta"]), eps=eps)
        out_w = params["tok_emb"] if tied else params["lm_head"]
        return _head(x, params["ln_f"], out_w, eps=eps, tied=tied)


# -- 4. the work of a step ------------------------------------------------
# Counts are the algorithm's: what must be computed and moved once, not
# what an implementation happens to do (padding, recomputation and
# re-reads do not count, so they lower the share).

def layer_matmul_params(c: Dict[str, Any]) -> int:
    """Weights of one block's seven projections."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    kh = int(c["num_key_value_heads"])
    hd = head_size(c)
    m = int(c["intermediate_size"])
    return d * h * hd + 2 * d * kh * hd + h * hd * d + 3 * d * m


def matmul_params(c: Dict[str, Any]) -> int:
    """Every weight a token's forward pass multiplies by: the blocks and
    the output head (the embedding is a lookup)."""
    return (int(c["num_hidden_layers"]) * layer_matmul_params(c)
            + int(c["hidden_size"]) * int(c["vocab_size"]))


def kv_bytes_per_token(c: Dict[str, Any]) -> float:
    """K and V of one token over all layers, in the served KV type, with
    an int8 cache's float32 scale per token, head and layer."""
    kh = int(c["num_key_value_heads"])
    kv = c["serving"]["kv_dtype"]
    per = 2 * kh * head_size(c) * BYTES[kv]
    if kv == "int8":
        per += 2 * kh * 4
    return float(int(c["num_hidden_layers"]) * per)


def _weight_bytes(c: Dict[str, Any]) -> int:
    return BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                 else "bfloat16"]


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each, per chip of a tensor-parallel group of `chips`."""
    h, hd = int(c["num_attention_heads"]), head_size(c)
    layers = int(c["num_hidden_layers"])
    flops = 2.0 * batch * matmul_params(c)
    flops += 4.0 * batch * context * h * hd * layers  # QK^T and PV
    bytes_ = float(matmul_params(c) * _weight_bytes(c))
    bytes_ += batch * context * kv_bytes_per_token(c)  # read the cache
    bytes_ += batch * kv_bytes_per_token(c)            # append one token
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions (each reads the
    weights once)."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    hd = head_size(c)
    layers = int(c["num_hidden_layers"])
    body = matmul_params(c) - d * int(c["vocab_size"])
    flops = 2.0 * prompt_tokens * body
    flops += 2.0 * prompt_tokens * mean_prompt * h * hd * layers  # causal
    flops += 2.0 * (prompt_tokens / max(mean_prompt, 1.0)) \
        * d * int(c["vocab_size"])  # the head, last position only
    bytes_ = programs * float(matmul_params(c) * _weight_bytes(c))
    bytes_ += prompt_tokens * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


# -- 5. step-kernel calls in one decode step ------------------------------

def step_kernel_calls(config: Dict[str, Any]) -> int:
    """The paged-attention kernel runs once per layer per step."""
    return int(config["num_hidden_layers"])


# -- 6. the shapes test_chip_compile.py compiles against ------------------

def compile_shapes(config: Dict[str, Any], ecfg, devices):
    """(mcfg, params, pool, mesh): parameters and page pool as
    `ShapeDtypeStruct`s with their shardings on `devices` (described, not
    attached); mesh is None on one device."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import sharding as shd
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    mcfg = model_config(config)
    init = functools.partial(llama.init_params_on_device, mcfg,
                             quantize=ecfg.quantize_weights == "int8")
    pshape = jax.eval_shape(init)
    pool_shape = jax.eval_shape(lambda: PagePool.zeros(
        mcfg, config["serving"]["n_pages"], ecfg.page_size,
        dtype=jnp.dtype(ecfg.kv_dtype)))
    if len(devices) > 1:
        mesh = Mesh(np.asarray(devices).reshape(1, 1, len(devices)),
                    ("data", "fsdp", "tensor"))
        psh = shd.param_shardings(pshape, mcfg, mesh)

        def pool_spec(leaf):
            if not pool_shape.quantized:
                return shd.KV_POOL_SPEC
            return (shd.KV_FUSED_SPEC if leaf.dtype == jnp.int8
                    else shd.KV_FUSED_SCALE_SPEC)

        pool_sh = jax.tree.map(lambda l: NamedSharding(mesh, pool_spec(l)),
                               pool_shape)
    else:
        mesh = None
        one = SingleDeviceSharding(devices[0])
        psh = jax.tree.map(lambda _: one, pshape)
        pool_sh = jax.tree.map(lambda _: one, pool_shape)

    def with_sh(shape_tree, sh_tree):
        return jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=h), shape_tree, sh_tree)

    return mcfg, with_sh(pshape, psh), with_sh(pool_shape, pool_sh), mesh
