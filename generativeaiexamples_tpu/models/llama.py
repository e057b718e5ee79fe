"""Llama-family decoder in pure JAX (functional, pytree params).

TPU-native replacement for the LLM the reference serves via TensorRT-LLM
inside NIM containers (deploy/compose/docker-compose-nim-ms.yaml:2-22,
model `meta/llama3-8b-instruct`). Nothing here is a torch translation:

- Params are a plain pytree; per-layer weights are STACKED on a leading
  layer axis and the forward pass is a `lax.scan` over layers — one
  compiled layer body regardless of depth (fast XLA compiles, friendly
  to rematerialization).
- Attention is pluggable (ops.attention dispatcher: Pallas flash kernel
  on TPU, XLA reference elsewhere).
- Sharding is expressed as a parallel PartitionSpec pytree
  (`param_specs`) using the logical-axis rule table — Megatron-style TP
  (heads/mlp/vocab on the "tensor" axis) by default, with FSDP on the
  hidden axis available via the same rules.

Supports llama2/llama3 geometry: RMSNorm, RoPE (configurable theta),
GQA, SwiGLU MLP, optional tied embeddings.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, mm
from generativeaiexamples_tpu.parallel.mesh import LLM_RULES, logical_to_spec

Params = Dict[str, Any]


@dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1-style rope frequency scaling (HF config.json
    `rope_scaling` with `rope_type: "llama3"`).

    Wavelengths shorter than original_max/high_freq_factor are kept,
    longer than original_max/low_freq_factor are divided by `factor`,
    and the band in between is smoothly interpolated — matching HF
    transformers' `_compute_llama3_parameters`.
    """

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling as the DeepSeek-V3 family's code reads the HF
    keys (`rope_scaling` with `type: "yarn"`): every dimension's
    frequency is a blend of the original and the interpolated one
    (divided by `factor`), along a linear ramp between the correction
    dimensions of `beta_fast` and `beta_slow` rotations at the original
    length. `softmax_mscale` multiplies the attention's softmax scale
    (the family squares it: once for q, once for k); `cos_sin_scale`
    multiplies cos and sin."""

    factor: float = 32.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 4096

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def softmax_mscale(self) -> float:
        return self._mscale(self.factor, self.mscale_all_dim)

    @property
    def cos_sin_scale(self) -> float:
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))


def yarn_freqs(head_dim: int, theta: float, s: YarnScaling) -> jax.Array:
    """Inverse frequencies [Hd/2] under YaRN."""
    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(s.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(s.beta_fast)), 0)
    high = min(math.ceil(correction_dim(s.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim)
    return extra / s.factor * ramp + extra * (1.0 - ramp)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 14336
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    rope_scaling: Optional[RopeScaling] = None
    dtype: Any = jnp.bfloat16
    # Looped decoders: the SAME n_layers blocks run n_passes times for
    # every token (`walk_passes` below says how); each (pass, block) has
    # a cache row of its own and every pass closes with ln_f.
    n_passes: int = 1
    # RMSNorm on each branch's OUTPUT too, before the residual add
    # (leaves ln1_post / ln2_post), beside the one on its input.
    post_norms: bool = False

    @property
    def cache_rows(self) -> int:
        """Rows of a KV cache: one per (pass, block), row u * n_layers + l.
        The cache's leading axis is THIS, not the weights' layer axis."""
        return self.n_layers * self.n_passes

    # sparse experts whose weights live here: none (serving/engine.py)
    experts_held = 0

    @property
    def post_norm_init(self) -> float:
        """What the seeded initialisers give an output norm's gain: a
        branch's normed output has unit size times this, and a token's
        stream takes n_layers x n_passes x 2 of them, so 1/sqrt of that
        count keeps their sum the size of the stream that carries them
        (depth-scaled, as a residual branch's gain usually starts). With
        gains of one every block outweighs the whole carried state and a
        pass of random weights amplifies any rounding 1.75x (PERF.md,
        PR 29); a checkpoint's own gains replace these."""
        return (2.0 * self.n_layers * self.n_passes) ** -0.5

    @property
    def residual_dtype(self):
        """The type the residual stream is carried in. A looped model
        adds n_layers x n_passes x 2 branch outputs of about unit size
        onto it; in bfloat16 each is rounded to the stream's 8 bits, and
        at 192 block executions the logits are a fifth off their float32
        values (PERF.md, PR 29). Only the stream is float32: every
        matmul still takes and gives `dtype`."""
        return jnp.float32 if self.n_passes > 1 else self.dtype

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                           mlp_dim=28672)

    @staticmethod
    def llama3_2_1b() -> "LlamaConfig":
        # HF publishes this checkpoint with rope_type "llama3", factor 32.
        return LlamaConfig(vocab_size=128256, dim=2048, n_layers=16,
                           n_heads=32, n_kv_heads=8, head_dim=64,
                           mlp_dim=8192, tie_embeddings=True,
                           max_seq_len=131072,
                           rope_scaling=RopeScaling(factor=32.0))

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        """Hermetic-test geometry: compiles in < 1 s on one CPU core."""
        return LlamaConfig(vocab_size=vocab_size, dim=64, n_layers=2,
                           n_heads=4, n_kv_heads=2, head_dim=16, mlp_dim=128,
                           max_seq_len=128, dtype=jnp.float32)


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Random init (tests + pretraining-from-scratch); serving loads HF
    weights via models.hf_loader instead."""
    k = jax.random.split(key, 8)
    D, H, KH, Hd, M, L = (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                          cfg.mlp_dim, cfg.n_layers)

    def norm(key, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2]) ** -0.5
        return (jax.random.normal(key, shape) * scale).astype(cfg.dtype)

    params: Params = {
        "tok_emb": norm(k[0], cfg.vocab_size, D, scale=0.02),
        "ln_f": jnp.ones((D,), cfg.dtype),
        "layers": {
            "ln1": jnp.ones((L, D), cfg.dtype),
            "ln2": jnp.ones((L, D), cfg.dtype),
            "wq": norm(k[1], L, D, H * Hd),
            "wk": norm(k[2], L, D, KH * Hd),
            "wv": norm(k[3], L, D, KH * Hd),
            "wo": norm(k[4], L, H * Hd, D),
            "w_gate": norm(k[5], L, D, M),
            "w_up": norm(k[6], L, D, M),
            "w_down": norm(k[7], L, M, D),
        },
    }
    if cfg.post_norms:
        gain = jnp.full((L, D), cfg.post_norm_init, cfg.dtype)
        params["layers"]["ln1_post"] = params["layers"]["ln2_post"] = gain
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(k[0], D, cfg.vocab_size, scale=D ** -0.5)
    return params


def init_params_on_device(cfg: LlamaConfig, seed: int = 0, *,
                          quantize: bool = False, shardings=None) -> Params:
    """Seeded random params drawn leaf by leaf ON DEVICE, each leaf
    directly in its final dtype — the full-width path when no checkpoint
    exists (hermetic serving, bench, chip_smoke). Same tree as
    `init_params` (or, with `quantize`, as
    `quantize_llama_params(init_params(...))`), but no f32 or bf16 copy
    of a quantized leaf ever exists: llama3-8b int8 peaks at one leaf
    above its ~9 GB, where init-then-quantize needs 7.5 GB of f32 for
    `w_gate` alone. int8 codes are uniform with the per-column scale
    that gives the leaf `init_params`' standard deviation.

    `shardings`: a tree of NamedSharding aligned with the result
    (serving.sharding.param_shardings over `jax.eval_shape` of this
    function); each leaf is then created already sharded — never whole
    on one chip and moved."""
    D, H, KH, Hd, M, L, V = (cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, cfg.mlp_dim, cfg.n_layers,
                             cfg.vocab_size)
    root = jax.random.key(seed)
    leaf_ids = itertools.count(1)

    def draw(fn, sharding):
        key = jax.random.fold_in(root, next(leaf_ids))
        return jax.jit(fn, out_shardings=sharding)(key)

    def sharding_at(*path):
        sh = shardings
        for name in path:
            sh = None if sh is None else sh[name]
        return sh

    def ones(path, *shape, value=1.0):
        return draw(lambda k: jnp.full(shape, value, cfg.dtype),
                    sharding_at(*path))

    def normal(path, *shape, scale):
        return draw(lambda k: jax.random.normal(k, shape, cfg.dtype)
                    * jnp.asarray(scale, cfg.dtype), sharding_at(*path))

    def weight(path, *shape, scale=None):
        scale = scale if scale is not None else shape[-2] ** -0.5
        if not quantize:
            return normal(path, *shape, scale=scale)
        sh = sharding_at(*path)
        # uniform codes in [-127, 127] have std 127/sqrt(3)
        q = draw(lambda k: jnp.maximum(jax.lax.bitcast_convert_type(
            jax.random.bits(k, shape, jnp.uint8), jnp.int8), -127),
            None if sh is None else sh.q)
        s = draw(lambda k: jnp.full(shape[:-2] + shape[-1:],
                                    scale * 3 ** 0.5 / 127.0, jnp.float32),
                 None if sh is None else sh.s)
        return QuantizedTensor(q, s)

    params: Params = {
        "tok_emb": normal(("tok_emb",), V, D, scale=0.02),
        "ln_f": ones(("ln_f",), D),
        "layers": {
            "ln1": ones(("layers", "ln1"), L, D),
            "ln2": ones(("layers", "ln2"), L, D),
            "wq": weight(("layers", "wq"), L, D, H * Hd),
            "wk": weight(("layers", "wk"), L, D, KH * Hd),
            "wv": weight(("layers", "wv"), L, D, KH * Hd),
            "wo": weight(("layers", "wo"), L, H * Hd, D),
            "w_gate": weight(("layers", "w_gate"), L, D, M),
            "w_up": weight(("layers", "w_up"), L, D, M),
            "w_down": weight(("layers", "w_down"), L, M, D),
        },
    }
    if cfg.post_norms:  # drawn last: the other leaves keep their keys
        for name in ("ln1_post", "ln2_post"):
            params["layers"][name] = ones(("layers", name), L, D,
                                          value=cfg.post_norm_init)
    if not cfg.tie_embeddings:
        params["lm_head"] = weight(("lm_head",), D, V, scale=D ** -0.5)
    return params


def param_specs(cfg: LlamaConfig, rules: dict = LLM_RULES) -> Params:
    """PartitionSpec pytree parallel to init_params' output.

    Megatron layout: q/k/v and mlp-in sharded on output dim (tensor),
    wo / w_down sharded on input dim (tensor) so the row-parallel matmul
    reduces over the sharded axis; embeddings sharded on vocab.
    """
    ls = lambda *ax: logical_to_spec(ax, rules)  # noqa: E731
    specs: Params = {
        "tok_emb": ls("vocab", "embed_fsdp"),
        "ln_f": ls(None),
        "layers": {
            "ln1": ls("layers", None),
            "ln2": ls("layers", None),
            "wq": ls("layers", "embed_fsdp", "heads"),
            "wk": ls("layers", "embed_fsdp", "kv_heads"),
            "wv": ls("layers", "embed_fsdp", "kv_heads"),
            "wo": ls("layers", "heads", "embed_fsdp"),
            "w_gate": ls("layers", "embed_fsdp", "mlp"),
            "w_up": ls("layers", "embed_fsdp", "mlp"),
            "w_down": ls("layers", "mlp", "embed_fsdp"),
        },
    }
    if cfg.post_norms:
        specs["layers"]["ln1_post"] = ls("layers", None)
        specs["layers"]["ln2_post"] = ls("layers", None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ls("embed_fsdp", "vocab")
    return specs


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def rope_freqs(head_dim: int, theta: float,
               scaling: Optional[RopeScaling] = None) -> jax.Array:
    """Inverse frequencies [Hd/2], with optional llama3 or YaRN scaling."""
    if isinstance(scaling, YarnScaling):
        return yarn_freqs(head_dim, theta, scaling)
    freqs = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if scaling is None:
        return freqs
    s = scaling
    wavelen = 2.0 * jnp.pi / freqs
    high_wl = s.original_max_position_embeddings / s.high_freq_factor
    low_wl = s.original_max_position_embeddings / s.low_freq_factor
    smooth = (s.original_max_position_embeddings / wavelen - s.low_freq_factor) \
        / (s.high_freq_factor - s.low_freq_factor)
    mid = (1.0 - smooth) * freqs / s.factor + smooth * freqs
    return jnp.where(wavelen < high_wl, freqs,
                     jnp.where(wavelen > low_wl, freqs / s.factor, mid))


def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling: Optional[RopeScaling] = None) -> jax.Array:
    """Rotary position embedding. x [B, n, S, Hd], positions [B, S]."""
    Hd = x.shape[-1]
    freqs = rope_freqs(Hd, theta, scaling)  # [Hd/2]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,S,Hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if isinstance(scaling, YarnScaling) and scaling.cos_sin_scale != 1.0:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@dataclass
class KVCache:
    """Contiguous KV cache: k/v [R, B, KH, S_max, Hd] with R =
    cfg.cache_rows (the layers, times the passes of a looped model),
    lengths [B].

    `lengths[b]` counts tokens already written. The paged variant for
    continuous-batching serving lives in serving.kv_cache; this one backs
    simple generate() loops and tests.
    """

    k: jax.Array
    v: jax.Array
    lengths: jax.Array

    @staticmethod
    def zeros(cfg: LlamaConfig, batch: int, max_len: Optional[int] = None,
              dtype=None) -> "KVCache":
        S = max_len or cfg.max_seq_len
        shape = (cfg.cache_rows, batch, cfg.n_kv_heads, S, cfg.head_dim)
        dtype = dtype or cfg.dtype
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                       jnp.zeros((batch,), jnp.int32))


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "lengths"], meta_fields=[]
)


def add_branch(cfg: LlamaConfig, x, y, w, post: str, scope: str):
    """The residual add that ends a branch: x + y, or, for a family that
    norms a branch's output too (cfg.post_norms), x + RMSNorm(y; w[post])."""
    if cfg.post_norms:
        with jax.named_scope(scope):
            y = rms_norm(y, w[post], cfg.rms_eps)
    return x + y


def final_norm(cfg: LlamaConfig, params: Params, x):
    """ln_f before the output head. A looped model's last pass was
    already closed with it by `walk_passes`: not applied a second time."""
    if cfg.n_passes > 1:
        return x.astype(cfg.dtype)
    return rms_norm(x, params["ln_f"], cfg.rms_eps)


def walk_passes(cfg: LlamaConfig, params: Params, x, run_pass, state=None,
                rolled=False):
    """THE one place that says how a forward pass walks the model; the
    contiguous `forward` below and every paged step program of
    serving/engine_model.py take the walk from here.

    For pass u = 0 .. n_passes-1 it calls
        x, state, rows_out = run_pass(x, state, u * n_layers)
    which runs the n_layers blocks once, block l with WEIGHT slice l and
    CACHE ROW u * n_layers + l (the caller's own scan or unrolled loop).
    A looped model (n_passes > 1) closes every pass with ln_f, whose
    output opens the next pass and, after the last, feeds the head
    directly (`final_norm`). A one-pass model is one call and no norm
    here, which is the program it always was.

    Returns (x, state, rows_out) with each pass's per-row outputs
    concatenated along their leading axis into [cache_rows, ...].
    `rolled`: the passes as ONE `lax.fori_loop` body (row0 is then
    traced and rows_out is None), for callers whose pass is large to
    compile and returns no rows: the decode steps."""
    if cfg.n_passes == 1:
        return run_pass(x, state, 0)

    def one_pass(row0, x, state):
        with jax.named_scope("loop.pass"):
            x, state, out = run_pass(x, state, row0)
        with jax.named_scope("loop.norm"):
            x = rms_norm(x, params["ln_f"], cfg.rms_eps)
        return x, state, out

    if rolled:
        x, state = jax.lax.fori_loop(
            0, cfg.n_passes,
            lambda u, c: one_pass(u * cfg.n_layers, *c)[:2], (x, state))
        return x, state, None
    outs = []
    for u in range(cfg.n_passes):
        x, state, out = one_pass(u * cfg.n_layers, x, state)
        outs.append(out)
    if outs[0] is None:
        return x, state, None
    return x, state, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)


# The transformer block, in two halves around the attention a caller
# supplies: `_layer` below (the contiguous cache) and every paged step
# program of serving/engine_model.py are written with them, so a named
# scope, a fusion boundary or the next architecture's change to the
# block is written once. The named scopes are metadata only: they name
# the matmuls in a profile's op metadata and change no compiled program
# and no compile-cache key.


def project_qkv(cfg: LlamaConfig, h, w, positions, direct=False):
    """The block up to its attention: q, k, v of the normed stream `h`
    as [B, heads, S, Hd], q and k rotated. `direct` (the caller's
    choice: engine_model.direct_qkv) holds each matmul's result behind
    an optimization barrier, so that XLA cannot fuse the head split
    into the dot; same values, another fusion boundary."""
    B, S, _ = h.shape
    H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hold = jax.lax.optimization_barrier if direct else (lambda y: y)
    with jax.named_scope("attn.qkv"):
        q = hold(mm(h, w["wq"])).reshape(B, S, H, Hd).transpose(0, 2, 1, 3)
        k = hold(mm(h, w["wk"])).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
        v = hold(mm(h, w["wv"])).reshape(B, S, KH, Hd).transpose(0, 2, 1, 3)
    return (rope(q, positions, cfg.rope_theta, cfg.rope_scaling),
            rope(k, positions, cfg.rope_theta, cfg.rope_scaling), v)


def finish_block(cfg: LlamaConfig, x, out, w):
    """The block from its attention's output `out` [B, H, S, Hd] on:
    the output projection and the feed-forward, each added to the
    stream `x` (add_branch)."""
    x = attn_out(cfg, x, out, w)
    h = rms_norm(x, w["ln2"], cfg.rms_eps).astype(cfg.dtype)
    return add_branch(cfg, x, swiglu(h, w), w, "ln2_post", "mlp.post_norm")


def attn_out(cfg: LlamaConfig, x, out, w):
    """The attention branch's end: heads `out` [B, H, S, Hd] through the
    output projection, added to the stream `x`."""
    B, S, _ = x.shape
    with jax.named_scope("attn.out"):
        return add_branch(
            cfg, x, mm(out.transpose(0, 2, 1, 3).reshape(B, S, -1), w["wo"]),
            w, "ln1_post", "attn.post_norm")


def swiglu(h, w):
    """SwiGLU of the normed stream `h` with w_gate / w_up / w_down: the
    dense feed-forward, and a sparse layer's shared expert."""
    with jax.named_scope("mlp.gate_up"):
        h = jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"])
    with jax.named_scope("mlp.down"):
        return mm(h, w["w_down"])


def _layer(cfg: LlamaConfig, x, w, positions, kv, kv_lengths, attn_lengths,
           causal, q_offset, use_pallas, mesh=None):
    """One transformer block. x [B,S,D]; w: this block's weights. kv:
    (k_cache, v_cache) for this cache row ([B,KH,S_max,Hd]) or None.
    Returns (x_out, new_kv)."""
    B, S, _ = x.shape
    h = rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
    q, k, v = project_qkv(cfg, h, w, positions)

    if kv is None:
        out = attn_ops.attention(q, k, v, causal=causal, lengths=attn_lengths,
                                 use_pallas=use_pallas, mesh=mesh)
        new_kv = (k, v)
    else:
        kc, vc = kv
        # Scatter the S new tokens at [kv_lengths, kv_lengths+S) per batch.
        idx = kv_lengths[:, None] + jnp.arange(S)[None, :]  # [B, S]
        bidx = jnp.arange(B)[:, None]
        kc = kc.at[bidx, :, idx, :].set(k.transpose(0, 2, 1, 3).astype(kc.dtype))
        vc = vc.at[bidx, :, idx, :].set(v.transpose(0, 2, 1, 3).astype(vc.dtype))
        out = attn_ops.attention(q, kc, vc, causal=causal,
                                 lengths=attn_lengths, q_offset=q_offset,
                                 use_pallas=use_pallas, mesh=mesh)
        new_kv = (kc, vc)
    return finish_block(cfg, x, out, w), new_kv


def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S]
    *,
    positions: Optional[jax.Array] = None,  # [B, S] absolute positions
    kv_cache: Optional[KVCache] = None,
    lengths: Optional[jax.Array] = None,  # [B] valid tokens in `tokens`
    use_pallas: Optional[bool] = None,
    mesh=None,  # multi-device: routes kernels through shard_map
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Token ids -> logits. Three modes:

    1. No cache (training / golden tests): full causal attention.
    2. Prefill into cache: pass a fresh KVCache (lengths 0) — k/v are
       written at absolute positions, logits returned for all S.
    3. Decode: S small (usually 1), cache lengths > 0 — new k/v appended,
       attention over the whole cache prefix.
    Returns (logits [B,S,V] float32, updated cache or None).
    """
    B, S = tokens.shape
    if positions is None:
        base = kv_cache.lengths[:, None] if kv_cache is not None else 0
        positions = base + jnp.arange(S)[None, :]
    x = params["tok_emb"][tokens].astype(cfg.residual_dtype)

    if kv_cache is None:
        attn_lengths = lengths if lengths is not None else jnp.full((B,), S, jnp.int32)
        causal, q_offset, kv_lengths = True, None, None
    else:
        new_total = kv_cache.lengths + (lengths if lengths is not None
                                        else jnp.full((B,), S, jnp.int32))
        attn_lengths = new_total
        causal, q_offset, kv_lengths = True, kv_cache.lengths, kv_cache.lengths

    L = cfg.n_layers
    kv_in = (kv_cache.k, kv_cache.v) if kv_cache is not None else None
    # The stacked weights as a tuple in this order: the scan's operands
    # stay in the order they always had (a dict's would be sorted).
    names = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
             "w_down") + (("ln1_post", "ln2_post") if cfg.post_norms else ())
    weights = tuple(params["layers"][n] for n in names)

    def body(x, layer):
        ws, kv = layer
        return _layer(cfg, x, dict(zip(names, ws)), positions, kv,
                      kv_lengths, attn_lengths, causal, q_offset,
                      use_pallas, mesh)

    def run_pass(x, _, row0):  # blocks 0..L-1 over cache rows row0..row0+L-1
        rows = kv_in
        if rows is not None and cfg.n_passes > 1:
            rows = tuple(c[row0:row0 + L] for c in kv_in)
        x, kv_out = jax.lax.scan(body, x, (weights, rows))
        return x, None, kv_out

    x, _, kv_out = walk_passes(cfg, params, x, run_pass)

    x = final_norm(cfg, params, x)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = (x @ params["tok_emb"].T.astype(x.dtype)
                      ).astype(jnp.float32)
        else:
            logits = mm(x, params["lm_head"]).astype(jnp.float32)

    new_cache = None
    if kv_cache is not None:
        new_cache = KVCache(kv_out[0], kv_out[1], attn_lengths)
    return logits, new_cache


def greedy_generate(
    params: Params, cfg: LlamaConfig, prompt: jax.Array, max_new_tokens: int,
    *, eos_id: Optional[int] = None, use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Simple batch greedy decode (tests / offline use; the serving engine
    has its own continuous-batching loop). prompt [B, S] -> [B, S+N]."""
    B, S = prompt.shape
    cache = KVCache.zeros(cfg, B, max_len=S + max_new_tokens)
    logits, cache = forward(params, cfg, prompt, kv_cache=cache,
                            use_pallas=use_pallas)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    done = jnp.zeros((B,), bool) if eos_id is not None else None
    if eos_id is not None:
        done = tok[:, 0] == eos_id

    def step(carry, _):
        cache, tok, done = carry
        logits, cache = forward(params, cfg, tok, kv_cache=cache,
                                use_pallas=use_pallas)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        if eos_id is not None:
            # Static shapes: "stopping" = pinning finished rows to eos.
            nxt = jnp.where(done[:, None], eos_id, nxt)
            done = done | (nxt[:, 0] == eos_id)
        return (cache, nxt, done), nxt

    (_, _, _), toks = jax.lax.scan(step, (cache, tok, done), None,
                                   length=max_new_tokens - 1)
    out = jnp.concatenate([prompt, tok, toks[:, :, 0].T], axis=1)
    return out
