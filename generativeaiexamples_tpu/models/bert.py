"""BERT-class encoder in pure JAX: embedder + cross-encoder reranker.

TPU-native replacement for the reference's two NeMo Retriever Triton
microservices (deploy/compose/docker-compose-nim-ms.yaml:24-57 embedding
`NV-Embed-QA`≙snowflake-arctic-embed-l per compose.env:24-28, and :59-84
reranking `nv-rerank-qa-mistral-4b`). One encoder implementation serves
both roles:

- embedder: CLS pooling + L2 normalize -> dense retrieval vector
  (arctic-embed's recipe);
- cross-encoder: [CLS] query [SEP] passage [SEP] through the encoder,
  CLS -> linear -> relevance score (the reranker).

Same structural idioms as models.llama: stacked layers + lax.scan,
pluggable attention (bidirectional here), PartitionSpec pytree for TP.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops import attention as attn_ops
from generativeaiexamples_tpu.parallel.mesh import LLM_RULES, logical_to_spec

Params = Dict[str, Any]


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    mlp_dim: int = 4096
    max_position: int = 512
    type_vocab_size: int = 2
    ln_eps: float = 1e-12
    pooling: str = "cls"  # cls | mean
    normalize: bool = True
    n_labels: int = 0  # >0 adds a cross-encoder classification head
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def arctic_embed_l() -> "BertConfig":
        return BertConfig()  # BERT-large geometry, CLS pooling, normalized

    @staticmethod
    def reranker_base() -> "BertConfig":
        """Cross-encoder reranker (ms-marco-MiniLM-class geometry scaled to
        BERT-base; weight loader accepts any HF BERT cross-encoder)."""
        return BertConfig(dim=768, n_layers=12, n_heads=12, mlp_dim=3072,
                          pooling="cls", normalize=False, n_labels=1)

    @staticmethod
    def tiny(vocab_size: int = 128) -> "BertConfig":
        return BertConfig(vocab_size=vocab_size, dim=32, n_layers=2,
                          n_heads=2, mlp_dim=64, max_position=64)


def init_params(cfg: BertConfig, key: jax.Array) -> Params:
    k = jax.random.split(key, 10)
    D, M, L = cfg.dim, cfg.mlp_dim, cfg.n_layers

    def norm(key, *shape, scale=0.02):
        return (jax.random.normal(key, shape) * scale).astype(cfg.dtype)

    params: Params = {
        "tok_emb": norm(k[0], cfg.vocab_size, D),
        "pos_emb": norm(k[1], cfg.max_position, D),
        "type_emb": norm(k[2], cfg.type_vocab_size, D),
        "emb_ln": {"w": jnp.ones((D,), cfg.dtype), "b": jnp.zeros((D,), cfg.dtype)},
        "layers": {
            "wq": norm(k[3], L, D, D), "bq": jnp.zeros((L, D), cfg.dtype),
            "wk": norm(k[4], L, D, D), "bk": jnp.zeros((L, D), cfg.dtype),
            "wv": norm(k[5], L, D, D), "bv": jnp.zeros((L, D), cfg.dtype),
            "wo": norm(k[6], L, D, D), "bo": jnp.zeros((L, D), cfg.dtype),
            "ln1_w": jnp.ones((L, D), cfg.dtype), "ln1_b": jnp.zeros((L, D), cfg.dtype),
            "w_in": norm(k[7], L, D, M), "b_in": jnp.zeros((L, M), cfg.dtype),
            "w_out": norm(k[8], L, M, D), "b_out": jnp.zeros((L, D), cfg.dtype),
            "ln2_w": jnp.ones((L, D), cfg.dtype), "ln2_b": jnp.zeros((L, D), cfg.dtype),
        },
    }
    if cfg.n_labels:
        params["classifier"] = {
            "pool_w": norm(k[9], D, D), "pool_b": jnp.zeros((D,), cfg.dtype),
            "w": norm(k[9], D, cfg.n_labels), "b": jnp.zeros((cfg.n_labels,), cfg.dtype),
        }
    return params


def param_specs(cfg: BertConfig, rules: dict = LLM_RULES) -> Params:
    ls = lambda *ax: logical_to_spec(ax, rules)  # noqa: E731
    specs: Params = {
        "tok_emb": ls("vocab", "embed_fsdp"),
        "pos_emb": ls(None, "embed_fsdp"),
        "type_emb": ls(None, "embed_fsdp"),
        "emb_ln": {"w": ls(None), "b": ls(None)},
        "layers": {
            "wq": ls("layers", "embed_fsdp", "heads"), "bq": ls("layers", "heads"),
            "wk": ls("layers", "embed_fsdp", "heads"), "bk": ls("layers", "heads"),
            "wv": ls("layers", "embed_fsdp", "heads"), "bv": ls("layers", "heads"),
            "wo": ls("layers", "heads", "embed_fsdp"), "bo": ls("layers", None),
            "ln1_w": ls("layers", None), "ln1_b": ls("layers", None),
            "w_in": ls("layers", "embed_fsdp", "mlp"), "b_in": ls("layers", "mlp"),
            "w_out": ls("layers", "mlp", "embed_fsdp"), "b_out": ls("layers", None),
            "ln2_w": ls("layers", None), "ln2_b": ls("layers", None),
        },
    }
    if cfg.n_labels:
        specs["classifier"] = {
            "pool_w": ls("embed_fsdp", None), "pool_b": ls(None),
            "w": ls("embed_fsdp", None), "b": ls(None),
        }
    return specs


def fuse_qkv_params(params: Params) -> Params:
    """One-time QKV weight fusion: replace wq/wk/wv (and biases) with
    the concatenated [L, D, 3D] wqkv forward() projects with. Engines
    call this at init so the fusion is not a per-forward HBM transient
    (~150 MB for BERT-large bf16). Idempotent; loaders/checkpoints keep
    the split layout."""
    lw = params["layers"]
    if "wqkv" in lw:
        return params
    import jax.numpy as jnp

    fused = {k_: v_ for k_, v_ in lw.items()
             if k_ not in ("wq", "wk", "wv", "bq", "bk", "bv")}
    fused["wqkv"] = jnp.concatenate([lw["wq"], lw["wk"], lw["wv"]], axis=-1)
    fused["bqkv"] = jnp.concatenate([lw["bq"], lw["bk"], lw["bv"]], axis=-1)
    return {**params, "layers": fused}


def layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def forward(
    params: Params,
    cfg: BertConfig,
    tokens: jax.Array,  # [B, S]
    *,
    lengths: Optional[jax.Array] = None,  # [B] valid tokens (padding mask)
    token_types: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,  # Pallas interpret mode (CPU tests)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden [B,S,D], pooled [B,D] or scores [B,n_labels])."""
    B, S = tokens.shape
    H, Hd = cfg.n_heads, cfg.head_dim
    if token_types is None:
        token_types = jnp.zeros_like(tokens)
    x = (params["tok_emb"][tokens] + params["pos_emb"][jnp.arange(S)][None]
         + params["type_emb"][token_types])
    x = layer_norm(x, params["emb_ln"]["w"], params["emb_ln"]["b"], cfg.ln_eps)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)

    # Fused QKV projection: one [D, 3D] matmul per layer instead of
    # three [D, D] — fewer, larger MXU ops. Engines pre-fuse at init
    # (fuse_qkv_params) so the concat is a one-time cost; a raw param
    # tree is fused here per forward (outside the scan — inside it,
    # XLA re-materializes the concat every layer; measured on-chip in
    # scripts/decompose_bert_forward.py). Attention at S <= 512 runs
    # the dedicated grouped-heads encoder kernel
    # (ops/encoder_attention.py) — the flash kernel's per-(b, h,
    # block) grid overhead dominated at these shapes (the r3
    # paged-kernel DMA-issue floor class; full forward 422 -> ~180 ms
    # at arctic B=32 across the kernel iterations).
    lw = params["layers"]
    if "wqkv" in lw:
        wqkv, bqkv = lw["wqkv"], lw["bqkv"]
    else:
        wqkv = jnp.concatenate([lw["wq"], lw["wk"], lw["wv"]], axis=-1)
        bqkv = jnp.concatenate([lw["bq"], lw["bk"], lw["bv"]], axis=-1)

    resolved_pallas = attn_ops.on_tpu() if use_pallas is None else use_pallas

    # The named scopes below are metadata only: they name the weight
    # matmuls in a profile's op metadata and change no compiled program.
    def body(x, w):
        h = attn_in = x
        with jax.named_scope("attn.qkv"):
            qkv = (h @ w["wqkv"] + w["bqkv"]).reshape(B, S, 3, H, Hd)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        if resolved_pallas and S <= 512:
            # Dedicated encoder kernel: ONE grid step per batch row
            # (heads looped inside) — the flash kernel's per-(b,h,
            # block) grid overhead dominated at these shapes.
            from generativeaiexamples_tpu.ops.encoder_attention import (
                encoder_attention)

            out = encoder_attention(q, k, v, lengths, interpret=interpret)
        else:
            out = attn_ops.attention(q, k, v, causal=False,
                                     lengths=lengths,
                                     use_pallas=use_pallas,
                                     interpret=interpret,
                                     block_q=min(S, 512),
                                     block_k=min(S, 512))
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * Hd)
        with jax.named_scope("attn.out"):
            attn = attn_in + out @ w["wo"] + w["bo"]
        x = layer_norm(attn, w["ln1_w"], w["ln1_b"], cfg.ln_eps)
        with jax.named_scope("mlp.in"):
            h = jax.nn.gelu(x @ w["w_in"] + w["b_in"], approximate=False)
        with jax.named_scope("mlp.out"):
            mlp = x + h @ w["w_out"] + w["b_out"]
        x = layer_norm(mlp, w["ln2_w"], w["ln2_b"], cfg.ln_eps)
        return x, None

    xs = {"wqkv": wqkv, "bqkv": bqkv,
          **{k_: v_ for k_, v_ in lw.items()
             if k_ not in ("wq", "wk", "wv", "bq", "bk", "bv")}}
    x, _ = jax.lax.scan(body, x, xs)

    mask = (jnp.arange(S)[None, :] < lengths[:, None]).astype(x.dtype)
    if cfg.pooling == "mean":
        pooled = (x * mask[..., None]).sum(1) / jnp.maximum(
            mask.sum(1, keepdims=True), 1.0)
    else:
        pooled = x[:, 0]
    if cfg.n_labels:
        c = params["classifier"]
        pooled = jnp.tanh(pooled @ c["pool_w"] + c["pool_b"])
        return x, pooled @ c["w"] + c["b"]
    if cfg.normalize:
        pooled = pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True).clip(1e-12)
    return x, pooled
