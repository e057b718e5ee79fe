"""HF checkpoint -> pytree weight loading.

The reference never loads weights (NIM containers pull them from NGC,
deploy/compose/docker-compose-nim-ms.yaml:86-160 download jobs). Here
weights come straight from HF-format snapshots (safetensors) into the
stacked-layer pytrees of models.llama / models.bert, optionally sharded
onto a mesh during load (per-leaf device_put with the model's
PartitionSpec so no host ever materializes more than one full tensor).

Name mappings are explicit tables — no torch import needed for loading
(safetensors reads straight to numpy); torch only appears in tests that
build golden models.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import bert as bert_lib
from generativeaiexamples_tpu.models import llama as llama_lib


def _stack(sd: Mapping[str, np.ndarray], fmt: str, n_layers: int,
           transpose: bool = False) -> np.ndarray:
    mats = [np.asarray(sd[fmt.format(i)]) for i in range(n_layers)]
    if transpose:
        mats = [m.T for m in mats]
    return np.stack(mats)


def _llama_numpy_tree(
    sd: Mapping[str, np.ndarray], cfg: llama_lib.LlamaConfig
) -> Dict[str, Any]:
    """HF LlamaForCausalLM names -> models.llama pytree (numpy leaves).

    HF linear weights are [out, in]; ours are [in, out] (x @ w), hence the
    transposes. HF's q/k rotary convention (rotate_half) matches
    models.llama.rope, so no permutation is needed.
    """
    L = cfg.n_layers
    p = "model.layers.{}."
    params: Dict[str, Any] = {
        "tok_emb": np.asarray(sd["model.embed_tokens.weight"]),
        "ln_f": np.asarray(sd["model.norm.weight"]),
        "layers": {
            "ln1": _stack(sd, p + "input_layernorm.weight", L),
            "ln2": _stack(sd, p + "post_attention_layernorm.weight", L),
            "wq": _stack(sd, p + "self_attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, p + "self_attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, p + "self_attn.v_proj.weight", L, transpose=True),
            "wo": _stack(sd, p + "self_attn.o_proj.weight", L, transpose=True),
            "w_gate": _stack(sd, p + "mlp.gate_proj.weight", L, transpose=True),
            "w_up": _stack(sd, p + "mlp.up_proj.weight", L, transpose=True),
            "w_down": _stack(sd, p + "mlp.down_proj.weight", L, transpose=True),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = np.asarray(sd["lm_head.weight"]).T
    return params


def llama_params_from_state_dict(
    sd: Mapping[str, np.ndarray], cfg: llama_lib.LlamaConfig, dtype=None
) -> Dict[str, Any]:
    """HF LlamaForCausalLM state dict -> jnp pytree on the default device
    (single-chip / test path; use load_llama(mesh=...) for sharded load)."""
    dtype = dtype or cfg.dtype
    return jax.tree.map(lambda a: jnp.asarray(a, dtype),
                        _llama_numpy_tree(sd, cfg))


def bert_params_from_state_dict(
    sd: Mapping[str, np.ndarray], cfg: bert_lib.BertConfig, dtype=None
) -> Dict[str, Any]:
    """HF BertModel names -> models.bert pytree. Accepts both bare
    ("embeddings...") and prefixed ("bert.embeddings...") name styles."""
    dtype = dtype or cfg.dtype
    if not any(k.startswith("embeddings.") for k in sd):
        sd = {re.sub(r"^bert\.", "", k): v for k, v in sd.items()}
    L = cfg.n_layers
    p = "encoder.layer.{}."
    params: Dict[str, Any] = {
        "tok_emb": np.asarray(sd["embeddings.word_embeddings.weight"]),
        "pos_emb": np.asarray(sd["embeddings.position_embeddings.weight"]),
        "type_emb": np.asarray(sd["embeddings.token_type_embeddings.weight"]),
        "emb_ln": {
            "w": np.asarray(sd["embeddings.LayerNorm.weight"]),
            "b": np.asarray(sd["embeddings.LayerNorm.bias"]),
        },
        "layers": {
            "wq": _stack(sd, p + "attention.self.query.weight", L, transpose=True),
            "bq": _stack(sd, p + "attention.self.query.bias", L),
            "wk": _stack(sd, p + "attention.self.key.weight", L, transpose=True),
            "bk": _stack(sd, p + "attention.self.key.bias", L),
            "wv": _stack(sd, p + "attention.self.value.weight", L, transpose=True),
            "bv": _stack(sd, p + "attention.self.value.bias", L),
            "wo": _stack(sd, p + "attention.output.dense.weight", L, transpose=True),
            "bo": _stack(sd, p + "attention.output.dense.bias", L),
            "ln1_w": _stack(sd, p + "attention.output.LayerNorm.weight", L),
            "ln1_b": _stack(sd, p + "attention.output.LayerNorm.bias", L),
            "w_in": _stack(sd, p + "intermediate.dense.weight", L, transpose=True),
            "b_in": _stack(sd, p + "intermediate.dense.bias", L),
            "w_out": _stack(sd, p + "output.dense.weight", L, transpose=True),
            "b_out": _stack(sd, p + "output.dense.bias", L),
            "ln2_w": _stack(sd, p + "output.LayerNorm.weight", L),
            "ln2_b": _stack(sd, p + "output.LayerNorm.bias", L),
        },
    }
    if cfg.n_labels and "classifier.weight" not in sd:
        raise ValueError(
            f"config requests n_labels={cfg.n_labels} (cross-encoder head) "
            "but checkpoint has no classifier.weight — this is an embedding "
            "checkpoint, not a reranker"
        )
    if cfg.n_labels:
        params["classifier"] = {
            "pool_w": np.asarray(sd["pooler.dense.weight"]).T
            if "pooler.dense.weight" in sd else np.eye(cfg.dim, dtype=np.float32),
            "pool_b": np.asarray(sd.get("pooler.dense.bias", np.zeros(cfg.dim))),
            "w": np.asarray(sd["classifier.weight"]).T,
            "b": np.asarray(sd["classifier.bias"]),
        }
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)


# ---------------------------------------------------------------------------
# Safetensors snapshot reading
# ---------------------------------------------------------------------------


def read_safetensors_dir(path: str) -> Dict[str, np.ndarray]:
    """Read all *.safetensors in an HF snapshot dir into one name->array
    dict (numpy, zero-copy views where possible)."""
    from safetensors import safe_open

    files = sorted(
        os.path.join(path, f) for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    out: Dict[str, np.ndarray] = {}
    for f in files:
        with safe_open(f, framework="numpy") as fh:
            for name in fh.keys():
                out[name] = fh.get_tensor(name)
    return out


# `model_type`s whose tensors carry the HF Llama names `_llama_numpy_tree`
# and `stream_load_llama` read (a config.json without the key is taken
# as one of these, as before).
_LLAMA_NAMED_TYPES = ("llama", "mistral")
# Families this module can configure but not load: looped decoders.
_LOOPED_TYPES = ("ouro",)


def _require_llama_names(path: str) -> None:
    """Refuse a snapshot whose `model_type` this loader holds no
    tensor-name map for, instead of reading it as a Llama: an "ouro"
    snapshot read that way would drop half its norms (their names,
    input_layernorm_2 / post_attention_layernorm_2, are unconfirmed
    here) and silently serve a different model."""
    cfg_file = os.path.join(path, "config.json")
    if not os.path.exists(cfg_file):
        return
    with open(cfg_file) as fh:
        mt = json.load(fh).get("model_type")
    if mt is not None and mt not in _LLAMA_NAMED_TYPES:
        raise ValueError(
            f"{path}: model_type {mt!r} has no tensor-name map in "
            f"models/hf_loader.py (known: {list(_LLAMA_NAMED_TYPES)}); "
            "refusing to load it as a Llama")


def llama_config_from_hf(path: str) -> llama_lib.LlamaConfig:
    """Derive LlamaConfig from an HF snapshot's config.json. A
    `model_type` of "ouro" adds that family's loop: passes from
    `total_ut_steps`, a norm on each branch's output. (Whether the
    snapshot's TENSORS can be read is the loaders' question:
    `_require_llama_names`.)"""
    with open(os.path.join(path, "config.json")) as fh:
        c = json.load(fh)
    if "linear_attn_config" in c:
        raise ValueError(
            f"{path}: model_type {c.get('model_type')!r} has "
            "linear-attention layers (linear_attn_config) beside latent "
            "attention: it is no LlamaConfig; its configuration is "
            "models/linear_attn_moe.py's LinearAttnMoeConfig, and this "
            "loader holds no tensor-name map for it (the convolutions, "
            "A_log, dt_bias, the low-rank gates, the expert stacks)")
    if int(c.get("hc_mult", 1)) > 1:
        raise ValueError(
            f"{path}: model_type {c.get('model_type')!r} has "
            f"{c['hc_mult']} residual streams (hc_mult) mixed around every "
            "branch: it is no LlamaConfig, whose block knows one stream and "
            "one add; the mixing is models/hyper_connections.py's, under "
            "models/latent_moe.py's LatentMoeConfig, and this loader holds "
            "no tensor-name map for it (each branch's phi, biases and "
            "three gains)")
    if "kv_lora_rank" in c:
        raise ValueError(
            f"{path}: model_type {c.get('model_type')!r} has latent "
            "attention (kv_lora_rank): it is no LlamaConfig; its "
            "configuration is models/latent_moe.py's LatentMoeConfig, and "
            "this loader holds no tensor-name map for it")
    if "mamba_n_heads" in c:
        raise ValueError(
            f"{path}: model_type {c.get('model_type')!r} has state-space "
            "layers (mamba_n_heads): it is no LlamaConfig; "
            "its configuration is models/hybrid_ssm.py's HybridSsmConfig, "
            "and this loader holds no tensor-name map for it (in_proj, "
            "conv1d, dt_bias, A_log, D, the expert stacks)")
    if "sa_config" in c:
        raise ValueError(
            f"{path}: model_type {c.get('model_type')!r} has learned "
            "sparse attention (sa_config: an indexer beside the "
            "attention): it is no LlamaConfig; its configuration is "
            "models/sparse_attn_moe.py's SparseAttnMoeConfig, and this "
            "loader holds no tensor-name map for it (q_norm, k_norm, the "
            "indexer's projections, the expert stacks)")
    if "sliding_window_layout" in c:
        raise ValueError(
            f"{path}: model {c.get('model_name') or c.get('model_type')!r} "
            "has window layers beside global ones (sliding_window_layout, "
            "rope_layout) and a router before its attention: it is no "
            "LlamaConfig; its configuration is models/window_attn_moe.py's "
            "WindowAttnMoeConfig, and this loader holds no tensor-name map "
            "for it (the router, the expert stacks)")
    if c.get("model_type") == "afmoe":
        raise ValueError(
            f"{path}: model_type 'afmoe' has gated attention over window "
            "and global layers (layer_types), a norm on both sides of each "
            "branch and sparse experts behind dense layers: it is no "
            "LlamaConfig; its configuration is models/gated_window_moe.py's "
            "GatedWindowMoeConfig, and this loader holds no tensor-name map "
            "for it (the gate's projection, q_norm, k_norm, the four norms, "
            "the router and its bias, the expert stacks)")
    family = {}
    if c.get("model_type") in _LOOPED_TYPES:
        family = dict(n_passes=int(c["total_ut_steps"]), post_norms=True)
    rs = c.get("rope_scaling") or None
    scaling = None
    if rs is not None:
        rope_type = rs.get("rope_type", rs.get("type"))
        if rope_type != "llama3":
            raise ValueError(
                f"unsupported rope_scaling type {rope_type!r} in {path}; "
                "only llama3-style frequency scaling is implemented"
            )
        scaling = llama_lib.RopeScaling(
            factor=float(rs["factor"]),
            low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
            high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
            original_max_position_embeddings=int(
                rs.get("original_max_position_embeddings", 8192)),
        )
    return llama_lib.LlamaConfig(
        vocab_size=c["vocab_size"],
        dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c.get("num_key_value_heads", c["num_attention_heads"]),
        head_dim=c.get("head_dim", c["hidden_size"] // c["num_attention_heads"]),
        mlp_dim=c["intermediate_size"],
        rope_theta=c.get("rope_theta", 10000.0),
        rms_eps=c.get("rms_norm_eps", 1e-5),
        max_seq_len=c.get("max_position_embeddings", 8192),
        tie_embeddings=c.get("tie_word_embeddings", False),
        rope_scaling=scaling,
        **family,
    )


def bert_config_from_hf(path: str, n_labels: int = 0) -> bert_lib.BertConfig:
    with open(os.path.join(path, "config.json")) as fh:
        c = json.load(fh)
    return bert_lib.BertConfig(
        vocab_size=c["vocab_size"],
        dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"],
        mlp_dim=c["intermediate_size"],
        max_position=c.get("max_position_embeddings", 512),
        type_vocab_size=c.get("type_vocab_size", 2),
        ln_eps=c.get("layer_norm_eps", 1e-12),
        n_labels=n_labels,
    )


def load_bert(path: str, cfg: Optional[bert_lib.BertConfig] = None,
              n_labels: int = 0, dtype=None):
    """Load an HF BERT-family snapshot (embedder: n_labels=0; cross-
    encoder reranker: n_labels=1)."""
    cfg = cfg or bert_config_from_hf(path, n_labels=n_labels)
    sd = read_safetensors_dir(path)
    params = bert_params_from_state_dict(sd, cfg, dtype=dtype)
    return params, cfg


def _quantize_numpy_leaf(a: np.ndarray, contract_axis: int = -2):
    """Host-side per-output-channel symmetric int8 (numpy twin of
    ops.quant.quantize_tensor) — quantizing BEFORE device transfer keeps
    peak HBM at the int8 footprint, which is what makes llama3-70b fit
    an 8-chip v5e slice at all (~70 GB int8 over 8x16 GB)."""
    from generativeaiexamples_tpu.ops.quant import QuantizedTensor

    af = a.astype(np.float32)
    amax = np.abs(af).max(axis=contract_axis, keepdims=True).clip(1e-8)
    s = (amax / 127.0).astype(np.float32)
    q = np.clip(np.round(af / s), -127, 127).astype(np.int8)
    return QuantizedTensor(q, np.squeeze(s, axis=contract_axis))


# ---------------------------------------------------------------------------
# Layer-streaming llama load
# ---------------------------------------------------------------------------
# The old path materialized the FULL numpy tree on host before any
# device_put — ~140 GB of host RAM for llama3-70b bf16, per worker. The
# streaming path reads one leaf-layer at a time straight to its
# NamedSharding placement: host peak = one layer tensor, and under a
# multi-process mesh each host reads only its shard slices from the
# safetensors files (row/column ranges via get_slice) wherever the
# quantization scale allows — leaves whose CONTRACTED axis is sharded
# (wo, w_down under TP; any leaf under FSDP) need the full layer on host
# once so the per-output-channel amax matches the unsharded reference
# exactly.

import logging

_LOG = logging.getLogger(__name__)

# leaf -> (HF name format, transpose). HF linears are [out, in]; ours
# [in, out], so a transposed leaf's target axes map to swapped source
# axes when slicing.
_LLAMA_LAYER_LEAVES = {
    "ln1": ("model.layers.{}.input_layernorm.weight", False),
    "ln2": ("model.layers.{}.post_attention_layernorm.weight", False),
    "wq": ("model.layers.{}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{}.self_attn.o_proj.weight", True),
    "w_gate": ("model.layers.{}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{}.mlp.down_proj.weight", True),
}


class _SnapshotReader:
    """Random access over an HF safetensors snapshot: tensor-name ->
    file handle indexed once; reads can be sliced (only the requested
    row/column ranges touch disk) — the primitive that lets each host
    pull just its shard."""

    def __init__(self, path: str):
        from safetensors import safe_open

        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if f.endswith(".safetensors"))
        if not files:
            raise FileNotFoundError(f"no .safetensors files under {path}")
        self._handles = [safe_open(f, framework="numpy") for f in files]
        self._where: Dict[str, Any] = {}
        for h in self._handles:
            for name in h.keys():
                self._where[name] = h

    def shape(self, name: str, transpose: bool) -> tuple:
        s = tuple(self._where[name].get_slice(name).get_shape())
        return tuple(reversed(s)) if transpose else s

    def read(self, name: str, transpose: bool, index=None) -> np.ndarray:
        """Read `name`, optionally only the TARGET-coordinate `index`
        (tuple of slices); transposed leaves swap the slices into
        source coordinates so the disk read itself is partial."""
        h = self._where[name]
        if index is None:
            a = h.get_tensor(name)
        else:
            src = tuple(reversed(index)) if transpose else tuple(index)
            a = h.get_slice(name)[src]
        return a.T if transpose else a


def _slice_shape(shape, index) -> tuple:
    out = []
    for dim, s in zip(shape, index):
        lo = s.start or 0
        hi = dim if s.stop is None else s.stop
        out.append(hi - lo)
    return tuple(out)


def _is_full(s: slice, dim: int) -> bool:
    return (s.start or 0) == 0 and (s.stop is None or s.stop >= dim)


def _unique_shards(sharding, shape):
    """Addressable shards grouped by identical index (replication):
    [(index, [devices])] — each distinct slice is read/built once."""
    groups: Dict[tuple, list] = {}
    index_of: Dict[tuple, tuple] = {}
    for d, idx in sharding.addressable_devices_indices_map(shape).items():
        key = tuple((s.start, s.stop, s.step) for s in idx)
        groups.setdefault(key, []).append(d)
        index_of[key] = idx
    return [(index_of[k], devs) for k, devs in groups.items()]


def _assemble(shape, sharding, np_dtype, fill):
    """Build one (possibly sharded) jax.Array from host shard buffers.
    `fill(buf, index)` populates the buffer for one shard; with no
    sharding the single full buffer lands on the default device."""
    if sharding is None:
        buf = np.empty(shape, np_dtype)
        fill(buf, tuple(slice(None) for _ in shape))
        return jnp.asarray(buf)
    arrays = []
    for idx, devs in _unique_shards(sharding, shape):
        buf = np.empty(_slice_shape(shape, idx), np_dtype)
        fill(buf, idx)
        arrays.extend(jax.device_put(buf, d) for d in devs)
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)


def _stream_plain(reader, names, transpose, shape, sharding, np_dtype,
                  stacked):
    """Plain leaf (no quantization): slice-read each shard directly.
    `names` is one HF tensor name per layer (or a single name for flat
    leaves, where `shape` has no leading layer axis)."""

    def fill(buf, idx):
        if not stacked:
            buf[...] = reader.read(names[0], transpose,
                                   index=idx).astype(np_dtype)
            return
        for j, l in enumerate(range(idx[0].start or 0,
                                    shape[0] if idx[0].stop is None
                                    else idx[0].stop)):
            buf[j] = reader.read(names[l], transpose,
                                 index=idx[1:]).astype(np_dtype)

    return _assemble(shape, sharding, np_dtype, fill)


def _stream_quant(reader, names, transpose, shape, q_sharding, s_sharding,
                  stacked):
    """Int8 leaf: per-layer read -> quantize -> place q (int8) and s
    (f32 per-output-channel scales) shards.

    When the contracted axis (-2) is fully local per shard, reads are
    sliced to the shard's output columns and quantized locally — the
    amax runs over the same full contraction axis, so scales are
    bit-identical to the unsharded reference. A SHARDED contract axis
    (wo/w_down under TP, anything under FSDP) forces one full-layer
    read so the scales stay correct; the slice happens after quantize.
    """
    from generativeaiexamples_tpu.ops.quant import QuantizedTensor

    L = shape[0] if stacked else 1
    s_shape = shape[:-2] + shape[-1:]

    def shards(sharding, shp):
        if sharding is None:
            return [(tuple(slice(None) for _ in shp), [None])]
        return _unique_shards(sharding, shp)

    q_shards = [(idx, devs, np.empty(_slice_shape(shape, idx), np.int8))
                for idx, devs in shards(q_sharding, shape)]
    s_shards = [(idx, devs, np.empty(_slice_shape(s_shape, idx), np.float32))
                for idx, devs in shards(s_sharding, s_shape)]
    need_full = any(not _is_full(idx[-2], shape[-2])
                    for idx, _, _ in q_shards)

    for l in range(L):
        name = names[l if stacked else 0]
        cache: Dict[tuple, QuantizedTensor] = {}

        def qt_for(out_slice):
            key = (out_slice.start, out_slice.stop)
            if key not in cache:
                if need_full:
                    cache[key] = _quantize_numpy_leaf(
                        reader.read(name, transpose))
                else:
                    cache[key] = _quantize_numpy_leaf(reader.read(
                        name, transpose, index=(slice(None), out_slice)))
            return cache[key]

        for idx, _, buf in q_shards:
            li = idx[1:] if stacked else idx
            qt = qt_for(slice(None) if need_full else li[-1])
            part = qt.q[li] if need_full else qt.q
            if stacked:
                buf[l] = part
            else:
                buf[...] = part
        for idx, _, buf in s_shards:
            li = idx[1:] if stacked else idx
            qt = qt_for(slice(None) if need_full else li[-1])
            part = qt.s[li] if need_full else qt.s
            if stacked:
                buf[l] = part
            else:
                buf[...] = part

    def place(shp, shardlist, sharding):
        if sharding is None:
            (_, _, buf), = shardlist
            return jnp.asarray(buf)
        arrays = []
        for idx, devs, buf in shardlist:
            arrays.extend(jax.device_put(buf, d) for d in devs)
        return jax.make_array_from_single_device_arrays(shp, sharding,
                                                        arrays)

    return QuantizedTensor(place(shape, q_shards, q_sharding),
                           place(s_shape, s_shards, s_sharding))


def stream_load_llama(path: str, cfg: llama_lib.LlamaConfig, mesh=None,
                      dtype=None, quantize: bool = False,
                      progress: Optional[Callable[[str, int, int], None]]
                      = None) -> Dict[str, Any]:
    """Layer-streaming HF llama load: leaf by leaf, layer by layer,
    straight to NamedSharding placement. Values are bit-identical to
    the old materialize-then-put path (pinned by
    tests/test_checkpoint_e2e.py); host peak drops from the full tree
    to one leaf's local shard. `progress(leaf, i, total)` fires after
    each placed leaf (default: one log line each)."""
    import ml_dtypes
    from jax.sharding import NamedSharding

    from generativeaiexamples_tpu.ops.quant import LLAMA_QUANT_KEYS

    _require_llama_names(path)
    if cfg.n_passes > 1 or cfg.post_norms:
        raise ValueError(f"{path}: no tensor-name map for a looped or "
                         f"post-normed model (n_passes={cfg.n_passes}, "
                         f"post_norms={cfg.post_norms})")
    dtype = dtype or cfg.dtype
    np_dtype = {jnp.bfloat16: ml_dtypes.bfloat16}.get(dtype, dtype)
    reader = _SnapshotReader(path)
    specs = llama_lib.param_specs(cfg)

    def shardings_for(spec, quantized):
        if mesh is None:
            return None, None
        if not quantized:
            return NamedSharding(mesh, spec), None
        from generativeaiexamples_tpu.serving.sharding import (
            _quantized_leaf_spec)

        qs = _quantized_leaf_spec(spec)
        return NamedSharding(mesh, qs.q), NamedSharding(mesh, qs.s)

    flat = [("tok_emb", ["model.embed_tokens.weight"], False, False, None),
            ("ln_f", ["model.norm.weight"], False, False, None)]
    for leaf, (fmt, transpose) in _LLAMA_LAYER_LEAVES.items():
        names = [fmt.format(i) for i in range(cfg.n_layers)]
        flat.append((leaf, names, transpose,
                     quantize and leaf in LLAMA_QUANT_KEYS, ("layers", leaf)))
    if not cfg.tie_embeddings:
        flat.append(("lm_head", ["lm_head.weight"], True, quantize, None))

    params: Dict[str, Any] = {"layers": {}}
    done_bytes = 0
    for i, (leaf, names, transpose, quantized, where) in enumerate(flat):
        spec = specs["layers"][leaf] if where else specs[leaf]
        layer_shape = reader.shape(names[0], transpose)
        shape = ((cfg.n_layers,) + layer_shape if where else layer_shape)
        q_sh, s_sh = shardings_for(spec, quantized)
        if quantized:
            val = _stream_quant(reader, names, transpose, shape, q_sh, s_sh,
                                stacked=where is not None)
            done_bytes += val.q.nbytes + val.s.nbytes
        else:
            val = _stream_plain(reader, names, transpose, shape, q_sh,
                                np_dtype, stacked=where is not None)
            done_bytes += val.nbytes
        if where:
            params["layers"][leaf] = val
        else:
            params[leaf] = val
        if progress is not None:
            progress(leaf, i + 1, len(flat))
        else:
            _LOG.info("stream-load %s: leaf %d/%d (%s, %s global bytes "
                      "placed so far)", os.path.basename(path.rstrip("/")),
                      i + 1, len(flat), leaf, f"{done_bytes:,}")
    return params


def load_llama(path: str, cfg: Optional[llama_lib.LlamaConfig] = None,
               mesh=None, dtype=None, quantize: bool = False,
               progress=None):
    """Load an HF llama snapshot via the layer-streaming path; if `mesh`
    is given, each leaf goes straight to its TP/FSDP PartitionSpec
    placement as it is read — required for models larger than one
    device's HBM (llama3-70b on v5e). With `quantize`, weights are
    int8-quantized on host per layer BEFORE transfer, so neither host
    RAM nor per-chip HBM ever exceeds one layer + the quantized
    footprint."""
    cfg = cfg or llama_config_from_hf(path)
    dtype = dtype or cfg.dtype
    params = stream_load_llama(path, cfg, mesh=mesh, dtype=dtype,
                               quantize=quantize, progress=progress)
    return params, cfg
