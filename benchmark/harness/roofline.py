"""Operations and bytes a decoder step needs, from its shapes, and the
least time a chip could take for them. Kept with the benchmark so that
no PR that claims a gain can change the yardstick. No JAX.

Counts are the algorithm's: what must be computed and moved once, not
what an implementation happens to do (padding, recomputation and
re-reads do not count, so they lower the share).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}


def load_peaks(bench_dir: str, device_kind: str) -> Dict[str, float]:
    """The published peaks of `device_kind`; an unknown device is an
    error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                   f"(known: {sorted(table)}); add its row to peaks.json")


def layer_matmul_params(c: Dict[str, Any]) -> int:
    """Weights of one block's seven projections."""
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    kh = int(c["num_key_value_heads"])
    hd = int(c.get("head_dim") or d // h)
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
    hd = int(c.get("head_dim")
             or c["hidden_size"] // c["num_attention_heads"])
    kv = c["serving"]["kv_dtype"]
    per = 2 * kh * hd * _BYTES[kv]
    if kv == "int8":
        per += 2 * kh * 4
    return float(int(c["num_hidden_layers"]) * per)


def decode_step(c: Dict[str, Any], batch: float, context: float,
                chips: int = 1) -> Dict[str, float]:
    """One decode step of `batch` sequences with `context` cached tokens
    each, per chip of a tensor-parallel group of `chips`."""
    wbytes = _BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                    else "bfloat16"]
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    hd = int(c.get("head_dim") or d // h)
    layers = int(c["num_hidden_layers"])
    flops = 2.0 * batch * matmul_params(c)
    flops += 4.0 * batch * context * h * hd * layers  # QK^T and PV
    bytes_ = float(matmul_params(c) * wbytes)
    bytes_ += batch * context * kv_bytes_per_token(c)  # read the cache
    bytes_ += batch * kv_bytes_per_token(c)            # append one token
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill(c: Dict[str, Any], prompt_tokens: float, mean_prompt: float,
            programs: float, chips: int = 1) -> Dict[str, float]:
    """Prefill of `prompt_tokens` tokens in all, in prompts of
    `mean_prompt` tokens, over `programs` executions (each reads the
    weights once)."""
    wbytes = _BYTES["int8" if c["serving"]["quantize_weights"] == "int8"
                    else "bfloat16"]
    d, h = int(c["hidden_size"]), int(c["num_attention_heads"])
    hd = int(c.get("head_dim") or d // h)
    layers = int(c["num_hidden_layers"])
    body = matmul_params(c) - d * int(c["vocab_size"])
    flops = 2.0 * prompt_tokens * body
    flops += 2.0 * prompt_tokens * mean_prompt * h * hd * layers  # causal
    flops += 2.0 * (prompt_tokens / max(mean_prompt, 1.0)) \
        * d * int(c["vocab_size"])  # the head, last position only
    bytes_ = programs * float(matmul_params(c) * wbytes)
    bytes_ += prompt_tokens * kv_bytes_per_token(c)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def least_seconds(work: Dict[str, float], peaks: Dict[str, float],
                  matmul_peak: str = "bf16_tflops") -> Dict[str, Any]:
    """The larger of operations over peak and bytes over peak, and which
    of the two bounds. int8 weights are multiplied in bf16 after a
    convert, so the bf16 peak is the one that applies."""
    t_ops = work["flops"] / (peaks[matmul_peak] * 1e12)
    t_mem = work["bytes"] / (peaks["hbm_gbps"] * 1e9)
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
