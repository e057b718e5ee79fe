"""Weight-only int8 quantization.

Why: a v5e chip has 16 GB HBM; llama3-8b in bf16 is ~16 GB of weights
alone. Per-output-channel int8 (scale = amax/127 over the input dim)
halves weight HBM and roughly doubles decode throughput (decode is
weight-bandwidth-bound). The reference gets this from TRT-LLM's
quantized engines inside NIM; here it's a pytree transform.

`QuantizedTensor` is a pytree node, so quantized params flow through
lax.scan stacking, jit, and device_put exactly like plain arrays, and
`mm(x, w)` dispatches on leaf type — model code never branches.
Where the matmul's result is consumed as it is (`wo`, `w_gate`, `w_up`,
`w_down`: four projections of a block's seven), XLA fuses the
int8->bf16 convert + scale into the matmul's weight read: one
`convolution(bf16 x, s8 w)` that streams the codes from HBM. Where the
consumer is a head split (`wq`, `wk`, `wv` in a decode step), XLA fuses
THAT into the dot instead and first stages the weight in VMEM in
another layout, once a block and layer; serving/engine_model.py
(`direct_qkv`) says where that pays and where the caller keeps the
consumer out (PERF.md, PR 30).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class QuantizedTensor:
    q: jax.Array  # int8, same shape as the original weight
    s: jax.Array  # float32 scale, shape = original shape minus the reduced axis

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


jax.tree_util.register_dataclass(
    QuantizedTensor, data_fields=["q", "s"], meta_fields=[]
)


def quantize_tensor(w: jax.Array, contract_axis: int = -2) -> QuantizedTensor:
    """Per-output-channel symmetric int8. For y = x @ w ([in, out]), the
    contraction axis is -2; scales are per-out-column."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=contract_axis, keepdims=True)
    s = (amax / 127.0).clip(1e-8)
    q = jnp.round(wf / s).clip(-127, 127).astype(jnp.int8)
    return QuantizedTensor(q, jnp.squeeze(s, axis=contract_axis))


def mm(x: jax.Array, w) -> jax.Array:
    """x @ w where w is a plain array or a QuantizedTensor."""
    if isinstance(w, QuantizedTensor):
        y = x @ w.q.astype(x.dtype)
        return y * w.s.astype(x.dtype)
    return x @ w


# Weight names quantized in the llama param tree. Embedding stays bf16
# (it's a lookup, not a matmul); norms are vectors.
LLAMA_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_llama_params(params: dict) -> dict:
    """bf16 llama pytree -> weight-only int8 pytree (layers stacked:
    contraction axis is -2 because of the leading layer axis)."""
    out = dict(params)
    out["layers"] = {
        k: (quantize_tensor(v, contract_axis=-2) if k in LLAMA_QUANT_KEYS else v)
        for k, v in params["layers"].items()
    }
    if "lm_head" in params:
        out["lm_head"] = quantize_tensor(params["lm_head"], contract_axis=-2)
    return out
