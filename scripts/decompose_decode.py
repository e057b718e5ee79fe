"""Decompose the B=128 int8-KV decode step cost on real TPU.

Times decode_multi_step (K=8) in four variants to attribute the gap
between the measured ~70 ms/iteration and the ~30 ms weight-bandwidth
floor: full path, attention stubbed out, KV-quantize-on-write stubbed,
and both stubbed. Usage: python scripts/decompose_decode.py [B] [mode]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model
from generativeaiexamples_tpu.serving.kv_cache import PagePool

def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    kv = sys.argv[2] if len(sys.argv) > 2 else "int8"
    stub_attn = "--stub-attn" in sys.argv
    stub_quant = "--stub-quant" in sys.argv

    cfg = llama.LlamaConfig.llama3_8b()
    params = llama.init_params_on_device(cfg, quantize=True)
    jax.block_until_ready(params["layers"]["wq"].q)

    ps = 128 if kv == "int8" else 64
    maxp = 384 // ps
    n_pages = B * maxp + 1
    pool = PagePool.zeros(cfg, n_pages, ps, dtype=jnp.dtype(kv))

    if stub_attn:
        # Patch the ENGINE's binding: engine_model imports the dispatch
        # function at module level, so patching the source module
        # (paged_attention) would be a no-op.
        engine_model.paged_attention_dispatch = (
            lambda q, *a, **k: q)  # skip the kernel, keep shapes
    if stub_quant:
        from generativeaiexamples_tpu.serving import paged_attention_int8 as pi

        def fake_quant(x, scale_dtype=jnp.float32):
            return (x.astype(jnp.int8),
                    jnp.ones(x.shape[:-1], scale_dtype))
        # engine_model imports quantize_kv function-locally at trace
        # time, so patching the source module reaches it.
        pi.quantize_kv = fake_quant

    rng = np.random.default_rng(0)
    tables = np.zeros((B, maxp), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    for b in range(B):
        tables[b] = perm[b * maxp:(b + 1) * maxp]
    lengths = np.full((B,), 129, np.int32)
    last = jnp.zeros((B,), jnp.int32)
    key = jax.random.PRNGKey(0)

    def step(last, pool, lengths):
        return engine_model.decode_multi_step(
            params, cfg, pool, last, jnp.asarray(tables),
            jnp.asarray(lengths), jnp.ones((B,), bool),
            jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32), key, 8,
            sampling_flags=(True, False, False))

    block, last, pool = step(last, pool, lengths)
    np.asarray(block)  # compile + real completion
    n = 4
    t0 = time.perf_counter()
    for i in range(n):
        block, last, pool = step(last, pool, lengths + 8 * (i + 1))
        np.asarray(block)
    dt = (time.perf_counter() - t0) / (n * 8) * 1e3
    tag = f"B={B} kv={kv} stub_attn={stub_attn} stub_quant={stub_quant}"
    print(f"[decompose] {tag}: {dt:.2f} ms per decode iteration "
          f"({B / dt * 1e3:.0f} tok/s)")


if __name__ == "__main__":
    main()
