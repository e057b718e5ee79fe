"""Microbenchmarks for the serving hot path on the local accelerator.

Measures, in order:
1. device kind + HBM
2. per-dispatch host overhead (jit identity round-trip)
3. effective weight-read bandwidth: bf16 matmul vs int8-dequant matmul
   at decode shapes ([B, D] x [D, M])
4. prefill_step / decode_multi_step wall time for the bench config

Run WITHOUT JAX_PLATFORMS to hit the TPU. Weights are built on device
(jax.random) so no host->device bulk transfer is involved.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, n=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} platform={dev.platform}", flush=True)
    try:
        ms = dev.memory_stats()
        print(f"  hbm bytes_limit={ms.get('bytes_limit', 0)/2**30:.1f} GiB "
              f"in_use={ms.get('bytes_in_use', 0)/2**30:.2f} GiB", flush=True)
    except Exception as e:
        print(f"  memory_stats unavailable: {e}", flush=True)

    # 2. dispatch overhead
    x = jnp.zeros((16,), jnp.float32)
    f = jax.jit(lambda a: a + 1)
    t = timeit(lambda: f(x), n=50)
    print(f"dispatch overhead (jit add): {t*1e3:.2f} ms", flush=True)
    # with host sync each call
    t0 = time.perf_counter()
    for _ in range(50):
        np.asarray(f(x))
    t = (time.perf_counter() - t0) / 50
    print(f"dispatch + host sync: {t*1e3:.2f} ms", flush=True)

    # 3. matmul bandwidth at decode shapes
    B, D, M = 16, 4096, 14336
    key = jax.random.PRNGKey(0)
    xa = jax.random.normal(key, (B, D), jnp.bfloat16)
    wb = jax.random.normal(key, (D, M), jnp.bfloat16)
    wq = jax.random.randint(key, (D, M), -127, 127, jnp.int8)
    ws = jnp.ones((M,), jnp.float32)

    mm_bf16 = jax.jit(lambda x, w: x @ w)
    t = timeit(lambda: mm_bf16(xa, wb))
    print(f"bf16 mm [{B},{D}]x[{D},{M}]: {t*1e3:.3f} ms "
          f"({D*M*2/t/2**30:.0f} GiB/s weight read)", flush=True)

    mm_i8 = jax.jit(lambda x, q, s: (x @ q.astype(x.dtype)) * s.astype(x.dtype))
    t = timeit(lambda: mm_i8(xa, wq, ws))
    print(f"int8-dequant mm: {t*1e3:.3f} ms "
          f"({D*M/t/2**30:.0f} GiB/s int8 read)", flush=True)

    # int8 with f32 accumulation via preferred_element_type on int8 inputs
    mm_i8b = jax.jit(lambda x, q, s: jax.lax.dot_general(
        x, q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * s)
    try:
        t = timeit(lambda: mm_i8b(xa, wq, ws))
        print(f"int8 dot_general(bf16,int8)->f32: {t*1e3:.3f} ms "
              f"({D*M/t/2**30:.0f} GiB/s)", flush=True)
    except Exception as e:
        print(f"mixed dot_general failed: {e}", flush=True)

    # a full stacked-layer sweep: read every layer's w once (scan) to see
    # sustained bandwidth over 8 GB
    L = 8
    wq_l = jax.random.randint(key, (L, D, M), -127, 127, jnp.int8)
    ws_l = jnp.ones((L, M), jnp.float32)

    @jax.jit
    def sweep(x, q, s):
        def body(x, layer):
            qq, ss = layer
            y = (x @ qq.astype(x.dtype)) * ss.astype(x.dtype)
            return x + y[:, :D], None

        x, _ = jax.lax.scan(body, x, (q, s))
        return x

    t = timeit(lambda: sweep(xa, wq_l, ws_l), n=10)
    gb = L * D * M / 2**30
    print(f"scan over {L} int8 layers ({gb:.1f} GiB): {t*1e3:.2f} ms "
          f"({gb/t:.0f} GiB/s sustained)", flush=True)

    # 4. engine steps at bench geometry
    if "--engine" in sys.argv:
        from generativeaiexamples_tpu.config.schema import EngineConfig
        from generativeaiexamples_tpu.models import llama
        from generativeaiexamples_tpu.serving import engine_model
        from generativeaiexamples_tpu.serving.kv_cache import (
            PageAllocator, PagePool, SequencePages)
        cfg = llama.LlamaConfig.llama3_8b()
        t0 = time.perf_counter()
        params = llama.init_params_on_device(cfg, quantize=True)
        jax.block_until_ready(params["layers"]["wq"].q)
        print(f"params on device in {time.perf_counter()-t0:.1f}s", flush=True)

        batch, prompt_len, gen, page = 16, 128, 128, 64
        max_seq = prompt_len + gen + page
        max_pages = max_seq // page
        n_pages = batch * max_pages + 1
        pool = PagePool.zeros(cfg, n_pages, page)
        alloc = PageAllocator(n_pages)

        toks = jnp.zeros((1, prompt_len), jnp.int32)
        seq = SequencePages(alloc, page, max_pages)
        seq.ensure(prompt_len)
        row = np.zeros((prompt_len // page,), np.int32)
        row[: len(seq.pages)] = seq.pages

        t0 = time.perf_counter()
        logits, pool = engine_model.prefill_step(
            params, cfg, pool, toks, jnp.int32(prompt_len), jnp.asarray(row))
        jax.block_until_ready(logits)
        print(f"prefill compile+run: {time.perf_counter()-t0:.1f}s", flush=True)

        def run_prefill():
            nonlocal pool
            logits, pool = engine_model.prefill_step(
                params, cfg, pool, toks, jnp.int32(prompt_len),
                jnp.asarray(row))
            return logits

        t = timeit(run_prefill, n=5, warmup=1)
        print(f"prefill_step S={prompt_len}: {t*1e3:.1f} ms", flush=True)

        tokens = jnp.zeros((batch,), jnp.int32)
        tables = jnp.tile(jnp.arange(max_pages, dtype=jnp.int32)[None],
                          (batch, 1))
        lengths = jnp.full((batch,), prompt_len + 1, jnp.int32)
        active = jnp.ones((batch,), bool)
        temps = jnp.zeros((batch,), jnp.float32)
        top_ps = jnp.ones((batch,), jnp.float32)
        top_ks = jnp.zeros((batch,), jnp.int32)
        rng = jax.random.PRNGKey(0)

        for K in (8, 16, 32):
            t0 = time.perf_counter()

            def run_decode(K=K):
                nonlocal pool, tokens
                out, tokens, pool = engine_model.decode_multi_step(
                    params, cfg, pool, tokens, tables, lengths, active,
                    temps, top_ps, top_ks, rng, K, None,
                    sampling_flags=(True, False, False))
                return out

            out = run_decode()
            jax.block_until_ready(out)
            print(f"decode K={K} compile+run: {time.perf_counter()-t0:.1f}s",
                  flush=True)
            t = timeit(run_decode, n=5, warmup=1)
            print(f"decode_multi_step K={K} B={batch}: {t*1e3:.1f} ms "
                  f"-> {batch*K/t:.0f} tok/s", flush=True)


if __name__ == "__main__":
    main()
