#!/usr/bin/env python3
"""A looped configuration of the benchmark at its published widths, on
the chip, through the step programs the benchmark times:

    chiprun -- python3 scripts/check_looped_on_chip.py [--config NAME] [--seeds 3]

logits: a 128-token seeded prompt through `prefill_batch_step` (the
cell's group of 4, one real row) and `prefill_step`, then 64 tokens
through `decode_multi_step` (greedy, blocks of 8) and the int8 cache; the
same 64 positions replayed through `decode_step` (the same
`_decode_once`, which returns logits) and compared with
`reference_logits` of the whole 192-token sequence: the largest
|difference| over the largest |reference logit|, position by position.
The same comparison for two programs of LOWER precision than the
configuration states (activations rounded to float8 after every block;
cache scales rounded to bfloat16), which a tolerance has to refuse.

step: the decode program's compile time, memory and time a step at the
cell's shape (32 slots, contexts around the mix's mean).

One JSON object per line on stdout; never a measurement on the CPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def say(**kw):
    print(json.dumps(kw), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ouro-2.6b-int8")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--skip-step", action="store_true")
    ap.add_argument("--block", type=int, default=None,
                    help="steps a dispatch of the timed decode program "
                         "(default: the configuration's)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the same control flow on the CPU at the tests' "
                         "tiny size: never a measurement")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import architectures
    from benchmark.harness import system
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import PagePool
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    dev = jax.devices()[0]
    if args.rehearse:
        from benchmark.tests.test_ouro import TINY_OURO as config
    elif dev.platform != "tpu":
        raise SystemExit("check_looped_on_chip: no TPU; refusing")
    else:
        setup_compile_cache()
        with open(os.path.join("benchmark", "configs",
                               args.config + ".json")) as fh:
            config = json.load(fh)
    entry = architectures.load(config)
    mcfg = entry.model_config(config)
    ecfg = system.engine_config(config)
    ps, B = ecfg.page_size, ecfg.max_batch_size
    maxp = ecfg.max_seq_len // ps
    n_pages = config["serving"]["n_pages"]
    K = ecfg.decode_steps_per_dispatch
    greedy = (True, False, False)
    say(device=dev.device_kind, rows=mcfg.cache_rows, passes=mcfg.n_passes,
        slots=B, pages=n_pages, block=K)

    def fresh_pool():
        return PagePool.zeros(mcfg, n_pages, ps,
                              dtype=jnp.dtype(ecfg.kv_dtype))

    def zeros(n, dt=jnp.float32):
        return jnp.zeros((n,), dt)

    P, NEW = (128, 64) if not args.rehearse else (32, 2 * K)

    def serve(params, ids_prompt, decode_step=em.decode_step,
              after_prefill=lambda pool: pool):
        """-> (served tokens [NEW] from decode_multi_step, prefill logits
        [V], replayed logits [NEW, V])."""
        N = ecfg.max_prefill_group
        toks = np.zeros((N, P), np.int32)
        toks[0] = ids_prompt
        lengths = np.ones((N,), np.int32)
        lengths[0] = P
        rows = np.zeros((N, P // ps), np.int32)
        rows[0] = 1 + np.arange(P // ps)
        table = np.zeros((B, maxp), np.int32)
        table[0] = 1 + np.arange(maxp)
        key = jax.random.PRNGKey(0)

        def prefilled():
            first, pool = em.prefill_batch_step(
                params, mcfg, fresh_pool(), jnp.asarray(toks),
                jnp.asarray(lengths), jnp.asarray(rows), zeros(N), zeros(N),
                zeros(N, jnp.int32), key, None, sampling_flags=greedy)
            return int(first[0]), pool

        # the served path: decode_multi_step, greedy, device-chained
        first, pool = prefilled()
        active = np.zeros((B,), bool)
        active[0] = True
        ln = np.ones((B,), np.int32)
        ln[0] = P + 1
        last = jnp.zeros((B,), jnp.int32).at[0].set(first)
        served = [first]
        for _ in range(NEW // K):
            block, last, pool = em.decode_multi_step(
                params, mcfg, pool, last, jnp.asarray(table), jnp.asarray(ln),
                jnp.asarray(active), zeros(B), zeros(B), zeros(B, jnp.int32),
                key, K, None, sampling_flags=greedy)
            served += [int(t) for t in np.asarray(block)[0, 1:]]
            ln[0] += K
        del pool
        # the single-sequence prefill's logits at the prompt's end
        pre_logits, pool = em.prefill_step(
            params, mcfg, fresh_pool(), jnp.asarray(toks[:1]), jnp.int32(P),
            jnp.asarray(rows[0]), None)
        del pool
        # the replay: the same positions through decode_step, for logits
        _, pool = prefilled()
        pool = after_prefill(pool)
        out = []
        for i in range(NEW):
            cur = np.zeros((B,), np.int32)
            cur[0] = served[i]
            ln = np.ones((B,), np.int32)
            ln[0] = P + 1 + i
            logits, pool = decode_step(params, mcfg, pool, jnp.asarray(cur),
                                       jnp.asarray(table), jnp.asarray(ln),
                                       None)
            out.append(np.asarray(logits[0]))
        del pool
        return served, np.asarray(pre_logits), np.stack(out)

    def compare(name, seed, params, ids_prompt, ref_cache, **kw):
        served, pre, dec = serve(params, ids_prompt, **kw)
        seq = list(ids_prompt) + served[:NEW]
        key = tuple(seq)
        if key not in ref_cache:
            ref_cache.clear()
            ref_cache[key] = np.asarray(entry.reference_logits(
                config, params, np.asarray(seq, np.int32)))
        ref = ref_cache[key]
        top = float(np.abs(ref).max())
        worst_pre = float(np.abs(pre - ref[P - 1]).max()) / top
        per_pos = np.abs(dec - ref[P:P + NEW]).max(axis=1) / top
        # what run.py's check compares: the served token's shortfall
        short = [float((ref[P - 1 + i].max() - ref[P - 1 + i, t])
                       / abs(ref[P - 1 + i].max()))
                 for i, t in enumerate(served[:NEW])]
        say(check=name, seed=seed, largest_ref_logit=top,
            prefill_rel=worst_pre, decode_rel_max=float(per_pos.max()),
            decode_rel_mean=float(per_pos.mean()),
            decode_rel_first8=float(per_pos[:8].max()),
            decode_rel_last8=float(per_pos[-8:].max()),
            served_shortfall_max=max(short))
        return float(max(worst_pre, per_pos.max()))

    # -- lower precisions than the configuration states -------------------
    # (`reduce_precision`, not a pair of casts: XLA drops a cast to a
    # narrower type and back as excess precision it is allowed to keep)
    def to_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    # models/llama.py's block half, under the name the step programs call
    real_finish = em.finish_block

    def fp8_finish(cfg, x, out, w):
        y = real_finish(cfg, x, out, w)
        return jax.lax.reduce_precision(y, exponent_bits=4, mantissa_bits=3)

    from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8
    real_quantize = pa8.quantize_kv

    def bf16_scale_quantize(x, scale_dtype=jnp.float32):
        q, s = real_quantize(x, scale_dtype=scale_dtype)
        return q, to_bf16(s)

    def lowered_decode(patch):
        """decode_step with `patch` on while it is traced (once)."""
        jitted = jax.jit(
            lambda p, pool, t, tb, ln: em._decode_once(
                p, mcfg, pool, t, tb, ln, None), donate_argnums=(1,))

        def step(params, cfg, pool, tokens, tables, lengths, use_pallas):
            patch(True)
            try:
                return jitted(params, pool, tokens, tables, lengths)
            finally:
                patch(False)
        return step

    @functools.partial(jax.jit, donate_argnums=0)
    def bf16_scales(pool):  # the prompt's scales too, not only new tokens'
        return type(pool)(pool.kv, to_bf16(pool.s), pool.page_size)

    def patch_fp8(on):
        em.finish_block = fp8_finish if on else real_finish

    def patch_scales(on):
        pa8.quantize_kv = bf16_scale_quantize if on else real_quantize

    readings = {"served": [], "fp8_activations": [], "bf16_cache_scales": []}
    params = None
    for s in range(args.seeds):
        seed = 2**31 + 1009 * s + 17
        params, _ = entry.init_params(config, mcfg, seed, [dev])
        rng = np.random.default_rng(seed)
        ids_prompt = rng.integers(1, mcfg.vocab_size, P).astype(np.int32)
        cache = {}
        readings["served"].append(
            compare("served", seed, params, ids_prompt, cache))
        if s == 0:
            for name, patch, after in (
                    ("fp8_activations", patch_fp8, lambda pool: pool),
                    ("bf16_cache_scales", patch_scales, bf16_scales)):
                # teacher-forced with the SERVED tokens: the served path
                # is recomputed as it is, the replay is the lowered one
                readings[name].append(compare(
                    name, seed, params, ids_prompt, cache,
                    decode_step=lowered_decode(patch), after_prefill=after))
        if s < args.seeds - 1:
            del params
    say(readings=readings)

    if args.skip_step:
        return 0
    if params is None:
        params, _ = entry.init_params(config, mcfg, 1, [dev])
    K = args.block or K
    # -- the decode program at the cell's shape ----------------------------
    rng = np.random.default_rng(7)
    # contexts around the mix's mean of 208
    ctx = rng.integers(64, 352, B) if not args.rehearse \
        else rng.integers(8, 40, B)
    table = np.zeros((B, maxp), np.int32)
    nxt = 1
    for b in range(B):
        need = -(-(int(ctx[b]) + 4 * K) // ps)
        table[b, :need] = np.arange(nxt, nxt + need)
        nxt += need
    assert nxt <= n_pages, (nxt, n_pages)
    pool = fresh_pool()
    argv = lambda ln: (  # noqa: E731
        params, mcfg, pool, jnp.zeros((B,), jnp.int32), jnp.asarray(table),
        jnp.asarray(ln), jnp.ones((B,), bool), zeros(B), zeros(B),
        zeros(B, jnp.int32), jax.random.PRNGKey(1), K, None)
    t0 = time.monotonic()
    compiled = em.decode_multi_step.lower(
        *argv(ctx.astype(np.int32)), sampling_flags=greedy).compile()
    compile_s = time.monotonic() - t0
    m = compiled.memory_analysis()
    say(step="compiled", block=K, compile_s=compile_s, temp_gib=m.temp_size_in_bytes / 2**30,
        args_gib=m.argument_size_in_bytes / 2**30)
    ln = ctx.astype(np.int32)
    times = []
    for i in range(4):
        t0 = time.monotonic()
        block, last, pool = em.decode_multi_step(
            *argv(ln), sampling_flags=greedy)
        jax.block_until_ready(block)
        times.append((time.monotonic() - t0) / K * 1e3)
        ln = ln + K
    say(step="timed", step_ms=times, mean_context=float(ctx.mean()),
        peak_gib=(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        / 2**30)
    return 0


if __name__ == "__main__":
    sys.exit(main())
