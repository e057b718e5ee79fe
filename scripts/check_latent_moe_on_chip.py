#!/usr/bin/env python3
"""A latent-attention configuration with sparse experts at its published
widths, on the chip, through the step programs the benchmark times:

    chiprun -- python3 scripts/check_latent_moe_on_chip.py [--config NAME] [--seeds 3]

logits: a seeded prompt through `prefill_batch_step` (the cell's group of
4, one real row) and `prefill_step`, then NEW tokens through
`decode_multi_step` (greedy, blocks of 8) and the latent pool; the same
positions replayed through `served_latent.decode_once` (the body of
`decode_step` and `decode_multi_step`, which returns logits and the
router's choices) and compared with the plain reference's ONE forward
pass of the whole sequence (`benchmark/architectures/axk1.py`, computed
piece by piece so that it fits beside the model):

- `rel`: the largest |difference| of logits over the largest |reference
  logit|, over the prefill's position and every decoded one, is held
  under REL_TOL;
- `agree`: the share of (token, expert layer) top-k SETS on which program
  and reference agree is held above AGREE_MIN. Routing is discrete: the
  8th and 9th of 192 scores often lie within a bf16 rounding of each
  other, the tie then falls the other way in a bf16 program than in the
  float32 reference, and the output steps by an expert (which is why the
  seeded routed experts are drawn at a quarter gain:
  latent_moe.ROUTED_INIT_GAIN).

The same comparison for two programs of LOWER precision than the
configuration states, which it must refuse: activations rounded to
float8 after every block, and a latent pool that keeps float8 rows. A
third, a router whose scores are rounded to bfloat16, is READ and not
judged: the chip shows it inside the bf16 stream's own noise (PERF.md,
PR 33).

step: the decode program's compile time, memory and time a step at the
cell's shape (all slots live, contexts around the mix's mean).

One JSON object per line on stdout; never a measurement on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Both limits lie between two readings on the chip (PERF.md, PR 33; three
# seeds): the served programs' logits are 3.2-5.7 % off the reference and
# float8 activations 11.0 %; the served programs agree with the reference
# on 72-74 % of the top-8 sets and float8 activations on 41 %.
REL_TOL = 0.08
AGREE_MIN = 0.58


def say(**kw):
    print(json.dumps(kw), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ax-k1-int8-ep16")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--skip-step", action="store_true")
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
    from generativeaiexamples_tpu.models import latent_moe
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving import served_latent
    from generativeaiexamples_tpu.serving.kv_cache import PagePool
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    dev = jax.devices()[0]
    if args.rehearse:
        from benchmark.tests.test_axk1 import tiny_file
        config = tiny_file()
    elif dev.platform != "tpu":
        raise SystemExit("check_latent_moe_on_chip: no TPU; refusing")
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
    say(device=dev.device_kind, rows=mcfg.cache_rows,
        experts_held=mcfg.experts_held, slots=B, pages=n_pages, block=K)

    def fresh_pool():
        return PagePool.zeros(mcfg, n_pages, ps,
                              dtype=jnp.dtype(ecfg.kv_dtype))

    def zeros(n, dt=jnp.float32):
        return jnp.zeros((n,), dt)

    P, NEW = (min(ecfg.prefill_buckets), 32) if not args.rehearse \
        else (16, 2 * K)

    def replay_step(patch=None):
        """`served_latent.decode_once` jitted (with `patch` on while traced):
        -> (logits, pool, choices [Lm, B, k])."""
        def step(p, pool, t, tb, ln):
            logits, pool, _, choices = served_latent.decode_once(
                p, mcfg, pool, t, tb, ln, None)
            return logits, pool, choices
        jitted = jax.jit(step, donate_argnums=(1,))

        def run(*a):
            if patch is not None:
                patch(True)
            try:
                return jitted(*a)
            finally:
                if patch is not None:
                    patch(False)
        return run

    def serve(params, ids_prompt, step, after_prefill=lambda pool: pool):
        """-> (served tokens [NEW], prefill logits [V], replayed logits
        [NEW, V], the router's choices in the replay [NEW, Lm, k])."""
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
        pre_logits, pool = em.prefill_step(
            params, mcfg, fresh_pool(), jnp.asarray(toks[:1]), jnp.int32(P),
            jnp.asarray(rows[0]), None)
        del pool
        _, pool = prefilled()
        pool = after_prefill(pool)
        out, chosen = [], []
        for i in range(NEW):
            cur = np.zeros((B,), np.int32)
            cur[0] = served[i]
            ln = np.ones((B,), np.int32)
            ln[0] = P + 1 + i
            logits, pool, choices = step(
                params, pool, jnp.asarray(cur), jnp.asarray(table),
                jnp.asarray(ln))
            out.append(np.asarray(logits[0]))
            chosen.append(np.asarray(choices[:, 0]))
        del pool
        return served, np.asarray(pre_logits), np.stack(out), np.stack(chosen)

    def compare(name, seed, params, ids_prompt, ref_cache, step, **kw):
        served, pre, dec, chosen = serve(params, ids_prompt, step, **kw)
        seq = list(ids_prompt) + served[:NEW]
        key = tuple(seq)
        if key not in ref_cache:
            ref_cache.clear()
            logits, choices = entry.reference_forward(
                config, params, np.asarray(seq, np.int32))
            ref_cache[key] = (np.asarray(logits), np.asarray(choices))
        ref, ref_choice = ref_cache[key]
        top = float(np.abs(ref).max())
        worst_pre = float(np.abs(pre - ref[P - 1]).max()) / top
        per_pos = np.abs(dec - ref[P:P + NEW]).max(axis=1) / top
        # the router's sets: replay position i is sequence position P + i
        want = np.sort(ref_choice[:, P:P + NEW], -1).transpose(1, 0, 2)
        same = np.all(np.sort(chosen, -1) == want, -1)   # [NEW, Lm]
        agree, agree_first = float(same.mean()), float(same[:, 0].mean())
        short = [float((ref[P - 1 + i].max() - ref[P - 1 + i, t])
                       / abs(ref[P - 1 + i].max()))
                 for i, t in enumerate(served[:NEW])]
        rel = float(max(worst_pre, per_pos.max()))
        ok = rel <= REL_TOL and agree >= AGREE_MIN
        say(check=name, seed=seed, largest_ref_logit=top,
            prefill_rel=worst_pre, decode_rel_max=float(per_pos.max()),
            decode_rel_mean=float(per_pos.mean()), agree=agree,
            agree_first=agree_first,
            agree_by_layer=[round(float(a), 3) for a in same.mean(axis=0)],
            served_shortfall_max=max(short), rel_tol=REL_TOL,
            agree_min=AGREE_MIN, passes=ok)
        return {"rel": rel, "agree": agree, "agree_first": agree_first,
                "passes": ok}

    # -- lower precisions than the configuration states -------------------
    real_ff, real_route = latent_moe.feed_forward, latent_moe.route

    def fp8_ff(*a, **kw):
        x, counts, idx = real_ff(*a, **kw)
        return (jax.lax.reduce_precision(x, exponent_bits=4,
                                         mantissa_bits=3), counts, idx)

    def bf16_route(cfg, h, router):
        s = jax.nn.sigmoid(jnp.dot(h, router,
                                   preferred_element_type=jnp.float32))
        s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        top, idx = jax.lax.top_k(s, cfg.n_experts_per_tok)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), top * cfg.routed_scaling_factor

    def to_fp8(a):
        return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)

    from generativeaiexamples_tpu.serving import kv_cache
    real_padded = kv_cache.LatentPagePool._padded

    def fp8_padded(self, x):
        return to_fp8(real_padded(self, x))

    @functools.partial(jax.jit, donate_argnums=0)
    def fp8_rows(pool):  # the prompt's rows too, not only new tokens'
        return dataclasses.replace(pool, c=to_fp8(pool.c))

    def patch_fp8(on):
        latent_moe.feed_forward = fp8_ff if on else real_ff

    def patch_pool(on):
        kv_cache.LatentPagePool._padded = fp8_padded if on else real_padded

    def patch_router(on):
        latent_moe.route = bf16_route if on else real_route

    refused = ("fp8_activations", "fp8_latent_pool")
    readings = {"served": [], "fp8_activations": [], "fp8_latent_pool": [],
                "bf16_router": []}
    params = None
    for s in range(args.seeds):
        seed = 2**31 + 1009 * s + 17
        params, _ = entry.init_params(config, mcfg, seed, [dev])
        rng = np.random.default_rng(seed)
        ids_prompt = rng.integers(1, mcfg.vocab_size, P).astype(np.int32)
        cache = {}
        readings["served"].append(
            compare("served", seed, params, ids_prompt, cache, replay_step()))
        if s == 0:
            for name, patch, after in (
                    ("fp8_activations", patch_fp8, lambda pool: pool),
                    ("fp8_latent_pool", patch_pool, fp8_rows),
                    ("bf16_router", patch_router, lambda pool: pool)):
                readings[name].append(compare(
                    name, seed, params, ids_prompt, cache,
                    replay_step(patch), after_prefill=after))
        if s < args.seeds - 1:
            del params
    verdict = (all(r["passes"] for r in readings["served"])
               and not any(r["passes"] for name in refused
                           for r in readings[name]))
    say(readings=readings, ok=verdict)

    if args.skip_step:
        return 0 if verdict else 1
    K = ecfg.decode_steps_per_dispatch
    rng = np.random.default_rng(7)
    ctx = rng.integers(256, 1024, B) if not args.rehearse \
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
    m = compiled.memory_analysis()
    say(step="compiled", block=K, compile_s=time.monotonic() - t0,
        temp_gib=m.temp_size_in_bytes / 2**30,
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
    load = np.asarray(block)[B:, 1:]
    say(step="timed", step_ms=times, mean_context=float(ctx.mean()),
        pairs_per_expert_step=float(load.mean()),
        experts_hit_of_held=float((load > 0).mean()) * mcfg.experts_held,
        peak_gib=(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        / 2**30)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
