#!/usr/bin/env python3
"""A configuration with state-space layers beside attention at its
published widths, on the chip, through the step programs the benchmark
times:

    chiprun -- python3 scripts/check_hybrid_on_chip.py [--config NAME] [--seeds 2]

logits: three seeded prompts of UNEQUAL length, padded to one bucket,
through `prefill_batch_step` (the cell's group of 4, one padding row)
into decode slots that an earlier sequence has just used and left dirty,
then NEW tokens through `decode_multi_step` (greedy, blocks of 8) and
both pools with every other slot idle; the same positions replayed
through `served_hybrid.decode_once` (the body of `decode_step` and
`decode_multi_step`, which returns logits and the router's choices) and
compared with the plain reference's ONE forward pass of each whole
sequence (`benchmark/architectures/granitemoehybrid.py`: the recurrence
as a loop over tokens, one expert's weights in float32 at a time):

- `rel`: the largest |difference| of logits over the largest |reference
  logit|, over each prompt's last position and every decoded one, is
  held under REL_TOL;
- `state_rel`: for the state the FIRST state-space layer is left with
  after the last token, the largest |difference| over the largest
  |reference value| of the same head, the worst head, is held under
  STATE_TOL. The logits alone cannot see the state's type: the skip term
  D * x is ten times what the state gives y, and a head that forgets
  slowly (step 0.001, -A of 1) rounds a bfloat16 state a thousand times
  before its weight on the output has halved. The first layer because
  its input is the embedding alone, which program and reference share:
  a deeper layer's state also carries every router's near-tie that fell
  the other way upstream (random weights; `agree`), which moves it by
  more than the state's type does. Every layer's reading is printed
  (`state_rel_by_layer`), the first is judged;
- `agree`: the share of (token, layer) top-10 SETS on which program and
  reference agree, read and not judged.

The same comparison for two programs it must refuse: a state kept in
bfloat16 (rounded after the prefill and after every decode step), and a
state-space layer that leaves out D * x.

step: the decode program's compile time, memory and time a step at the
cell's shape (all slots live, contexts around the mix's mean).

One JSON object per line on stdout; never a measurement on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Each limit lies between two readings on the chip (PERF.md, PR 35; two
# seeds, three prompts each, 256 decoded tokens). Logits: the served
# programs read 2.5 and 2.9 % of the largest reference logit (bf16
# activations, int8 weights, and the routers' near-ties under random
# weights: a seventh of the top-10 sets differ; 8.4 and 9.6 % before
# hybrid_ssm.ROUTED_INIT_GAIN), a layer without D * x 108-112 %. The
# first layer's state: served 1.7-2.2 % on the worst head, a bfloat16
# state 2.5-6.5 % a prompt and 6.5 % as judged (the worst prompt).
REL_TOL = 0.20
STATE_TOL = 0.04


def say(**kw):
    print(json.dumps(kw), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="granite-4.0-h-small-int8")
    ap.add_argument("--seeds", type=int, default=2)
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
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving import served_hybrid
    from generativeaiexamples_tpu.serving import ssm_state_update as upd
    from generativeaiexamples_tpu.serving.kv_cache import PagePool
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    dev = jax.devices()[0]
    if args.rehearse:
        from benchmark.tests.test_granitemoehybrid import tiny_file
        config = tiny_file()
    elif dev.platform != "tpu":
        raise SystemExit("check_hybrid_on_chip: no TPU; refusing")
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
    N = ecfg.max_prefill_group
    greedy = (True, False, False)
    say(device=dev.device_kind, rows=mcfg.cache_rows,
        ssm_layers=mcfg.n_ssm_layers, experts=mcfg.n_experts, slots=B,
        pages=n_pages, block=K)

    def fresh_pool():
        return PagePool.zeros(mcfg, n_pages, ps,
                              dtype=jnp.dtype(ecfg.kv_dtype), slots=B)

    def zeros(n, dt=jnp.float32):
        return jnp.zeros((n,), dt)

    P = min(ecfg.prefill_buckets)
    # enough decoded tokens that a state rounded at every step shows
    # above the bf16 activations' own noise (its error grows as the
    # root of the steps on a head that forgets slowly; theirs does not)
    NEW = 256 if not args.rehearse else 2 * K
    # three rows of the group, unequal lengths, in slots far apart
    lengths = [P, P * 3 // 5, P * 4 // 5 + 1][:min(3, N, B)]
    slots = [B // 2, 0, B - 1][:len(lengths)]
    key = jax.random.PRNGKey(0)

    def group(prompts):
        toks = np.zeros((N, P), np.int32)
        ln = np.ones((N,), np.int32)
        rows = np.zeros((N, P // ps), np.int32)
        idxs = np.full((N,), B, np.int32)  # a padding row: dropped
        table = np.zeros((B, maxp), np.int32)
        for r, ids in enumerate(prompts):
            toks[r, :len(ids)] = ids
            ln[r] = len(ids)
            table[slots[r]] = 1 + r * maxp + np.arange(maxp)
            rows[r] = table[slots[r], :P // ps]
            idxs[r] = slots[r]
        return toks, ln, rows, idxs, table

    def prefilled(params, pool, prompts):
        toks, ln, rows, idxs, _ = group(prompts)
        first, pool = em.prefill_batch_step(
            params, mcfg, pool, jnp.asarray(toks), jnp.asarray(ln),
            jnp.asarray(rows), zeros(N), zeros(N), zeros(N, jnp.int32), key,
            None, sampling_flags=greedy, state_slots=jnp.asarray(idxs))
        return [int(t) for t in np.asarray(first)[:len(prompts)]], pool

    def decode_blocks(params, pool, prompts, first, n_new):
        """-> (served tokens a row [rows][n_new + 1], pool)."""
        _, _, _, _, table = group(prompts)
        active = np.zeros((B,), bool)
        ln = np.ones((B,), np.int32)
        last = np.zeros((B,), np.int32)
        for r, ids in enumerate(prompts):
            active[slots[r]] = True
            ln[slots[r]] = len(ids) + 1
            last[slots[r]] = first[r]
        last = jnp.asarray(last)
        served = [[t] for t in first]
        for _ in range(n_new // K):
            block, last, pool = em.decode_multi_step(
                params, mcfg, pool, last, jnp.asarray(table), jnp.asarray(ln),
                jnp.asarray(active), zeros(B), zeros(B), zeros(B, jnp.int32),
                key, K, None, sampling_flags=greedy)
            host = np.asarray(block)
            for r in range(len(prompts)):
                served[r] += [int(t) for t in host[slots[r], 1:]]
            ln = ln + K * active
        return served, pool

    def replay_step(patch=None):
        """`served_hybrid.decode_once` jitted (with `patch` on while traced):
        -> (logits, pool, choices [L, B, k])."""
        def step(p, pool, t, tb, ln, live):
            logits, pool, _, choices = served_hybrid.decode_once(
                p, mcfg, pool, t, tb, ln, None, mask=live)
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

    def serve(params, prompts, dirty):
        """The timed path: slots dirtied by `dirty`, then `prompts`.
        -> served tokens a row."""
        pool = fresh_pool()
        if dirty:
            first, pool = prefilled(params, pool, dirty)
            _, pool = decode_blocks(params, pool, dirty, first, K)
        first, pool = prefilled(params, pool, prompts)
        served, pool = decode_blocks(params, pool, prompts, first, NEW)
        del pool
        return served

    def replay(params, prompts, served, step, after_prefill=lambda p: p):
        """-> (logits [rows][NEW, V], choices [rows][NEW, L, k], the
        state-space layers' states after the last token [rows][Ls, H, P,
        N]) of the sequence prompt + served[:NEW]."""
        _, _, _, _, table = group(prompts)
        _, pool = prefilled(params, fresh_pool(), prompts)
        pool = after_prefill(pool)
        live = np.zeros((B,), bool)
        live[slots[:len(prompts)]] = True
        out = [[] for _ in prompts]
        chosen = [[] for _ in prompts]
        for i in range(NEW):
            cur = np.zeros((B,), np.int32)
            ln = np.ones((B,), np.int32)
            for r, ids in enumerate(prompts):
                cur[slots[r]] = served[r][i]
                ln[slots[r]] = len(ids) + 1 + i
            logits, pool, choices = step(
                params, pool, jnp.asarray(cur), jnp.asarray(table),
                jnp.asarray(ln), jnp.asarray(live))
            host, ch = np.asarray(logits), np.asarray(choices)
            for r in range(len(prompts)):
                out[r].append(host[slots[r]])
                chosen[r].append(ch[:, slots[r]])
        states = [np.asarray(pool.state[:, s]) for s in slots[:len(prompts)]]
        del pool
        return ([np.stack(o) for o in out], [np.stack(c) for c in chosen],
                states)

    def compare(name, seed, params, prompts, served, ref_cache, step, **kw):
        dec, chosen, states = replay(params, prompts, served, step, **kw)
        rels, state_rels, agrees, tops, by_layer = [], [], [], [], []
        for r, ids in enumerate(prompts):
            n = len(ids)
            seq = tuple(int(t) for t in ids) + tuple(served[r][:NEW])
            if seq not in ref_cache:
                logits, ref_states, choices = entry.reference_forward(
                    config, params, np.asarray(seq, np.int32))
                ref_cache[seq] = (np.asarray(logits[n:n + NEW]),
                                  np.asarray(ref_states),
                                  np.asarray(choices[:, n:n + NEW]))
            ref, ref_states, ref_choice = ref_cache[seq]
            top = float(np.abs(ref).max())
            tops.append(top)
            rels.append(float(np.abs(dec[r] - ref).max()) / top)
            per_head = np.abs(states[r] - ref_states).max(axis=(2, 3)) \
                / np.abs(ref_states).max(axis=(2, 3))
            by_layer.append(per_head.max(axis=1))       # worst head a layer
            state_rels.append(float(per_head[0].max()))
            want = np.sort(ref_choice, -1).transpose(1, 0, 2)
            agrees.append(float(np.all(
                np.sort(chosen[r], -1) == want, -1).mean()))
        rel, state_rel = max(rels), max(state_rels)
        ok = rel <= REL_TOL and state_rel <= STATE_TOL
        say(check=name, seed=seed, lengths=[len(p) for p in prompts],
            slots=slots, largest_ref_logit=max(tops), rel_by_row=rels,
            state_rel_by_row=state_rels,
            state_rel_by_layer=[float(v) for v in np.max(by_layer, 0)],
            agree_by_row=agrees, rel=rel,
            state_rel=state_rel, rel_tol=REL_TOL, state_tol=STATE_TOL,
            passes=ok)
        return {"rel": rel, "state_rel": state_rel,
                "agree": float(np.mean(agrees)), "passes": ok}

    # -- programs the comparison must refuse ------------------------------
    def to_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    real_update = upd.ssm_state_update

    def bf16_update(state, layer, *a, **kw):
        state, y = real_update(state, layer, *a, **kw)
        return state.at[layer].set(to_bf16(state[layer])), y

    def patch_state(on):
        upd.ssm_state_update = bf16_update if on else real_update

    @functools.partial(jax.jit, donate_argnums=0)
    def bf16_state(pool):  # the prompt's state too, not only new tokens'
        return dataclasses.replace(pool, state=to_bf16(pool.state))

    refused = ("bf16_state", "no_skip_term")
    readings = {"served": [], "bf16_state": [], "no_skip_term": []}
    params = None
    for s in range(args.seeds):
        seed = 2**31 + 1009 * s + 35
        params, _ = entry.init_params(config, mcfg, seed, [dev])
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(1, mcfg.vocab_size, n).astype(np.int32)
                   for n in lengths]
        dirty = [rng.integers(1, mcfg.vocab_size, P).astype(np.int32)
                 for _ in lengths]
        served = serve(params, prompts, dirty)
        # the same prompts into clean slots serve the same tokens: a
        # reused slot never sees its predecessor's state
        clean = serve(params, prompts, [])
        same = served == clean
        say(check="reused_slots", seed=seed, same_tokens=same)
        cache = {}
        got = compare("served", seed, params, prompts, served, cache,
                      replay_step())
        got["passes"] = got["passes"] and same
        readings["served"].append(got)
        if s == 0:
            readings["bf16_state"].append(compare(
                "bf16_state", seed, params, prompts, served, cache,
                replay_step(patch_state), after_prefill=bf16_state))
            no_skip = dict(params, ssm=dict(
                params["ssm"], D=jnp.zeros_like(params["ssm"]["D"])))
            # the reference keeps D: compared under the served sequences
            dec = compare("no_skip_term", seed, params, prompts, served,
                          cache, lambda p, *a, _s=replay_step(): _s(
                              no_skip, *a))
            readings["no_skip_term"].append(dec)
            del no_skip, dec  # they hold the parameters' leaves
        if s < args.seeds - 1:
            del params, cache
            gc.collect()
    verdict = (all(r["passes"] for r in readings["served"])
               and not any(r["passes"] for name in refused
                           for r in readings[name]))
    say(readings=readings, ok=verdict)

    if args.skip_step:
        return 0 if verdict else 1
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
        experts_hit=float((load > 0).mean()) * mcfg.n_experts,
        peak_gib=(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        / 2**30)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
