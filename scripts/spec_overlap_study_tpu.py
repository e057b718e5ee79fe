"""Speculative-decode acceptance vs workload overlap, measured through
the engine API on the real chip (VERDICT r4 #5).

The n-gram drafter proposes the tokens that FOLLOWED the most recent
occurrence of the current token in the request's history (prompt +
generated so far) — prompt-lookup decoding. Its favorable case is RAG
answers quoting retrieved context; its unfavorable case is output that
never revisits its own n-grams. With seeded (random) weights the
model's output cannot be scripted, so this study measures acceptance
against the OBSERVED overlap of each run's output with its history:

  per workload class (prompt geometry) x k in {0, 1, 2}:
    - tok/s through the engine (B=32 int8 8b, the deployment config)
    - committed tokens per verify step (engine spec gauge)
    - measured output overlap: fraction of emitted (token, next-token)
      bigrams whose token occurred earlier in history with the SAME
      successor — exactly the event the drafter exploits

Classes: "varied" prompts (distinct tokens, cycles only if the model
falls into one) and "loop-prone" prompts (short repeated pattern —
random-weight greedy outputs revisit history often, standing in for
the context-echo regime).

The deployment default APP_ENGINE_SPECULATIVEK in deploy/compose.env
is set from this table (bench ships the same k).

Run (serialize with other chip users):
  PYTHONPATH=/root/repo python scripts/spec_overlap_study_tpu.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402


def measured_overlap(prompt, out):
    """Two rates describing how predictable the output was by the
    drafter's k=1 rule (most recent prior occurrence of the current
    token -> its successor):
      overlap      = hits / len(out)   — unconditional: the fraction
                     of ALL emitted tokens a history draft would have
                     gotten right (what acceptance actually tracks);
      lookup_rate  = draftable / len(out) — how often the lookup even
                     found a prior occurrence to draft from.
    The conditional rate is overlap / lookup_rate."""
    hist = list(prompt)
    hits = draftable = 0
    for t in out:
        prev = hist[-1]
        # most recent earlier occurrence of prev (exclude final pos)
        idx = None
        for j in range(len(hist) - 2, -1, -1):
            if hist[j] == prev:
                idx = j
                break
        if idx is not None:
            draftable += 1
            if hist[idx + 1] == t:
                hits += 1
        hist.append(t)
    n = max(1, len(out))
    return hits / n, draftable / n


def run_class(params, cfg, prompts, k, gen=96):
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    B = len(prompts)
    plen = max(len(p) for p in prompts)
    ecfg = EngineConfig(
        max_batch_size=B, max_seq_len=plen + gen + 128 + 2 * 8 * (k + 1),
        page_size=128, prefill_buckets=(plen,), kv_dtype="int8",
        decode_steps_per_dispatch=8, pipeline_depth=2, speculative_k=k)
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg)
    eng.warmup()
    eng.start()
    outs = [None] * B

    def worker(i):
        outs[i] = [ev["token_id"] for ev in
                   eng.generate_stream(prompts[i], max_new_tokens=gen)
                   if ev["token_id"] >= 0]

    eng.metrics.reset_window()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(B)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    eng.stop()
    del eng
    import gc

    gc.collect()
    total = sum(len(o) for o in outs)
    rates = [measured_overlap(p, o) for p, o in zip(prompts, outs)]
    ov = sum(r[0] for r in rates) / B
    lk = sum(r[1] for r in rates) / B
    return {
        "tok_per_sec": round(total / wall, 1),
        "tokens_per_step": round(snap.get("spec_tokens_per_step", 1.0), 3),
        "measured_overlap": round(ov, 3),
        "lookup_rate": round(lk, 3),
    }


def main() -> int:
    from generativeaiexamples_tpu.models import llama

    cfg = llama.LlamaConfig.llama3_8b()
    t0 = time.perf_counter()
    params = llama.init_params_on_device(cfg, quantize=True)
    leaf = params["layers"]["wq"]
    jax.block_until_ready(leaf.q if hasattr(leaf, "q") else leaf)
    print(f"[study] params ready in {time.perf_counter()-t0:.0f}s",
          file=sys.stderr)

    B, plen = 32, 128
    varied = [[2 + ((i * 131 + j * 17) % 5000) for j in range(plen)]
              for i in range(B)]
    # Loop-prone: an 8-token motif repeated across the prompt — the
    # drafter's lookup structure is saturated with repeats, standing in
    # for answers that quote retrieved context.
    loopy = [[2 + ((i * 7 + (j % 8) * 13) % 900) for j in range(plen)]
             for i in range(B)]

    table = {}
    for name, prompts in (("varied", varied), ("loop_prone", loopy)):
        for k in (0, 1, 2):
            r = run_class(params, cfg, prompts, k)
            table[f"{name}_k{k}"] = r
            print(f"[study] {name} k={k}: {r}", file=sys.stderr)
    print(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
