"""Composable-step-plan smoke: boot a plans-on engine (CPU is fine)
with speculation, tree drafts AND the fused prefill rider all enabled,
serve a long prompt alongside a live decode stream, and assert (a) the
composed fused+spec plan actually ran (fused_steps > 0 on a
speculative engine, every prompt token carried by a rider), (b) tree
drafts beat one token per verify step (spec_tokens_per_step > 1.0),
and (c) token streams are byte-identical to the offline greedy
continuation. CI-grade: exits nonzero on any violation, prints one
JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_plan_step.py
"""

from __future__ import annotations

import json
import os
import queue
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def run(params, cfg):
    """Drive the scheduler inline (single thread, no wall clock): the
    dispatch schedule is a pure function of engine state. A repetitive
    short stream (n-gram friendly — the tree draft's win condition)
    decodes continuously while a 200-token prompt's chunks ride the
    composed spec+rider plan."""
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    ecfg = EngineConfig(max_batch_size=2, max_seq_len=512, page_size=8,
                        prefill_buckets=(16,), decode_steps_per_dispatch=2,
                        speculative_k=2, speculative_tree_branches=3,
                        fused_prefill=True, step_plans=True,
                        pace_emission_max_streams=0)
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)

    def step():
        eng._admit_waiting()
        eng._advance_long_prefills()
        eng._emit_ready_first_tokens()
        while (len(eng._inflight) < eng.pipeline_depth
               and any(s is not None for s in eng.slots)):
            if not eng._dispatch_decode():
                break
        if not eng._inflight:
            return
        fl = eng._inflight.popleft()
        eng._process_block_host(fl, eng._fetch_block_host(fl))
        for seq in fl.releases:
            seq.release()
        fl.releases = []
        eng._reap_starved()
        eng._beat += 1
        eng._note_prefill_stalls()

    short = GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=120)
    eng.submit(short)
    for _ in range(2):
        step()
    long_prompt = [(i * 7) % cfg.vocab_size for i in range(200)]
    long_req = GenRequest(prompt_ids=long_prompt, max_new_tokens=4)
    eng.submit(long_req)
    for _ in range(500):
        step()
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._long_prefills and not eng._inflight
                and not eng._pending_first):
            break

    def drain(req):
        out = []
        while True:
            try:
                ev = req.stream.get_nowait()
            except queue.Empty:
                return out
            if ev["token_id"] >= 0:
                out.append(ev["token_id"])

    return drain(short), drain(long_req), eng.metrics.snapshot()


def main() -> int:
    from generativeaiexamples_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    s_toks, l_toks, m = run(params, cfg)
    want_s = np.asarray(llama.greedy_generate(
        params, cfg, jnp.asarray([[7, 8, 9]]), 120))[0, 3:].tolist()
    long_prompt = [(i * 7) % cfg.vocab_size for i in range(200)]
    want_l = np.asarray(llama.greedy_generate(
        params, cfg, jnp.asarray([long_prompt]), 4))[0, 200:].tolist()

    out = {"fused_steps": m["fused_steps"],
           "fused_prefill_tokens": m["fused_prefill_tokens"],
           "spec_tokens_per_step": round(m["spec_tokens_per_step"], 3),
           "plan_variants_compiled": m["plan_variants_compiled"]}
    failures = []
    if m["fused_steps"] <= 0:
        failures.append("no composed fused+spec plan dispatched "
                        "(fused_steps is zero on a speculative engine)")
    if m["fused_prefill_tokens"] != len(long_prompt):
        failures.append(
            f"riders carried {m['fused_prefill_tokens']} of "
            f"{len(long_prompt)} prompt tokens")
    if m["spec_tokens_per_step"] <= 1.0:
        failures.append(
            f"tree drafts committed {m['spec_tokens_per_step']:.2f} "
            f"tokens/verify-step (need > 1.0)")
    if s_toks != want_s:
        failures.append("short stream diverged from offline greedy")
    if l_toks != want_l:
        failures.append("long stream diverged from offline greedy")
    out["ok"] = not failures
    if failures:
        out["failures"] = failures
    print(json.dumps(out))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
