"""Fused prefill+decode smoke: boot a fused-on engine (CPU is fine),
serve a long prompt alongside a live decode stream, and assert (a) the
prefill actually rode decode dispatches (fused_steps > 0, every prompt
token carried by a rider) and (b) token outputs are byte-identical to a
fused-off engine driven through the same deterministic schedule.
CI-grade: exits nonzero on any violation, prints one JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_fused_step.py
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


def run(params, cfg, fused: bool):
    """Drive the scheduler inline (single thread, no wall clock): the
    dispatch schedule is then a pure function of engine state, so the
    fused-on and fused-off runs are exactly comparable."""
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=8,
                        prefill_buckets=(16,), decode_steps_per_dispatch=2,
                        fused_prefill=fused, pace_emission_max_streams=0)
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

    short = GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=64)
    eng.submit(short)
    for _ in range(2):
        step()
    long_prompt = [(i * 7) % cfg.vocab_size for i in range(200)]
    long_req = GenRequest(prompt_ids=long_prompt, max_new_tokens=4)
    eng.submit(long_req)
    for _ in range(400):
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
    s_off, l_off, m_off = run(params, cfg, fused=False)
    s_on, l_on, m_on = run(params, cfg, fused=True)
    long_prompt = [(i * 7) % cfg.vocab_size for i in range(200)]
    want = np.asarray(llama.greedy_generate(
        params, cfg, jnp.asarray([long_prompt]), 4))[0, 200:].tolist()

    out = {"fused_steps": m_on["fused_steps"],
           "fused_prefill_tokens": m_on["fused_prefill_tokens"],
           "prefill_stall_beats": m_on["prefill_stall_beats"],
           "fused_off_steps": m_off["fused_steps"]}
    failures = []
    if m_on["fused_steps"] <= 0:
        failures.append("fused_steps is zero with fused_prefill on")
    if m_on["fused_prefill_tokens"] != len(long_prompt):
        failures.append(
            f"riders carried {m_on['fused_prefill_tokens']} of "
            f"{len(long_prompt)} prompt tokens")
    if m_off["fused_steps"] != 0:
        failures.append("fused-off engine reported fused steps")
    if s_on != s_off or len(s_on) != 64:
        failures.append("short stream diverged between fused on/off")
    if l_on != l_off:
        failures.append("long stream diverged between fused on/off")
    if l_on != want:
        failures.append("long stream diverged from offline greedy")
    out["ok"] = not failures
    if failures:
        out["failures"] = failures
    print(json.dumps(out))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
