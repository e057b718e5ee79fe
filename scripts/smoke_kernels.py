"""Kernel-parity smoke: the CPU CI gate for the Pallas tree-attention
kernels and the fused first-token sampling tail.

Runs the shared parity suite (scripts/bench_kernels.py --verify) in
Pallas INTERPRET mode on the CPU backend — the same kernel code that
compiles on TPU, executed by the Pallas interpreter and pinned against
the XLA gather references — then an end-to-end engine check: a
tree-speculative engine served twice, once on the reference attention
route and once with ENGINE_TREE_KERNEL_INTERPRET=1 forcing the Pallas
kernels, must emit byte-identical greedy streams (the commit-semantics
contract: the kernel may only change speed, never content).

CI-grade: exits nonzero on any violation, prints one JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_kernels.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_E2E = r'''
import json, os, sys
import jax
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

cfg = llama.LlamaConfig.tiny()
params = llama.init_params(cfg, jax.random.PRNGKey(3))
ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=8,
                    prefill_buckets=(16,), decode_steps_per_dispatch=2,
                    speculative_k=2, speculative_tree_branches=3,
                    step_plans=True, pace_emission_max_streams=0,
                    kv_dtype=os.environ.get("SMOKE_KV_DTYPE", "bfloat16"))
eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)
eng.start()
toks = [ev["token_id"]
        for ev in eng.generate_stream([7, 8, 9, 7, 8, 9, 7, 8],
                                      max_new_tokens=48)
        if ev["token_id"] >= 0]
# A prompt past the biggest bucket takes the CHUNKED prefill path, so
# its finish exercises the fused first-token tail (rider_sample plan).
long_prompt = [(i * 7) % cfg.vocab_size for i in range(40)]
toks_long = [ev["token_id"]
             for ev in eng.generate_stream(long_prompt, max_new_tokens=8)
             if ev["token_id"] >= 0]
snap = eng.metrics.snapshot()
eng.stop()
print(json.dumps({"tokens": toks, "tokens_long": toks_long,
                  "spec_tps": snap["spec_tokens_per_step"],
                  "fused_sample": snap["fused_sample_dispatches"]}))
'''


def _run_e2e(kv_dtype: str, interpret_kernels: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", SMOKE_KV_DTYPE=kv_dtype)
    if interpret_kernels:
        env["ENGINE_TREE_KERNEL_INTERPRET"] = "1"
    else:
        env.pop("ENGINE_TREE_KERNEL_INTERPRET", None)
    proc = subprocess.run([sys.executable, "-c", _E2E], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print(proc.stderr[-4000:], file=sys.stderr)
        raise SystemExit(f"e2e child failed (kv_dtype={kv_dtype}, "
                         f"interpret={interpret_kernels})")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> None:
    # 1. Kernel parity + fused-sampling equality (interpret mode).
    from scripts import bench_kernels

    bench_kernels.run_verify()

    # 2. E2E commit semantics: reference route vs forced Pallas
    # kernels, bf16 and int8 pools — byte-identical greedy streams,
    # with speculation actually engaged and the fused sampling tail
    # actually used.
    summary = {"parity": "ok"}
    for kvd in ("bfloat16", "int8"):
        ref = _run_e2e(kvd, False)
        ker = _run_e2e(kvd, True)
        assert ref["tokens"] == ker["tokens"], (
            f"{kvd}: kernel route changed the greedy stream "
            f"(ref {ref['tokens'][:8]}... vs kernel {ker['tokens'][:8]}...)")
        assert ref["tokens_long"] == ker["tokens_long"], (
            f"{kvd}: chunked-prefill stream diverged under the kernel "
            f"route")
        assert len(ref["tokens"]) == 48, len(ref["tokens"])
        assert ref["spec_tps"] > 1.0, ref["spec_tps"]
        # The long prompt's finish must have ridden the fused
        # first-token tail (engine.fused_sampling default-on).
        assert ref["fused_sample"] >= 1, ref["fused_sample"]
        summary[f"{kvd}_tokens"] = len(ref["tokens"])
        summary[f"{kvd}_spec_tokens_per_step"] = round(ker["spec_tps"], 3)
        summary[f"{kvd}_fused_sample_dispatches"] = ker["fused_sample"]
    print(json.dumps({"smoke_kernels": summary}))
    print("smoke_kernels: PASS", file=sys.stderr)


if __name__ == "__main__":
    main()
