"""Prefix-cache smoke: boot the engine with prefix_cache=on (CPU is
fine) and assert a repeated prompt actually hits — hit-rate > 0 and the
second prefill runs only the uncached suffix. CI-grade: exits nonzero
on any violation, prints one JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_prefix_cache.py
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=64, page_size=8,
                        prefill_buckets=(16, 32), kv_dtype="float32",
                        decode_steps_per_dispatch=2, prefix_cache=True)
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                    use_pallas=False).start()
    try:
        prompt = [(i * 5 + 1) % cfg.vocab_size for i in range(26)]
        want = np.asarray(llama.greedy_generate(
            params, cfg, jnp.asarray([prompt]), 6))[0, len(prompt):]
        runs = []
        for _ in range(2):
            got = [e["token_id"] for e in
                   eng.generate_stream(prompt, max_new_tokens=6)
                   if e["token_id"] >= 0]
            runs.append(got)
        snap = eng.metrics.snapshot()
    finally:
        eng.stop()

    lookups = snap["prefix_hits"] + snap["prefix_miss"]
    hit_rate = snap["prefix_hits"] / lookups if lookups else 0.0
    suffix = snap["prefill_tokens"] - len(prompt)  # 2nd request's share
    out = {"prefix_hits": snap["prefix_hits"],
           "prefix_miss": snap["prefix_miss"],
           "prefix_hit_tokens": snap["prefix_hit_tokens"],
           "hit_rate": hit_rate,
           "second_prefill_tokens": suffix}
    failures = []
    if hit_rate <= 0:
        failures.append("hit-rate is zero on a repeated prompt")
    # 26 tokens = 3 full pages (24 cached) + 2-token suffix.
    if snap["prefix_hit_tokens"] != 24 or suffix != 2:
        failures.append(f"expected 24 cached / 2 suffix tokens, got "
                        f"{snap['prefix_hit_tokens']} / {suffix}")
    for i, got in enumerate(runs):
        if got != list(want):
            failures.append(f"run {i} diverged from offline greedy")
    out["ok"] = not failures
    if failures:
        out["failures"] = failures
    print(json.dumps(out))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
