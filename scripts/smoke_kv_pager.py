"""KV-pager smoke: boot the engine with prefix_cache + kv_pager on
(CPU is fine) and assert the tiered-session story end to end:

- sessions far beyond the device pool's capacity SURVIVE demotion
  (their prefixes stay fully matchable in the radix tree, parked in
  host RAM / disk instead of destroyed) — >= 4x more sessions
  resident than the pool alone could hold;
- a warm resume of a demoted session is byte-identical to offline
  greedy (promotion re-seats the exact bytes) and registers a prefix
  HIT with kv_promotions > 0.

CI-grade: exits nonzero on any violation, prints one JSON summary.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_kv_pager.py
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
    ecfg = EngineConfig(max_batch_size=1, max_seq_len=32, page_size=8,
                        prefill_buckets=(16,), kv_dtype="float32",
                        decode_steps_per_dispatch=2,
                        prefix_cache=True, prefix_cache_capacity=1.0,
                        kv_pager=True, kv_host_budget_mb=4)
    # 5 usable pages; every request needs 3 (16-token prompt + 4
    # generated) and caches 2, so the pool ALONE holds 2 sessions'
    # prefixes — the pager must park the rest.
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg, n_pages=6,
                    use_pallas=False).start()

    def run(prompt):
        return [e["token_id"] for e in
                eng.generate_stream(prompt, max_new_tokens=4)
                if e["token_id"] >= 0]

    def greedy(prompt):
        return list(np.asarray(llama.greedy_generate(
            params, cfg, jnp.asarray([prompt]), 4))[0, len(prompt):])

    failures = []
    n_sessions = 16
    prompts = [[(i * 7 + s) % cfg.vocab_size for i in range(16)]
               for s in range(n_sessions)]
    try:
        for s, p in enumerate(prompts):
            if run(p) != greedy(p):
                failures.append(f"session {s} diverged from offline greedy")
        # Every session's 2-page prefix must still be fully matchable
        # (resident SOMEWHERE: device, host RAM or disk spill).
        resident = sum(len(eng.prefix_cache.match_nodes(p)) == 2
                       for p in prompts)
        hbm_only = max(1, eng.prefix_cache.capacity_pages // 2)
        snap1 = eng.metrics.snapshot()
        if snap1["kv_demotions"] <= 0:
            failures.append("no demotions despite pool pressure")
        if resident < n_sessions:
            failures.append(f"only {resident}/{n_sessions} sessions "
                            "survived demotion")
        ratio = resident / hbm_only
        if ratio < 4.0:
            failures.append(f"sessions-resident ratio {ratio:.1f} < 4x "
                            "the HBM-only capacity")
        # Warm resumes of demoted sessions: byte-identical + promoted.
        for s in (0, 1, 2):
            if run(prompts[s]) != greedy(prompts[s]):
                failures.append(f"warm resume of session {s} diverged")
        snap2 = eng.metrics.snapshot()
        if snap2["kv_promotions"] <= 0:
            failures.append("warm resumes promoted zero pages")
        if snap2["prefix_hits"] <= snap1["prefix_hits"]:
            failures.append("warm resumes registered no prefix hits")
    finally:
        eng.stop()

    out = {"sessions": n_sessions, "resident": resident,
           "hbm_only_capacity": hbm_only,
           "sessions_resident_vs_hbm_only": round(ratio, 2),
           "kv_demotions": snap2["kv_demotions"],
           "kv_promotions": snap2["kv_promotions"],
           "kv_host_pages": snap2["kv_host_pages"],
           "kv_spill_pages": snap2["kv_spill_pages"],
           "prefix_hits": snap2["prefix_hits"],
           "ok": not failures}
    if failures:
        out["failures"] = failures
    print(json.dumps(out))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
