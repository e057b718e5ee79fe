"""Fleet-router smoke: boot a 2-replica fleet (CPU is fine) and assert
the three contracts the topology rests on:

  (a) routed streams are BYTE-IDENTICAL to a single engine's — the
      fleet changes where a request runs, never what it says;
  (b) prefix locality works end to end: turn 2 of a conversation lands
      on the replica holding its KV (router_prefix_hits > 0 AND that
      replica's ENGINE-level cache scores the hit);
  (c) graceful drain finishes the in-flight stream (no error event,
      full token count) while the drained replica stops admitting.

CI-grade: exits nonzero on any violation, prints one JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_router.py
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def collect(req, timeout=120):
    toks = []
    while True:
        ev = req.stream.get(timeout=timeout)
        if ev["token_id"] >= 0:
            toks.append(ev["token_id"])
        if ev["finished"]:
            return toks, ev["finish_reason"]


def main() -> int:
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, LocalReplica)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=8,
                        prefill_buckets=(16, 32), prefix_cache=True,
                        pace_emission_max_streams=0)

    def engine():
        return LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                         use_pallas=False)

    def run(target, ids, session="", max_new=24):
        req = GenRequest(prompt_ids=list(ids), max_new_tokens=max_new,
                         session_id=session)
        target.submit(req)
        return collect(req)

    failures = []
    prompts = [[(5 * i + j) % 250 + 1 for j in range(18 + 2 * i)]
               for i in range(4)]

    # Reference: single engine, sequential.
    single = engine().start()
    want = [run(single, p)[0] for p in prompts]
    single.stop()

    fleet = EngineFleet(
        [LocalReplica(f"r{i}", engine()) for i in range(2)],
        ByteTokenizer(), ecfg.page_size).start()

    # (a) byte-identical streams through the router.
    got = [run(fleet, p)[0] for p in prompts]
    if got != want:
        failures.append("routed streams differ from single engine")

    # (b) conversation replay: turn 2 must score a prefix hit on the
    # SAME replica (router counter + engine-level cache hit).
    turn1 = [11] * 40
    out1, _ = run(fleet, turn1, session="conv")
    turn2 = turn1 + out1 + [13] * 8
    run(fleet, turn2, session="conv")
    snap = fleet.metrics.snapshot()
    if snap["router_prefix_hits"] < 1:
        failures.append(f"router_prefix_hits={snap['router_prefix_hits']}"
                        " (expected > 0 on turn 2)")
    engine_hits = sum(r.engine.metrics.prefix_hits
                      for r in fleet.local_replicas())
    if engine_hits < 1:
        failures.append("turn 2 missed the replica holding its KV "
                        f"(engine prefix_hits={engine_hits})")

    # (c) graceful drain: the in-flight stream finishes cleanly.
    req = GenRequest(prompt_ids=[9] * 24, max_new_tokens=48)
    fleet.submit(req)
    rid = next((r for r, d in fleet.router.queue_depths().items() if d),
               None)
    if rid is None:
        failures.append("in-flight request not visible in queue depths")
    else:
        if not fleet.drain(rid, timeout_s=120.0):
            failures.append(f"drain of {rid} timed out with streams live")
        toks, reason = collect(req, timeout=5)
        if reason == "error" or (reason == "length" and len(toks) != 48):
            failures.append(
                f"drained stream ended {reason!r} after {len(toks)} tokens")
        state = fleet.fleet_health()["replicas"][rid]["state"]
        if state != "drained":
            failures.append(f"replica {rid} state {state!r} after drain")
    fleet.stop()

    print(json.dumps({
        "routed_byte_identical": got == want,
        "router_prefix_hits": snap["router_prefix_hits"],
        "router_hit_tokens": snap["router_hit_tokens"],
        "engine_prefix_hits": engine_hits,
        "drained_replica": rid,
        "failures": failures,
    }))
    if failures:
        print("SMOKE FAILED:", "; ".join(failures), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
