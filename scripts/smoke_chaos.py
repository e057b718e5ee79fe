"""Chaos smoke: the elastic fleet's crash-recovery contract, CPU-grade.

2 local replicas behind the router, a seeded bursty trace, and a
seeded chaos kill of one replica MID-BURST (serving/chaos.py). Gates:

  (a) zero lost requests: every request that had not started
      streaming when the replica died must COMPLETE (requeued to the
      survivor, keeping tier/tenant) — only mid-stream casualties may
      error (their KV died with the replica);
  (b) goodput floor: latency-tier goodput-under-SLO with the kill
      stays >= 0.9x the no-fault baseline on the same trace;
  (c) the fault is OBSERVABLE: the kill is counted
      (chaos_injected_kills), the eviction surfaced
      (replica_evictions, router_requeued), and the chaos flight lane
      carries the event;
  (d) zero zombie threads: after fleet.stop() no engine/fleet/chaos
      thread survives, and stuck_thread_joins == 0.

CI-grade: exits nonzero on any violation, prints one JSON summary.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_chaos.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

SLOS = {"latency": {"ttft_s": 3.0, "gap_p95_s": 3.0},
        "batch": {"wall_s": 120.0}, "standard": {"ttft_s": 10.0}}


def build_engine():
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=512, page_size=8,
                        prefill_buckets=(16,), decode_steps_per_dispatch=4,
                        pace_emission_max_streams=0)
    return LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)


def build_fleet(health_interval_s=0.05, threshold=2):
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, LocalReplica)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    reps = [LocalReplica(f"r{i}", build_engine()) for i in range(2)]
    return EngineFleet(reps, ByteTokenizer(), 8,
                       health_interval_s=health_interval_s,
                       health_fail_threshold=threshold).start()


def prewarm(fleet) -> None:
    from generativeaiexamples_tpu.serving.engine import GenRequest

    reqs = [GenRequest(prompt_ids=[(i * 5) % 250 + 1 for i in range(120)],
                       max_new_tokens=4, priority="batch",
                       session_id=f"warm{i}") for i in range(2)]
    reqs.append(GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=4,
                           priority="latency", session_id="warm-l"))
    for r in reqs:
        fleet.submit(r)
    for r in reqs:
        while not r.stream.get(timeout=600)["finished"]:
            pass


def run_one(kill: bool, failures):
    from generativeaiexamples_tpu.serving.chaos import (
        ChaosEvent, classify, run_chaos_trace)
    from generativeaiexamples_tpu.serving.qos import bursty_trace, goodput

    trace = bursty_trace(seed=11, horizon_s=2.5, latency_rps=3.0,
                         batch_requests=6)
    events = [ChaosEvent(t=0.8, kind="kill")] if kill else []
    fleet = build_fleet()
    try:
        prewarm(fleet)
        results, monkey = run_chaos_trace(fleet, trace, events, seed=3,
                                          timeout_s=120.0)
        snap = fleet.metrics.snapshot()
        lanes = fleet.flight_recorders()
    finally:
        fleet.stop()
    buckets = classify(results)
    good = goodput(results, SLOS)
    if kill:
        if buckets["lost"] != 0:
            failures.append(f"{buckets['lost']} non-mid-stream request(s) "
                            "lost through the kill (requeue must save them)")
        if snap["chaos_injected_kills"] != 1:
            failures.append("chaos_injected_kills="
                            f"{snap['chaos_injected_kills']} (expected 1)")
        if snap["replica_evictions"] < 1:
            failures.append("the killed replica was never evicted")
        chaos_evs = lanes["chaos"].snapshot_events()
        if not any(e["aux"].startswith("kill:") for e in chaos_evs):
            failures.append("chaos flight lane carries no kill event")
    else:
        if buckets["lost"] or buckets["midstream"]:
            failures.append(f"no-fault run had errors: {buckets}")
    return good.get("latency", 0.0), buckets, snap


def zombie_gate(failures):
    """All serving threads must be joined, and no stop-path join may
    have timed out, across everything this smoke started."""
    time.sleep(0.2)
    zombies = [t.name for t in threading.enumerate()
               if t.is_alive() and t.name.startswith(
                   ("llm-engine", "fleet-", "chaos-", "fleet-autoscaler"))]
    if zombies:
        failures.append(f"zombie threads after stop(): {zombies}")
    return zombies


def main() -> int:
    assert jax.default_backend() == "cpu", "smoke is a CPU gate"
    failures: list = []
    # Throwaway replay: the jitted steps are module-level, so the
    # first run pays every XLA compile mid-trace and would depress
    # the baseline the kill run is gated against. Both MEASURED runs
    # start equally warm.
    run_one(kill=False, failures=[])
    base_good, base_buckets, _ = run_one(kill=False, failures=failures)
    kill_good, kill_buckets, snap = run_one(kill=True, failures=failures)
    floor = 0.9 * base_good
    if kill_good < floor:
        failures.append(f"latency goodput through the kill {kill_good:.3f} "
                        f"< 0.9x baseline {base_good:.3f}")
    if snap["stuck_thread_joins"] != 0:
        failures.append(f"stuck_thread_joins={snap['stuck_thread_joins']} "
                        "(a stop-path join timed out)")
    zombies = zombie_gate(failures)
    summary = {
        "goodput_latency_baseline": round(base_good, 3),
        "goodput_latency_kill": round(kill_good, 3),
        "baseline_buckets": base_buckets,
        "kill_buckets": kill_buckets,
        "requeued": snap["router_requeued"],
        "replica_evictions": snap["replica_evictions"],
        "chaos_injected_kills": snap["chaos_injected_kills"],
        "stuck_thread_joins": snap["stuck_thread_joins"],
        "zombies": zombies,
        "failures": failures,
    }
    print(json.dumps(summary))
    if failures:
        print("smoke_chaos: FAILED", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print("smoke_chaos: ok", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
