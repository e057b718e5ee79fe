"""BENCH_QOS: goodput under SLO on a bursty multi-tenant trace.

The production-traffic gate (ROADMAP item 4): every other serving
scenario pushes a uniform burst through the engine and reads peak
tok/s; this one replays a seeded, heavy-tailed, multi-tenant arrival
trace (one batch-tier tenant floods long jobs at t=0, latency-tier
tenants arrive Poisson-with-bursts on top — serving/qos.py
bursty_trace) twice — FIFO scheduler vs engine.qos weighted-fair
scheduling + prefill preemption — and reports **goodput under SLO**
(the fraction of requests meeting their tier's TTFT / inter-token-gap
/ completion targets) per tier, plus an overload probe of the edge's
429 shedding.

Runs on the CPU backend as a bench.py child (scripts/bench_fleet.py
precedent): the scenario measures SCHEDULING policy, not chip speed —
host threads replaying arrival timestamps need wall-clock fidelity,
not a TPU.

Keys (merged into the bench artifact's extras):
  qos_goodput_latency_tier   latency-tier goodput, QoS scheduler
  qos_goodput_batch_tier     batch-tier goodput, QoS scheduler
  qos_fifo_goodput_baseline  latency-tier goodput, FIFO scheduler
  qos_fifo_goodput_batch     batch-tier goodput, FIFO scheduler
  qos_shed_rate              shed fraction in the edge overload probe
  qos_preemptions            long prefills paused for latency TTFT
  qos_latency_ttft_p95_ms / qos_fifo_ttft_p95_ms, qos_slo_ttft_ms,
  qos_trace_requests, qos_shed_reject_ms (429 latency — shed must be
  fast, not a hang)

Env knobs: BENCH_QOS_SEED / _HORIZON_S / _BATCH_REQUESTS /
_LATENCY_RPS / _SLO_TTFT_MS / _GEN (batch-tier output cap scale).

Usage: JAX_PLATFORMS=cpu python scripts/bench_qos.py
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def _engine(qos: bool):
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=512, page_size=8,
                        prefill_buckets=(16,), decode_steps_per_dispatch=4,
                        pace_emission_max_streams=0,
                        qos=qos)
    return LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                     use_pallas=False).start()


def _prewarm(eng) -> None:
    """Run one long and a few short requests to completion so XLA
    compiles land BEFORE the measured replay — both modes pay the same
    warm cost, neither pays it mid-trace."""
    from generativeaiexamples_tpu.serving.engine import GenRequest

    reqs = [GenRequest(prompt_ids=[(i * 5) % 250 + 1 for i in range(180)],
                       max_new_tokens=4, priority="batch"),
            GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=4,
                       priority="latency"),
            GenRequest(prompt_ids=[9, 8], max_new_tokens=4)]
    for r in reqs:
        eng.submit(r)
    for r in reqs:
        while True:
            if r.stream.get(timeout=600)["finished"]:
                break


def _run_mode(qos: bool, trace, slos):
    from generativeaiexamples_tpu.serving.qos import (
        goodput, run_trace_on_engine)

    eng = _engine(qos)
    try:
        _prewarm(eng)
        results = run_trace_on_engine(eng, trace, seed=1)
        snap = eng.metrics.snapshot()
    finally:
        eng.stop()
    lat_ttfts = sorted(r["ttft_s"] for r in results
                       if r["tier"] == "latency" and r["ttft_s"] is not None)
    p95 = (lat_ttfts[int(0.95 * (len(lat_ttfts) - 1))] * 1e3
           if lat_ttfts else None)
    return goodput(results, slos), p95, snap, results


def _overload_probe():
    """Edge shedding behavior: a burst past the latency bound must shed
    fast (429 path, serving/qos.py EdgeAdmission) — not hang. Measured
    engine-less: the edge decision is the thing under test."""
    from generativeaiexamples_tpu.serving.qos import EdgeAdmission

    edge = EdgeAdmission(bounds={"latency": 2}, retry_after_s=1.0,
                        enabled=True)
    offered, shed, reject_ms = 10, 0, 0.0
    for _ in range(offered):
        t0 = time.perf_counter()
        if edge.try_admit("latency") is not None:
            shed += 1
            reject_ms = max(reject_ms,
                            (time.perf_counter() - t0) * 1e3)
    return shed / offered, reject_ms


def main() -> None:
    from generativeaiexamples_tpu.serving.qos import bursty_trace

    seed = int(os.environ.get("BENCH_QOS_SEED", "7"))
    horizon = float(os.environ.get("BENCH_QOS_HORIZON_S", "5"))
    batch_n = int(os.environ.get("BENCH_QOS_BATCH_REQUESTS", "10"))
    rps = float(os.environ.get("BENCH_QOS_LATENCY_RPS", "2"))
    slo_ttft_ms = float(os.environ.get("BENCH_QOS_SLO_TTFT_MS", "1500"))

    trace = bursty_trace(seed=seed, horizon_s=horizon, latency_rps=rps,
                         batch_requests=batch_n)
    slos = {"latency": {"ttft_s": slo_ttft_ms / 1e3, "gap_p95_s": 2.0},
            "batch": {"wall_s": 120.0},
            "standard": {"ttft_s": 10.0}}

    fifo_good, fifo_p95, _, _ = _run_mode(False, trace, slos)
    qos_good, qos_p95, qos_snap, _ = _run_mode(True, trace, slos)
    shed_rate, reject_ms = _overload_probe()

    out = {
        "qos_goodput_latency_tier": round(qos_good.get("latency", 0.0), 3),
        "qos_goodput_batch_tier": round(qos_good.get("batch", 0.0), 3),
        "qos_fifo_goodput_baseline": round(fifo_good.get("latency", 0.0), 3),
        "qos_fifo_goodput_batch": round(fifo_good.get("batch", 0.0), 3),
        "qos_shed_rate": round(shed_rate, 3),
        "qos_preemptions": qos_snap["qos_preemptions"],
        "qos_latency_ttft_p95_ms": round(qos_p95, 1) if qos_p95 else None,
        "qos_fifo_ttft_p95_ms": round(fifo_p95, 1) if fifo_p95 else None,
        "qos_slo_ttft_ms": slo_ttft_ms,
        "qos_trace_requests": len(trace),
        "qos_shed_reject_ms": round(reject_ms, 2),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
