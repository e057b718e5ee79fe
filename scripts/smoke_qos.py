"""QoS smoke: the SLO-aware scheduler's two core contracts, CPU-grade.

  (a) goodput: on a canned bursty multi-tenant trace (batch-tier flood
      + latency-tier Poisson arrivals, serving/qos.py bursty_trace),
      the weighted-fair scheduler's latency-tier goodput-under-SLO
      strictly beats the FIFO baseline while batch-tier goodput stays
      within 10% — priority must not become starvation;
  (b) shedding: past the per-tier edge bound, a request gets a FAST
      429 with Retry-After through the real OpenAI server — overload
      is a rejection, never a hang.

CI-grade: exits nonzero on any violation, prints one JSON summary.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_qos.py
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def build_engine(qos: bool):
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
    return LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)


def prewarm(eng) -> None:
    from generativeaiexamples_tpu.serving.engine import GenRequest

    reqs = [GenRequest(prompt_ids=[(i * 5) % 250 + 1 for i in range(120)],
                       max_new_tokens=4, priority="batch"),
            GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=4,
                       priority="latency")]
    for r in reqs:
        eng.submit(r)
    for r in reqs:
        while not r.stream.get(timeout=600)["finished"]:
            pass


def goodput_gate(failures):
    from generativeaiexamples_tpu.serving.qos import (
        bursty_trace, goodput, run_trace_on_engine)

    trace = bursty_trace(seed=7, horizon_s=4.0, latency_rps=2.0,
                         batch_requests=10)
    slos = {"latency": {"ttft_s": 1.5, "gap_p95_s": 2.0},
            "batch": {"wall_s": 120.0}, "standard": {"ttft_s": 10.0}}
    out = {}
    p95 = {}
    for name, qos in (("fifo", False), ("qos", True)):
        eng = build_engine(qos).start()
        try:
            prewarm(eng)
            res = run_trace_on_engine(eng, trace, seed=2)
            out[name] = goodput(res, slos)
            ttfts = sorted(r["ttft_s"] for r in res
                           if r["tier"] == "latency"
                           and r["ttft_s"] is not None)
            p95[name] = (ttfts[int(0.95 * (len(ttfts) - 1))]
                         if ttfts else float("inf"))
            if qos:
                out["preemptions"] = \
                    eng.metrics.snapshot()["qos_preemptions"]
        finally:
            eng.stop()
    lat_q, lat_f = out["qos"].get("latency", 0), out["fifo"].get("latency", 0)
    bat_q, bat_f = out["qos"].get("batch", 0), out["fifo"].get("batch", 0)
    # Strict beat is the headline claim, but a host fast enough that
    # FIFO also meets every SLO (both 1.0) is not a regression — then
    # the gate falls back to TTFT: QoS must not be slower than FIFO
    # beyond noise. A genuine scheduling regression fails both prongs.
    if not (lat_q > lat_f
            or (lat_q == lat_f == 1.0
                and p95["qos"] <= p95["fifo"] * 1.5 + 0.05)):
        failures.append(
            f"latency goodput: qos {lat_q:.3f} does not beat fifo "
            f"{lat_f:.3f} (ttft p95 qos {p95['qos']:.3f}s vs fifo "
            f"{p95['fifo']:.3f}s)")
    if bat_q < bat_f - 0.10:
        failures.append(f"batch goodput collapsed under qos: {bat_q:.3f} "
                        f"vs fifo {bat_f:.3f}")
    return {"goodput_latency_qos": lat_q, "goodput_latency_fifo": lat_f,
            "goodput_batch_qos": bat_q, "goodput_batch_fifo": bat_f,
            "latency_ttft_p95_s": {k: round(v, 3) for k, v in p95.items()},
            "qos_preemptions": out.get("preemptions", 0)}


def shed_gate(failures):
    """A request past the latency bound must get a fast 429 +
    Retry-After from the real server while the bound-holding stream is
    still live."""
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.config.schema import ServingConfig
    from generativeaiexamples_tpu.serving.openai_server import OpenAIServer

    eng = build_engine(qos=False).start()

    async def body():
        srv = OpenAIServer(eng, model_name="tiny", serving_cfg=ServingConfig(
            qos_edge=True, qos_bound_latency=1, qos_retry_after_s=2.0))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            resp1 = await client.post("/v1/completions", json={
                "prompt": [5] * 4, "max_tokens": 64, "stream": True,
                "priority": "latency"})
            await resp1.content.readline()  # admitted: holds the bound
            t0 = time.perf_counter()
            resp2 = await client.post("/v1/completions", json={
                "prompt": [6] * 4, "max_tokens": 4, "priority": "latency"})
            reject_ms = (time.perf_counter() - t0) * 1e3
            status = resp2.status
            retry_after = resp2.headers.get("Retry-After")
            await resp2.release()
            async for _ in resp1.content:  # drain the held stream
                pass
            snap = await (await client.get("/metrics")).json()
            return status, retry_after, reject_ms, snap
        finally:
            await client.close()

    try:
        status, retry_after, reject_ms, snap = asyncio.run(body())
    finally:
        eng.stop()
    if status != 429:
        failures.append(f"over-bound request got {status}, wanted 429")
    if not retry_after:
        failures.append("429 carried no Retry-After header")
    if reject_ms > 2000:
        failures.append(f"shed took {reject_ms:.0f} ms — a hang, not a "
                        "rejection")
    if snap.get("qos_shed_latency", 0) < 1:
        failures.append(f"/metrics qos_shed_latency="
                        f"{snap.get('qos_shed_latency')} (expected >= 1)")
    return {"shed_status": status, "retry_after": retry_after,
            "shed_reject_ms": round(reject_ms, 1),
            "qos_shed_latency": snap.get("qos_shed_latency")}


def main() -> int:
    assert jax.default_backend() == "cpu", "smoke is a CPU gate"
    failures = []
    summary = goodput_gate(failures)
    summary.update(shed_gate(failures))
    summary["failures"] = failures
    print(json.dumps(summary))
    if failures:
        print("smoke_qos: FAILED", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print("smoke_qos: ok", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
