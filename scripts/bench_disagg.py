"""Disagg bench child: KV page-transfer cost and TTFT-under-prefill-
storm, disaggregated vs colocated. Prints ONE JSON line (the
BENCH_DISAGG keys bench.py merges into its artifact).

Runs on the CPU backend BY DESIGN (bench.py spawns it with
JAX_PLATFORMS=cpu), same rationale as the fleet/QoS/chaos children:
the subject is the serving TOPOLOGY — where prefill compute queues
relative to decode beats, and what a cross-replica page move costs —
not chip throughput, and a TPU bench process has exactly one chip.

Scenarios:

  transfer      one prefill-role -> decode-role page transfer of a
  microbench    BENCH_DISAGG_PROMPT-token prompt, repeated
                BENCH_DISAGG_XFERS times onto fresh decode engines:
                median ms/page (export gather + wire + import
                scatter + radix insert) and serialized bytes/page.

  prefill       BENCH_DISAGG_STORM long prompts (BENCH_DISAGG_STORM_
  storm         PROMPT tokens, chunked prefill) flood the fleet while
                BENCH_DISAGG_SHORTS short latency-tier requests
                arrive on a steady clock. Run three times on
                identical 2-replica fleets — colocated (both mixed),
                disaggregated serialized (roles prefill,decode +
                two-stage plans, the PR-14 shape), and disaggregated
                PIPELINED (disagg_pipeline=True: chunks ship under
                the prefill tail, decode admits early) — reporting
                short-request TTFT p50/p95, the disagg-vs-colocated
                goodput ratio (shorts with TTFT <= BENCH_DISAGG_SLO_S)
                and disagg_transfer_overlap_pct (ms of transfer
                hidden under prefill / total transfer ms; > 0 is the
                pipelining acceptance gate).

  device path   the transfer microbench repeated with
                disagg_device_path=True (both engines' pools live on
                the one CPU device, so mesh.devices_colocated holds):
                disagg_device_path_ms_per_page vs the host-bounce
                disagg_transfer_ms_per_page.

  process       spawn one `python -m generativeaiexamples_tpu.serving`
  spawn         worker (the autoscaler's process-per-replica lane)
                while the storm runs; disagg_spawn_ready_ms is boot ->
                /health, disagg_spawn_ttft_ms a short request served
                by the spawned replica end-to-end. BENCH_DISAGG_SPAWN=0
                skips (the slowest scenario: a full process boot).

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_disagg.py
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

PS = 32


def _pctl(vals, q):
    if not vals:
        return None
    v = sorted(vals)
    return round(v[min(len(v) - 1, int(q * (len(v) - 1)))] * 1e3, 1)


def main() -> int:
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.disagg import (
        KVPageTransfer, serialize_kv_transfer)
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, FleetOps, LocalReplica)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    xfer_prompt = int(os.environ.get("BENCH_DISAGG_PROMPT", "256"))
    n_xfers = int(os.environ.get("BENCH_DISAGG_XFERS", "3"))
    n_storm = int(os.environ.get("BENCH_DISAGG_STORM", "4"))
    storm_prompt = int(os.environ.get("BENCH_DISAGG_STORM_PROMPT", "448"))
    n_shorts = int(os.environ.get("BENCH_DISAGG_SHORTS", "12"))
    short_prompt = int(os.environ.get("BENCH_DISAGG_SHORT_PROMPT", "48"))
    short_gap_s = float(os.environ.get("BENCH_DISAGG_SHORT_GAP_S", "0.15"))
    slo_s = float(os.environ.get("BENCH_DISAGG_SLO_S", "2.0"))

    # bench_fleet's mid-size geometry: XLA compute (GIL-free)
    # dominates, the regime where two in-process replicas model two
    # chips; chunked prefill engages above the 128-token bucket.
    cfg = llama.LlamaConfig(vocab_size=256, dim=256, n_layers=4,
                            n_heads=4, n_kv_heads=2, head_dim=64,
                            mlp_dim=512, max_seq_len=512,
                            tie_embeddings=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=512, page_size=PS,
                        prefill_buckets=(64, 128),
                        decode_steps_per_dispatch=4, prefix_cache=True,
                        pace_emission_max_streams=0)
    tk = ByteTokenizer()

    def engine():
        return LLMEngine(params, cfg, tk, ecfg, use_pallas=False)

    # -- transfer microbench: host bounce, then device path -----------------
    prompt = [(i * 7) % 250 + 1 for i in range(xfer_prompt)]
    src_eng = engine().start()
    list(src_eng.generate_stream(prompt, max_new_tokens=1))  # prefill+cache
    src = LocalReplica("src", src_eng, role="prefill")
    ms_per_page, bytes_per_page, pages_moved = [], None, 0
    dev_ms_per_page, dev_pages = [], 0
    mover = KVPageTransfer()
    dev_ops = FleetOps()
    dev_mover = KVPageTransfer(device_path=True, ops=dev_ops)
    for _ in range(max(1, n_xfers)):
        dst_eng = engine().start()
        dst = LocalReplica("dst", dst_eng, role="decode")
        pages, ms = mover.transfer(src, dst, prompt)
        if pages:
            pages_moved = pages
            ms_per_page.append(ms / pages)
            if bytes_per_page is None:
                codes, scales, n_tok = src.export_kv_pages(prompt)
                payload = serialize_kv_transfer(prompt[:n_tok], codes,
                                                scales)
                bytes_per_page = len(payload) // pages
        dst_eng.stop()
        # Device path onto a FRESH engine (same-device pools: both
        # live on the one CPU backend device, the in-process analog
        # of two chips on one host's ICI domain).
        ddst_eng = engine().start()
        ddst = LocalReplica("ddst", ddst_eng, role="decode")
        pages, ms = dev_mover.transfer(src, ddst, prompt)
        if pages:
            dev_pages = pages
            dev_ms_per_page.append(ms / pages)
        ddst_eng.stop()
    device_fallbacks = dev_ops.disagg_device_fallbacks
    src_eng.stop()

    # -- prefill storm: colocated vs disaggregated --------------------------
    def storm_run(roles, disagg, pipeline=False):
        reps = [LocalReplica(f"r{i}", engine(),
                             role=(roles[i] if roles else "mixed"))
                for i in range(2)]
        fleet = EngineFleet(
            reps, tk, PS, disagg=disagg,
            # Pipelined variant: ship windows of 2 pages as the
            # prefill completes them, admit decode on the early
            # prefix (the tentpole path under measurement).
            disagg_pipeline=pipeline,
            disagg_transfer_chunk_pages=2 if pipeline else 0,
            # Shorts below a page-transfer's worth of prefill serve
            # straight on the decode pool (the DistServe shape).
            disagg_min_prompt_tokens=storm_prompt // 2).start()
        done = []
        lock = threading.Lock()

        def run_req(pids, max_new, prio, ttfts):
            req = GenRequest(prompt_ids=pids, max_new_tokens=max_new,
                             priority=prio)
            fleet.submit(req)
            first = None
            while True:
                ev = req.stream.get(timeout=600)
                if first is None and ev["token_id"] >= 0:
                    first = time.perf_counter() - req.submit_time
                if ev["finished"]:
                    break
            if ttfts is not None and first is not None:
                with lock:
                    ttfts.append(first)

        storm_ids = [[(i * 11 + j) % 250 + 1 for j in range(storm_prompt)]
                     for i in range(n_storm)]
        threads = [threading.Thread(
            target=run_req, args=(ids, 8, "batch", None))
            for ids in storm_ids]
        for t in threads:
            t.start()
        short_ttfts: list = []
        sthreads = []
        for i in range(n_shorts):
            ids = [(i * 13 + j) % 250 + 1 for j in range(short_prompt)]
            st = threading.Thread(target=run_req,
                                  args=(ids, 8, "latency", short_ttfts))
            sthreads.append(st)
            st.start()
            time.sleep(short_gap_s)
        for t in threads + sthreads:
            t.join(timeout=600)
        done = list(short_ttfts)
        snap = fleet.metrics.snapshot()
        fleet.stop()
        good = sum(1 for t in done if t <= slo_s)
        total_ms = snap.get("disagg_transfer_ms", 0.0) or 0.0
        overlap_ms = snap.get("disagg_overlap_ms", 0.0) or 0.0
        return {"ttft_p50_ms": _pctl(done, 0.50),
                "ttft_p95_ms": _pctl(done, 0.95),
                "goodput": round(good / max(1, n_shorts), 3),
                "kv_transfer_pages": snap["kv_transfer_pages"],
                "kv_transfer_chunks": snap.get("kv_transfer_chunks", 0),
                "disagg_plans": snap["router_disagg_plans"],
                "disagg_fallbacks": snap["disagg_fallbacks"],
                "early_admits": snap.get("disagg_early_admits", 0),
                "overlap_pct": (round(overlap_ms / total_ms, 3)
                                if total_ms > 0 else 0.0)}

    colo = storm_run(None, disagg=False)
    dis = storm_run(["prefill", "decode"], disagg=True)
    pipe = storm_run(["prefill", "decode"], disagg=True, pipeline=True)

    # -- process spawn under storm (BENCH_DISAGG_SPAWN=0 skips) -------------
    spawn_ready_ms = spawn_ttft_ms = None
    if os.environ.get("BENCH_DISAGG_SPAWN", "1") != "0":
        from generativeaiexamples_tpu.serving.fleet import (
            spawn_process_replica)

        rep = None

        def timed_req(seed):
            sids = [(j * 3 + seed) % 250 + 1 for j in range(short_prompt)]
            req = GenRequest(prompt_ids=sids, max_new_tokens=4,
                             priority="latency")
            t0 = time.perf_counter()
            rep.submit(req)
            first = None
            while True:
                ev = req.stream.get(timeout=300)
                if first is None and (ev.get("text") or ev["finished"]):
                    first = time.perf_counter() - t0
                if ev["finished"]:
                    break
            return first

        try:
            t0 = time.perf_counter()
            # warm=False: a 1-CPU bench host pays minutes for the full
            # all-buckets warmup; joining cold and compiling on the
            # first (throwaway) request keeps the scenario honest
            # about steady-state TTFT without the boot-long stall.
            rep = spawn_process_replica(
                "bench-spawn", model_size="tiny", warm=False,
                ready_timeout_s=float(os.environ.get(
                    "BENCH_DISAGG_SPAWN_TIMEOUT_S", "120")))
            spawn_ready_ms = round((time.perf_counter() - t0) * 1e3, 1)
            timed_req(0)  # throwaway: first-touch bucket compile
            spawn_ttft_ms = round(timed_req(1) * 1e3, 1)
        except Exception as e:
            spawn_ready_ms = f"error: {type(e).__name__}: {e}"
        finally:
            if rep is not None:
                rep.stop()

    out = {
        "disagg_transfer_pages": pages_moved,
        "disagg_transfer_ms_per_page": (
            round(statistics.median(ms_per_page), 2)
            if ms_per_page else None),
        "disagg_device_path_ms_per_page": (
            round(statistics.median(dev_ms_per_page), 2)
            if dev_ms_per_page else None),
        "disagg_device_path_pages": dev_pages,
        "disagg_device_fallbacks": device_fallbacks,
        "disagg_transfer_bytes_per_page": bytes_per_page,
        "disagg_storm_prompt": storm_prompt,
        "disagg_ttft_storm_p50_ms": dis["ttft_p50_ms"],
        "disagg_ttft_storm_p95_ms": dis["ttft_p95_ms"],
        "colocated_ttft_storm_p50_ms": colo["ttft_p50_ms"],
        "colocated_ttft_storm_p95_ms": colo["ttft_p95_ms"],
        "disagg_goodput": dis["goodput"],
        "colocated_goodput": colo["goodput"],
        "disagg_vs_colocated_goodput": round(
            dis["goodput"] / max(1e-9, colo["goodput"]), 3),
        "disagg_storm_transfer_pages": dis["kv_transfer_pages"],
        "disagg_storm_plans": dis["disagg_plans"],
        "disagg_storm_fallbacks": dis["disagg_fallbacks"],
        # Pipelined prefill-overlap storm (the tentpole): chunks ship
        # under the prefill tail, decode admits on the early prefix.
        "disagg_pipelined_ttft_storm_p50_ms": pipe["ttft_p50_ms"],
        "disagg_pipelined_ttft_storm_p95_ms": pipe["ttft_p95_ms"],
        "disagg_pipelined_goodput": pipe["goodput"],
        "disagg_transfer_chunks": pipe["kv_transfer_chunks"],
        "disagg_early_admits": pipe["early_admits"],
        "disagg_transfer_overlap_pct": pipe["overlap_pct"],
        "disagg_spawn_ready_ms": spawn_ready_ms,
        "disagg_spawn_ttft_ms": spawn_ttft_ms,
        "disagg_cpu_count": os.cpu_count(),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
