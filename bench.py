"""Benchmark: serving-engine decode throughput on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

Headline metric: continuous-batching decode throughput (tokens/sec/chip)
for the llama3-8b geometry, weight-only int8 (the deployment config for
a 16 GB v5e chip), random-init weights (no weight downloads in this
environment — throughput is weight-value-independent).

Baseline: BASELINE.json north star >= 2000 tokens/sec/chip (the
reference publishes no numbers — BASELINE.md).

Env knobs: BENCH_MODEL (8b|1b|tiny), BENCH_BATCH, BENCH_PROMPT,
BENCH_GEN, BENCH_PAGE, BENCH_QUANT (0|1), BENCH_KV_DTYPE, BENCH_SPEC,
BENCH_TREE (tree-draft branches; 0 = linear chain), BENCH_PLANS
(composable step plans + fused_prefill on the decode engine; 0 = the
lane-exclusive r05 config), BENCH_REPEAT (headline burst repetitions,
default 3; median reported — same as the --repeat N flag),
BENCH_K, BENCH_PIPELINE, BENCH_LONGCTX (0 skips),
BENCH_FUSED (0 skips),
BENCH_PREFIX (0 skips), BENCH_ENCODERS (0 skips), BENCH_KERNELS
(0 skips; BENCH_KERNELS_ITERS tunes the kernel roofline microbench,
scripts/bench_kernels.py),
BENCH_ANN (0 skips;
BENCH_ANN_N / _DIM / _NLIST / _NPROBE tune the corpus and index),
BENCH_ANN_TIERED (0 skips; BENCH_ANN_TIERED_N / _DIM / _NLIST /
_NPROBE / _HBM_MB / _WRITE_ROWS tune the capacity corpus, the forced
HBM budget and the concurrent-writer volume — N defaults to 10M on
TPU, 200k elsewhere),
BENCH_CONCURRENT (0 skips; BENCH_CONCURRENT_THREADS / _REQS / _N
tune caller count, requests per caller, corpus size),
BENCH_FLEET (0 skips; BENCH_FLEET_REPLICAS / _REQS / _THREADS /
_PROMPT / _GEN / _CONVS tune replica count and the burst /
conversation-replay workloads — the scenario runs in a child process
pinned to the CPU backend, see scripts/bench_fleet.py),
BENCH_QOS (0 skips; BENCH_QOS_SEED / _HORIZON_S / _BATCH_REQUESTS /
_LATENCY_RPS / _SLO_TTFT_MS tune the replayed bursty multi-tenant
trace and the latency-tier SLO — also a CPU-backend child process,
see scripts/bench_qos.py),
BENCH_CHAOS (0 skips; BENCH_CHAOS_SEED / _HORIZON_S /
_BATCH_REQUESTS / _LATENCY_RPS / _SLO_TTFT_MS / _KILL_T tune the
replayed trace, the SLO, and when the replica kill fires — a
CPU-backend child process, see scripts/bench_chaos.py),
BENCH_DISAGG (0 skips; BENCH_DISAGG_PROMPT / _XFERS / _STORM /
_STORM_PROMPT / _SHORTS / _SHORT_PROMPT / _SHORT_GAP_S / _SLO_S tune
the transfer microbench and the prefill-storm workload,
BENCH_DISAGG_SPAWN=0 skips the process-replica spawn scenario — a
CPU-backend child process, see scripts/bench_disagg.py).

Flags: --repeat N runs the headline decode burst N times and reports
the MEDIAN as the headline value, with per-run values and spread under
extras (headline_runs_tok_s / headline_spread_tok_s) — single-run
noise can no longer masquerade as a regression. The headline's
measurement recipe is pinned by THROUGHPUT_PROVENANCE below and
asserted into every run's artifact (r04 lacked the provenance string,
r05 added it mid-flight; it is now a constant, identical in all runs).

The r05 official config is BENCH_SPEC=1 BENCH_TREE=0 BENCH_PLANS=0;
the default now enables step plans + fused_prefill + tree drafts
(k=3, 4 branches) — the composed lattice whose ceiling the tree
verify raises. On TPU the tree path now dispatches the Pallas
tree-attention kernels (bf16 + int8 twins,
serving/paged_attention_tree.py; ENGINE_TREE_KERNEL=0 reverts to the
XLA gather route for A/B reads).

Scenario output keys (under "extras"):
  long-context:  ttft_prompt2k_ms, ttft_prompt8k_ms,
                 prefill_tok_per_sec_{2k,8k}, ttft_8k_under_load_ms,
                 short_stream_gap_p95_{before,during_8k_prefill}_ms
  fused dispatch: fused_gap_p95_during_8k_prefill_ms,
                 fused_vs_unfused_gap_ratio, fused_ttft_8k_under_load_ms,
                 fused_gap_p95_before_ms, fused_steps,
                 fused_prefill_tokens, prefill_stall_beats (the same
                 8k-prefill-under-load workload as long-context with
                 engine.fused_prefill on — prefill chunks ride inside
                 decode dispatches, serving/engine_model.py
                 fused_decode_prefill_step; BENCH_FUSED=0 skips)
  prefix cache:  prefix_ttft_cold_ms, prefix_ttft_warm_ms,
                 prefix_warm_speedup, prefix_hits, prefix_miss,
                 prefix_hit_tokens (warm-prefix vs cold TTFT through
                 serving/prefix_cache.py — the RAG repeated-prefix
                 serving shape; BENCH_PREFIX=0 skips)
  KV tiering:    kv_sessions_resident_vs_hbm_only,
                 kv_warm_resume_ttft_ms, kv_cold_resume_ttft_ms,
                 kv_promote_ms_per_page, kv_sessions, kv_demotions,
                 kv_promotions, kv_host_pages, kv_spill_pages
                 (session KV pager, serving/kv_pager.py:
                 BENCH_KV_SESSIONS distinct 2k-prompt sessions served
                 through a pool sized for ~2, prefix pages demoted
                 HBM -> host RAM -> disk with the radix tree as the
                 pager's index; warm resume TTFT = promote matched
                 pages back with one scatter + a 1-token suffix
                 forward, vs a cold full prefill; promote ms/page from
                 a standalone pager microbench. BENCH_KV_TIER=0 skips)
  encoders:      embed_docs_per_sec, embed_queries_per_sec,
                 rerank_pairs_per_sec
  kernel roofline: kern_<kernel>_ms, kern_<kernel>_gb_s,
                 kern_<kernel>_gflop_s, kern_<kernel>_hbm_util,
                 kern_<kernel>_mxu_util for kernels paged_bf16,
                 paged_int8, tree_bf16, tree_int8, tree_xla_ref,
                 int8_matmul, flash_prefill, plus kern_backend,
                 kern_device_kind and the kern_peak_* denominators
                 (per-kernel achieved vs peak bytes/s and FLOP/s from
                 scripts/bench_kernels.py — decode-attention kernels
                 are HBM-bound, so kern_*_hbm_util is their headline;
                 tree_xla_ref times the gather route the tree kernels
                 replace at the same shape. int8/tree entries are
                 TPU-only; BENCH_KERNELS=0 skips.
                 `bench_kernels.py --verify` is the kernel-parity
                 entry point, gated on CPU by smoke_kernels.py)
  ANN retrieval: ann_search_qps, ann_vs_flat_speedup, ann_recall_at_4,
                 ann_batch_qps, ann_int8_qps, ann_scanned_rows_per_query,
                 flat_search_qps (IVF vs exact brute-force MIPS through
                 TPUVectorStore at BENCH_ANN_N=100k synthetic clustered
                 vectors — the ops/ivf.py two-stage index;
                 BENCH_ANN=0 skips)
  tiered ANN:    tiered_recall_at_4, tiered_search_qps,
                 tiered_search_p50_ms, tiered_search_p99_ms,
                 tiered_hbm_resident_fraction, tiered_pager_hit_rate,
                 tiered_promotions, tiered_demotions,
                 tiered_compactions, tiered_ingest_rows_per_s,
                 tiered_ann_n, tiered_hbm_budget_mb (demand-paged
                 tiered IVF through TPUVectorStore at N=10M synthetic
                 vectors — hot partitions in HBM under a budget
                 SMALLER than the corpus, warm host RAM + mmap'd disk
                 spill behind it, ops/tiered.py — searched while a
                 concurrent writer streams rows into the warm tier;
                 the capacity bench. BENCH_ANN_TIERED=0 skips)
  concurrent:    concurrent_rag_qps, microbatch_occupancy,
                 embed_p99_wait_ms, serialized_rag_qps,
                 microbatch_vs_serial_speedup, microbatch_dispatches_saved
                 (16 concurrent embed+search RAG front-halves through
                 the serving/batcher.py cross-request micro-batcher vs
                 the same load with the batcher off — the Triton
                 dynamic-batcher role; BENCH_CONCURRENT=0 skips)
  serving fleet: fleet_single_tok_s, fleet_agg_tok_s, fleet_speedup,
                 fleet_qps_single, fleet_qps, fleet_ttft_p99_1rep_ms,
                 fleet_ttft_p99_ms, fleet_router_hit_rate,
                 fleet_hit_tokens, fleet_cold_ttft_ms,
                 fleet_warm_ttft_ms, fleet_replicas, fleet_cpu_count
                 (uniform burst through 1 engine vs
                 BENCH_FLEET_REPLICAS emulated replicas behind the
                 prefix-locality router, + a two-turn conversation
                 replay for router hit-rate and warm-vs-cold TTFT —
                 serving/fleet.py + serving/router.py. Runs as a CPU-
                 backend child process: replica scaling needs host
                 cores, not a second chip; on a 1-core container
                 fleet_speedup honestly reads contention, keyed by
                 fleet_cpu_count. BENCH_FLEET=0 skips)
  flight recorder: flight_overhead_pct, flight_on_tok_s,
                 flight_off_tok_s (the always-on flight recorder's
                 cost pin: one extra headline-shaped burst with the
                 recorder toggled OFF at runtime vs one with it back
                 ON, serving/flight.py — the recorder defaults ON, so
                 the headline itself already includes it; this extra
                 proves the inclusion is free. BENCH_FLIGHT=0 skips)
                 + from the fused scenario: flight_timeline_path (a
                 Perfetto-loadable Chrome-trace artifact under build/),
                 flight_attributed_pct and flight_top_gap_causes
                 (scripts/analyze_timeline.py stall attribution over
                 the fused run — device-busy / host-gap / idle + named
                 causes summing to ~100% of wall)
  QoS goodput:   qos_goodput_latency_tier, qos_goodput_batch_tier,
                 qos_shed_rate, qos_fifo_goodput_baseline,
                 qos_preemptions, qos_fifo_goodput_batch,
                 qos_latency_ttft_p95_ms, qos_fifo_ttft_p95_ms,
                 qos_slo_ttft_ms, qos_trace_requests,
                 qos_shed_reject_ms (goodput under SLO — the fraction
                 of requests meeting per-tier TTFT / gap / completion
                 targets — on a seeded bursty multi-tenant trace
                 (batch-tier flood + latency-tier Poisson arrivals,
                 serving/qos.py) replayed against the FIFO scheduler
                 vs engine.qos weighted-fair scheduling + prefill
                 preemption, plus the edge 429-shedding probe; the
                 production-traffic gate. Runs as a CPU-backend child
                 (scripts/bench_qos.py) — it measures scheduling
                 policy under wall-clock arrivals, not chip speed.
                 BENCH_QOS=0 skips)
  chaos / elastic fleet: chaos_goodput_baseline, chaos_goodput_kill,
                 chaos_kill_goodput_ratio (the goodput FLOOR gate:
                 >= 0.9 with a replica killed mid-burst),
                 chaos_kill_lost (must be 0 — every non-mid-stream
                 request survives via requeue), chaos_kill_midstream,
                 chaos_kill_requeued, chaos_upgrade_failed_streams /
                 chaos_upgrade_errors (must be 0 — a rolling engine
                 upgrade across the fleet drops nothing),
                 chaos_upgrade_replicas_rolled, chaos_upgrade_wall_s,
                 chaos_upgrade_goodput, chaos_upgrade_rolls,
                 chaos_scaleup_events, chaos_scaleup_goodput,
                 chaos_scaleup_active_after,
                 chaos_timeline_fleet_events, chaos_trace_requests,
                 chaos_slo_ttft_ms (the same seeded bursty trace
                 replayed through a 2-replica fleet with seeded fault
                 injection — serving/chaos.py kill mid-burst,
                 EngineFleet.rolling_upgrade under live traffic, and
                 a 1-replica fleet + serving/autoscaler.py under a
                 sustained burst, scale events visible on the
                 /debug/timeline control lanes. CPU-backend child
                 (scripts/bench_chaos.py). BENCH_CHAOS=0 skips)
  BENCH_DISAGG   disagg_transfer_ms_per_page / _bytes_per_page /
                 disagg_device_path_ms_per_page (the same microbench
                 over the device-to-device fast path — no
                 serialization, no host bounce) /
                 disagg_ttft_storm_p95_ms vs
                 colocated_ttft_storm_p95_ms /
                 disagg_vs_colocated_goodput /
                 disagg_pipelined_ttft_storm_p50_ms / _p95_ms /
                 disagg_transfer_chunks / disagg_early_admits /
                 disagg_transfer_overlap_pct (share of transfer wall
                 time hidden under the prefill tail; > 0 = the
                 pipelined chunk-ship path engaged) /
                 disagg_spawn_ready_ms / disagg_spawn_ttft_ms (one
                 process-per-replica worker spawned and served
                 through, the autoscaler's process lane;
                 BENCH_DISAGG_SPAWN=0 skips just this) — a
                 prefill-role -> decode-role KV page transfer
                 microbench (host bounce then device path), then
                 short latency-tier requests timed while long chunked
                 prefills storm a 2-replica fleet — colocated vs
                 serialized two-stage vs pipelined two-stage plans,
                 serving/disagg.py. CPU-backend child
                 (scripts/bench_disagg.py). BENCH_DISAGG=0 skips)

`python bench.py --help` prints this header and exits.

Sibling tooling (same checkout):
  scripts/smoke_prefix_cache.py / smoke_ann.py / smoke_tiered_ann.py /
  smoke_microbatch.py / smoke_fused_step.py / smoke_plan_step.py /
  smoke_router.py / smoke_kv_pager.py / smoke_flight.py /
  smoke_chaos.py / smoke_disagg.py
      targeted CPU smoke gates for the serving subsystems
  scripts/analyze_timeline.py build/timeline_fused.json
      stall attribution over a /debug/timeline (or bench) artifact:
      device-busy / host-gap / idle split + named top gap causes
  scripts/bench_fleet.py
      the fleet scenario as a standalone CPU tool (multi-replica
      aggregate throughput + router hit-rate)
  python -m generativeaiexamples_tpu.lint generativeaiexamples_tpu/
      graftlint static analysis (trace purity, lock discipline +
      cross-thread races, thread hygiene, call-graph-inferred hot-path
      host-sync, atomic persistence, metrics contract, config drift;
      docs/static_analysis.md) — also via scripts/lint.py [--ruff |
      --changed], with --explain-hot-path <func> for the hot-set chain
  scripts/ci_checks.sh
      the full check pipeline: graftlint (+ SARIF artifact, stale-
      baseline gate) + ruff + config-docs drift + tier-1 pytest
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# The decode headline's PINNED measurement recipe: emitted verbatim in
# every artifact and asserted below — any provenance drift (the
# r04-vs-r05 2866.9-vs-2439.5 readability gap) now fails the run
# instead of silently changing what the number means.
THROUGHPUT_PROVENANCE = (
    "headline value = median over --repeat runs of total_tokens/wall "
    "for the full decode burst (fixed window: the engine rate-gauge "
    "window is reset at burst start and the run drains completely — "
    "all worker threads joined — before wall stops; includes prefill "
    "ramp + drain); engine_metrics.tokens_per_sec = engine sliding-"
    "window gauge over the final run's emission events only — expected "
    "to read slightly above the headline")


def main() -> None:
    if "--help" in sys.argv or "-h" in sys.argv:
        print(__doc__)
        return
    # Default 3: the headline in every artifact — including the plain
    # `python bench.py` the round driver runs — is a median, so one
    # noisy burst can't move the official number (the r04-vs-r05 gap).
    # Parsed BEFORE any device work so a malformed flag fails fast,
    # not with an IndexError after the multi-minute warmup.
    repeat = int(os.environ.get("BENCH_REPEAT", "3"))
    if "--repeat" in sys.argv:
        i = sys.argv.index("--repeat")
        if i + 1 >= len(sys.argv) or not sys.argv[i + 1].isdigit():
            sys.exit("usage: bench.py [--repeat N]  (N a positive int)")
        repeat = int(sys.argv[i + 1])
    repeat = max(1, repeat)
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    setup_compile_cache()
    model = os.environ.get("BENCH_MODEL", "8b")
    # Deployment config for a 16 GB v5e chip (ENGINEERING_NOTES r3):
    # int8 weights + fused int8 KV pool -> B=128 fits; page 128 is the
    # int8 kernel's DMA-alignment requirement.
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    prompt_len = int(os.environ.get("BENCH_PROMPT", "128"))
    gen = int(os.environ.get("BENCH_GEN", "128"))
    page = int(os.environ.get("BENCH_PAGE", "128"))

    cfg = {"8b": llama.LlamaConfig.llama3_8b,
           "1b": llama.LlamaConfig.llama3_2_1b,
           "tiny": llama.LlamaConfig.tiny}[model]()
    # Default: int8 for 8b (the 16 GB HBM deployment config);
    # BENCH_QUANT=0/1 overrides (e.g. bf16-vs-int8 bandwidth probes).
    # Strict parse: "true"-style values silently meaning bf16 would OOM
    # an 8b bench on a 16 GB chip.
    qv = os.environ.get("BENCH_QUANT", "")
    try:
        quantize = {"": model == "8b", "0": False, "1": True}[qv]
    except KeyError:
        raise SystemExit(f"BENCH_QUANT must be '0' or '1', got {qv!r}")
    t0 = time.perf_counter()
    # Weights drawn ON DEVICE from a seed, int8 leaves directly as int8:
    # throughput does not depend on weight values.
    params = llama.init_params_on_device(cfg, quantize=quantize)
    jax.block_until_ready(params)
    print(f"[bench] params ready in {time.perf_counter()-t0:.1f}s "
          f"(backend={jax.default_backend()}, quant={quantize})",
          file=sys.stderr)

    # Greedy self-speculative decoding is part of the deployment config
    # (linear-chain history: k=1 measured fastest at 2769.6 vs 2572.7
    # tok/s non-spec; k=2 2714.9, k=3 2462.8 — linear acceptance on
    # this workload ~1.1-1.6 committed tokens/verify step, i.e. close
    # to the k=1 ceiling of 2.0). Tree drafts raise that ceiling:
    # BENCH_TREE branches x BENCH_SPEC depth verify in one widened
    # step, so deeper k pays off again. BENCH_SPEC=1 BENCH_TREE=0
    # BENCH_PLANS=0 reverts to the r05 official config.
    spec_k = int(os.environ.get("BENCH_SPEC", "3"))
    tree = int(os.environ.get("BENCH_TREE", "4")) if spec_k else 0
    plans = os.environ.get("BENCH_PLANS", "1") != "0"
    k_steps = int(os.environ.get("BENCH_K", "8"))
    depth = int(os.environ.get("BENCH_PIPELINE", "2"))
    # Page headroom for the worst-case in-flight speculative overshoot
    # (depth blocks x K steps x (k+1) commit positions, plus the tree
    # lattice's per-step scratch nodes) so end-of-request slots never
    # starve on page capacity and under-generate.
    max_seq = prompt_len + gen + page + depth * (
        k_steps * (spec_k + 1) + max(1, tree) * spec_k)
    ecfg = EngineConfig(max_batch_size=batch, max_seq_len=max_seq,
                        page_size=page, prefill_buckets=(prompt_len,),
                        kv_dtype=os.environ.get("BENCH_KV_DTYPE", "int8"),
                        decode_steps_per_dispatch=k_steps,
                        pipeline_depth=depth,
                        speculative_k=spec_k,
                        speculative_tree_branches=tree,
                        # "spec+fused both enabled": the headline
                        # engine runs the composed-plan config even
                        # though the burst itself has no long prompts
                        # to fuse — the lattice must not cost idle-path
                        # throughput.
                        step_plans=plans,
                        fused_prefill=plans)
    # Precompile EVERY (bucket, group-size) prefill variant and the
    # decode K-buckets — mid-traffic compiles would otherwise stall the
    # staggered-arrival measurement by tens of seconds.
    t0 = time.perf_counter()
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg)
    eng.warmup()
    eng.start()
    prompt = list(range(2, 2 + prompt_len))
    list(eng.generate_stream(prompt, max_new_tokens=4))  # e2e smoke
    print(f"[bench] warmup done in {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    lock = threading.Lock()

    def headline_burst():
        """ONE full-batch burst in the pinned headline shape: every
        worker streams `gen` tokens and records its own TTFT; returns
        ([(n_tokens, first_s)], wall_s). The flight-recorder overhead
        extra reuses this exact function, so the on/off pair measures
        the same burst the headline does — two hand-rolled twins
        would drift."""
        results = []

        def worker():
            n = 0
            first = None
            start = time.perf_counter()
            for ev in eng.generate_stream(prompt, max_new_tokens=gen):
                if ev["token_id"] >= 0:
                    if first is None:
                        first = time.perf_counter() - start
                    n += 1
            with lock:
                results.append((n, first))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker) for _ in range(batch)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results, time.perf_counter() - t0

    tps_runs = []
    wall_runs = []
    ttfts = []
    for run_i in range(repeat):
        # Phase boundary (part of the PINNED provenance): the sliding-
        # window gauge must cover ONLY the burst (the idle gap after
        # the warmup smoke otherwise stretches its span and under-
        # reads ~8% — r4 VERDICT weak #6), and wall stops only after
        # every worker drained its stream.
        eng.metrics.reset_window()
        results, wall = headline_burst()
        total_tokens = sum(n for n, _ in results)
        tps_runs.append(total_tokens / wall)
        wall_runs.append(wall)
        if run_i == 0:
            ttfts = sorted(f for _, f in results if f is not None)
        print(f"[bench] burst run {run_i + 1}/{repeat}: "
              f"{total_tokens / wall:.1f} tok/s over {wall:.2f}s",
              file=sys.stderr)
    # Headline = MEDIAN over the repeat runs of total_tokens / wall
    # (job throughput: includes the prefill ramp and final drain).
    # engine_metrics.tokens_per_sec = the engine's live sliding-window
    # gauge over the final burst (emission-event span only) — reads
    # slightly higher by design. See THROUGHPUT_PROVENANCE.
    import statistics

    snap = eng.metrics.snapshot()

    # -- flight-recorder overhead pin (ISSUE 12): the recorder is ON
    # by default, so every headline run above already paid it. One
    # extra headline-shaped burst with the recorder toggled OFF at
    # runtime, then one with it back ON (paired — same engine, same
    # compile state, adjacent in time), reports what the always-on
    # default costs. smoke_flight.py asserts the <= 1% bound on CPU;
    # here the measured number simply rides the artifact.
    flight_stats = {}
    if os.environ.get("BENCH_FLIGHT", "1") != "0":
        def _flight_tok_s() -> float:
            results, wall = headline_burst()
            return sum(n for n, _ in results) / wall

        eng.flight.set_enabled(False)
        off_tps = _flight_tok_s()
        eng.flight.set_enabled(True)
        on_tps = _flight_tok_s()
        flight_stats = {
            "flight_off_tok_s": round(off_tps, 1),
            "flight_on_tok_s": round(on_tps, 1),
            "flight_overhead_pct": round(
                (off_tps - on_tps) / off_tps * 100.0, 2) if off_tps
            else None,
        }

    # TTFT under REALISTIC load: 16 requests arriving staggered over
    # ~2 s (the VERDICT r1 bar is p50 <= 300 ms under 16-way load; the
    # full-batch burst above is the worst case, not the serving case).
    stag_results = []
    stag_lock = threading.Lock()

    def stag_worker(delay):
        time.sleep(delay)
        start = time.perf_counter()
        first = None
        # Consume the WHOLE stream: overlapping decodes are the load,
        # and full consumption drains the engine before the idle
        # single-request measurement below.
        for ev in eng.generate_stream(prompt, max_new_tokens=32):
            if ev["token_id"] >= 0 and first is None:
                first = time.perf_counter() - start
        with stag_lock:
            stag_results.append(first)

    n_stag = 16
    stag_threads = [threading.Thread(target=stag_worker,
                                     args=(i * 2.0 / n_stag,))
                    for i in range(n_stag)]
    for t in stag_threads:
        t.start()
    for t in stag_threads:
        t.join()
    stag_results = sorted(t for t in stag_results if t is not None)

    # Single-request TTFT against the warm, otherwise-idle engine (the
    # burst TTFT above is the worst case: all `batch` prefills queue at
    # once). This is the number comparable to the reference's per-query
    # latency posture.
    single_ttfts = []
    for _ in range(8):
        t0 = time.perf_counter()
        got_first = False
        for ev in eng.generate_stream(prompt, max_new_tokens=2):
            if ev["token_id"] >= 0 and not got_first:
                single_ttfts.append(time.perf_counter() - t0)
                got_first = True
            if ev["finished"]:
                break
    single_ttfts.sort()
    eng.stop()

    # -- long-context on hardware (VERDICT r3 weak #5): TTFT vs prompt
    # length through chunked prefill, prefill tok/s, and the pacing
    # claim — live streams' inter-token cadence while an 8k prefill
    # runs. Needs a big-context pool, so the main engine is torn down
    # first (its pool + the long pool together would not fit).
    longctx_stats = {}
    if os.environ.get("BENCH_LONGCTX", "1") != "0":
        import gc

        eng = None
        gc.collect()
        try:
            longctx_stats = _bench_longctx(params, cfg)
        except Exception as e:
            longctx_stats = {"longctx_error": f"{type(e).__name__}: {e}"}

    # -- fused prefill+decode dispatch (ISSUE 5 tentpole): the same
    # 8k-prefill-under-load workload as the longctx scenario, with
    # engine.fused_prefill on — prefill chunks ride inside decode
    # dispatches instead of serializing ahead of them.
    fused_stats = {}
    if os.environ.get("BENCH_FUSED", "1") != "0":
        import gc

        eng = None
        gc.collect()
        try:
            fused_stats = _bench_fused(params, cfg, longctx_stats)
        except Exception as e:
            fused_stats = {"fused_error": f"{type(e).__name__}: {e}"}

    # -- prefix cache: warm-prefix vs cold TTFT (the RAG serving shape
    # — identical system prompt + replayed context; ISSUE 1 tentpole).
    prefix_stats = {}
    if os.environ.get("BENCH_PREFIX", "1") != "0":
        import gc

        eng = None
        gc.collect()
        try:
            prefix_stats = _bench_prefix_cache(params, cfg)
        except Exception as e:
            prefix_stats = {"prefix_error": f"{type(e).__name__}: {e}"}

    # -- session KV pager (ISSUE 11 tentpole — the millions-of-
    # sessions memory story): sessions beyond the device pool's
    # capacity park in host RAM / disk via serving/kv_pager.py; warm
    # resume must promote pages back instead of re-prefilling.
    kv_tier_stats = {}
    if os.environ.get("BENCH_KV_TIER", "1") != "0":
        import gc

        eng = None
        gc.collect()
        try:
            kv_tier_stats = _bench_kv_pager(params, cfg)
        except Exception as e:
            kv_tier_stats = {"kv_tier_error": f"{type(e).__name__}: {e}"}

    # -- embedding + rerank engines (BASELINE.md north star #3: embed
    # QPS for the arctic-embed-l geometry; VERDICT r2 missing #1 — the
    # encoders existed for two rounds with no TPU number). Runs after
    # the LLM engine is torn down so BERT-large fits beside nothing.
    encoder_stats = {}
    if os.environ.get("BENCH_ENCODERS", "1") != "0":
        import gc

        eng = None
        del params
        gc.collect()
        try:
            encoder_stats = _bench_encoders()
        except Exception as e:  # report, don't kill the headline metric
            encoder_stats = {"error": f"{type(e).__name__}: {e}"}

    # -- kernel roofline microbench (ISSUE 15 tentpole): per-kernel
    # achieved vs peak bytes/s and FLOP/s for the paged linear/tree
    # attention kernels (bf16 + int8), the int8 matmul and flash
    # prefill — scripts/bench_kernels.py, run in-process on the same
    # accelerator AFTER the engines are torn down (the pools it
    # allocates need the HBM to itself). kern_* keys make kernel
    # regressions visible per-PR without decoding the e2e headline.
    kernel_stats = {}
    if os.environ.get("BENCH_KERNELS", "1") != "0":
        import gc

        # Guard like every sibling scenario: when earlier blocks were
        # skipped via env knobs, the headline engine pool and the 8b
        # weights are still resident — the roofline pools (B=128,
        # P=513 at the TPU geometry) must not allocate on top of them.
        eng = None
        params = None
        gc.collect()
        try:
            from scripts.bench_kernels import run_bench as _kern_run

            kernel_stats = _kern_run()
        except Exception as e:
            kernel_stats = {"kernel_error": f"{type(e).__name__}: {e}"}

    # -- ANN retrieval: IVF vs flat brute-force MIPS at 100k vectors
    # (ISSUE 2 tentpole — per-query retrieval cost must stop scaling
    # linearly with corpus size).
    ann_stats = {}
    if os.environ.get("BENCH_ANN", "1") != "0":
        import gc

        gc.collect()
        try:
            ann_stats = _bench_ann()
        except Exception as e:
            ann_stats = {"ann_error": f"{type(e).__name__}: {e}"}

    # -- tiered ANN capacity: demand-paged IVF at N=10M under live
    # writes (ISSUE 8 tentpole — the hot tier must be SMALLER than the
    # corpus while recall and p99 hold; the first bench about capacity
    # rather than peak rate).
    tiered_stats = {}
    if os.environ.get("BENCH_ANN_TIERED", "1") != "0":
        import gc

        gc.collect()
        try:
            tiered_stats = _bench_ann_tiered()
        except Exception as e:
            tiered_stats = {"tiered_error": f"{type(e).__name__}: {e}"}

    # -- concurrent RAG front half: cross-request micro-batching
    # (ISSUE 3 tentpole — N concurrent embed+search callers must share
    # device dispatches instead of serializing batch-of-1 launches).
    concurrent_stats = {}
    if os.environ.get("BENCH_CONCURRENT", "1") != "0":
        import gc

        gc.collect()
        try:
            concurrent_stats = _bench_concurrent()
        except Exception as e:
            concurrent_stats = {"concurrent_error":
                                f"{type(e).__name__}: {e}"}

    # -- serving fleet: N data-parallel replicas behind the prefix-
    # locality router (ISSUE 7 tentpole — aggregate throughput must
    # scale with replicas, and conversation turns must land on the
    # replica holding their KV). Runs in a CHILD process pinned to the
    # CPU backend: replicas-per-chip would serialize on this process's
    # one device and measure nothing, while threads-on-CPU engines
    # scale with host cores (fleet_cpu_count keys the reading).
    fleet_stats = {}
    if os.environ.get("BENCH_FLEET", "1") != "0":
        try:
            fleet_stats = _bench_fleet()
        except Exception as e:
            fleet_stats = {"fleet_error": f"{type(e).__name__}: {e}"}

    # -- QoS goodput under SLO (ISSUE 9 tentpole — the production-
    # traffic gate): a seeded bursty multi-tenant trace replayed
    # against FIFO vs the weighted-fair scheduler; per-tier goodput,
    # preemption and edge-shed keys. CPU-backend child like the fleet
    # scenario: the subject is scheduling policy under wall-clock
    # arrival timing, not chip throughput.
    qos_stats = {}
    if os.environ.get("BENCH_QOS", "1") != "0":
        try:
            qos_stats = _bench_qos()
        except Exception as e:
            qos_stats = {"qos_error": f"{type(e).__name__}: {e}"}

    # -- chaos / elastic fleet (ISSUE 13 tentpole — the operational
    # gate): the seeded bursty trace through a fleet that loses a
    # replica mid-burst, rolls an engine upgrade under live traffic,
    # and autoscales under a sustained burst; goodput floor + zero
    # lost/failed streams. CPU-backend child like fleet/QoS.
    chaos_stats = {}
    if os.environ.get("BENCH_CHAOS", "1") != "0":
        try:
            chaos_stats = _bench_chaos()
        except Exception as e:
            chaos_stats = {"chaos_error": f"{type(e).__name__}: {e}"}

    # -- disaggregated prefill/decode (ISSUE 14 tentpole — the
    # serving-topology gate): page-transfer ms/page + bytes/page
    # across a prefill-role -> decode-role replica pair, and short-
    # request TTFT p95 + goodput while long prefills storm the fleet,
    # disaggregated vs colocated. CPU-backend child like fleet/QoS.
    disagg_stats = {}
    if os.environ.get("BENCH_DISAGG", "1") != "0":
        try:
            disagg_stats = _bench_disagg()
        except Exception as e:
            disagg_stats = {"disagg_error": f"{type(e).__name__}: {e}"}

    tps = statistics.median(tps_runs)
    out = {
        "metric": f"decode_tokens_per_sec_per_chip_llama3_{model}"
                  + ("_int8" if quantize else ""),
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tps / 2000.0, 3),
        "extras": {
            "batch": batch, "prompt_len": prompt_len, "gen": gen,
            "speculative_k": spec_k,
            "speculative_tree_branches": tree,
            "step_plans": plans,
            "headline_repeat": repeat,
            # Both per-run lists are CHRONOLOGICAL, so index i pairs a
            # run's throughput with its wall.
            "headline_runs_tok_s": [round(v, 1) for v in tps_runs],
            "headline_spread_tok_s": round(max(tps_runs) - min(tps_runs), 1),
            "headline_runs_wall_s": [round(w, 2) for w in wall_runs],
            # The FINAL run's wall only (matches engine_metrics, which
            # the last reset_window scoped to that run) — the headline
            # is the median run, so value != total_tokens/wall_s in
            # general; per-run walls are in headline_runs_wall_s.
            "wall_s": round(wall, 2),
            "ttft_p50_ms": round(1e3 * ttfts[len(ttfts) // 2], 1) if ttfts else None,
            "ttft_staggered16_p50_ms": round(
                1e3 * stag_results[len(stag_results) // 2], 1)
            if stag_results else None,
            "ttft_single_p50_ms": round(
                1e3 * single_ttfts[len(single_ttfts) // 2], 1)
            if single_ttfts else None,
            "engine_metrics": {k: (round(v, 2) if isinstance(v, float) else v)
                               for k, v in snap.items()},
            "throughput_provenance": THROUGHPUT_PROVENANCE,
            "backend": jax.default_backend(),
            **flight_stats,
            **longctx_stats,
            **fused_stats,
            **prefix_stats,
            **kv_tier_stats,
            **encoder_stats,
            **kernel_stats,
            **ann_stats,
            **tiered_stats,
            **concurrent_stats,
            **fleet_stats,
            **qos_stats,
            **chaos_stats,
            **disagg_stats,
        },
    }
    # Provenance is pinned: the scenario refuses to emit an artifact
    # whose headline drifted from the documented recipe — the value
    # must be the MEDIAN of exactly `repeat` recorded runs (a future
    # edit that reads max / final-run / a different window fails here,
    # the r04-vs-r05 readability gap this pin exists to prevent).
    assert out["value"] == round(statistics.median(tps_runs), 1)
    assert len(out["extras"]["headline_runs_tok_s"]) == repeat
    assert len(out["extras"]["headline_runs_wall_s"]) == repeat
    assert out["extras"]["headline_repeat"] == repeat
    print(json.dumps(out))


def _bench_fleet():
    """Spawn scripts/bench_fleet.py on the CPU backend and merge its
    one-line JSON result (BENCH_FLEET_* env knobs pass through)."""
    return _cpu_child_scenario("bench_fleet.py", "fleet_error")


def _bench_qos():
    """Spawn scripts/bench_qos.py on the CPU backend and merge its
    one-line JSON result (BENCH_QOS_* env knobs pass through)."""
    return _cpu_child_scenario("bench_qos.py", "qos_error")


def _bench_disagg():
    """Spawn scripts/bench_disagg.py on the CPU backend and merge its
    one-line JSON result (BENCH_DISAGG_* env knobs pass through)."""
    return _cpu_child_scenario("bench_disagg.py", "disagg_error")


def _bench_chaos():
    """Spawn scripts/bench_chaos.py on the CPU backend and merge its
    one-line JSON result (BENCH_CHAOS_* env knobs pass through)."""
    return _cpu_child_scenario("bench_chaos.py", "chaos_error")


def _cpu_child_scenario(script_name: str, error_key: str):
    """Run a scripts/ scenario as a CPU-pinned child process and parse
    its one-line JSON output (shared by the fleet and QoS scenarios —
    both measure host-side behavior, not chip throughput)."""
    import subprocess
    import sys as _sys

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", script_name)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([_sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=1200)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        tail = (proc.stderr or proc.stdout or "").strip()[-400:]
        return {error_key: f"{script_name} rc={proc.returncode}: {tail}"}
    return json.loads(lines[-1])


def _p95_ms(v):
    return round(sorted(v)[int(0.95 * (len(v) - 1))] * 1e3, 1) if v \
        else None


def _longctx_engine(params, cfg, warm_lengths, tag, **overrides):
    """The shared long-context serving config (8k pool, 1024-token
    chunks, int8 KV). _bench_longctx and _bench_fused must measure the
    IDENTICAL workload on the identical engine geometry — the
    fused_vs_unfused_gap_ratio is meaningless otherwise — so both build
    through here and differ only in explicit overrides."""
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    # 8192 = the model's rope table; prompts stop a page short so the
    # generated tokens stay in range.
    ecfg = EngineConfig(max_batch_size=8, max_seq_len=8192, page_size=128,
                        prefill_buckets=(1024,), kv_dtype="int8",
                        decode_steps_per_dispatch=8, pipeline_depth=2,
                        **overrides)
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg)
    t0 = time.perf_counter()
    eng.warmup(long_prompts=True, long_prompt_lengths=warm_lengths)
    eng.start()
    print(f"[bench] {tag} warmup {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)
    return eng


def _gaps_under_8k_prefill(eng):
    """The 8k-prefill-under-load workload: 4 short streams decode
    continuously; an 8k prefill starts mid-flight. Returns (8k TTFT
    seconds, before-gaps, during-gaps) of the live streams' inter-token
    cadence around the prefill window."""
    import threading

    gaps_during = []
    gaps_before = []
    window = {}

    def short_worker():
        t_start = last = time.perf_counter()
        for ev in eng.generate_stream(list(range(2, 130)),
                                      max_new_tokens=480):
            if ev["token_id"] >= 0:
                now = time.perf_counter()
                gap = now - last
                last = now
                if window.get("start") and not window.get("end"):
                    gaps_during.append(gap)
                elif not window.get("start") and now - t_start > 2.0:
                    # Steady-state cadence only: the first blocks carry
                    # the pacer's uncalibrated interval estimate (first
                    # burst flushes unspaced by design).
                    gaps_before.append(gap)

    threads = [threading.Thread(target=short_worker) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(4.0)  # streams reach steady cadence (first ~2 s discarded)
    window["start"] = time.perf_counter()
    long_prompt = [2 + (i % 1000) for i in range(8064)]
    first = None
    t0 = time.perf_counter()
    for ev in eng.generate_stream(long_prompt, max_new_tokens=2):
        if ev["token_id"] >= 0 and first is None:
            first = time.perf_counter() - t0
            window["end"] = time.perf_counter()
    for t in threads:
        t.join(timeout=120)
    return first, gaps_before, gaps_during


def _bench_longctx(params, cfg):
    """Long-context serving on the real chip: chunked-prefill TTFT at
    2k and 8k prompts, prefill throughput, and inter-token cadence of
    live short streams while an 8k prefill is in progress (the
    one-chunk-per-landed-block pacing claim, engine.py _LongPrefill)."""
    import gc

    gc.collect()
    if cfg.max_seq_len < 8192 or cfg.vocab_size < 1024:
        return {"longctx_skipped":
                f"model geometry too small (max_seq_len={cfg.max_seq_len})"}
    eng = _longctx_engine(params, cfg, (2048, 8064), "longctx")
    stats = {}

    def one(plen, tag):
        prompt = [2 + (i % 1000) for i in range(plen)]
        t0 = time.perf_counter()
        first = None
        for ev in eng.generate_stream(prompt, max_new_tokens=2):
            if ev["token_id"] >= 0 and first is None:
                first = time.perf_counter() - t0
        stats[f"ttft_prompt{tag}_ms"] = round(first * 1e3, 1)
        stats[f"prefill_tok_per_sec_{tag}"] = round(plen / first, 1)

    one(2048, "2k")
    one(8064, "8k")
    first, gaps_before, gaps_during = _gaps_under_8k_prefill(eng)
    eng.stop()

    stats["ttft_8k_under_load_ms"] = round(first * 1e3, 1)
    stats["short_stream_gap_p95_before_ms"] = _p95_ms(gaps_before)
    stats["short_stream_gap_p95_during_8k_prefill_ms"] = _p95_ms(gaps_during)
    stats["short_stream_gap_max_during_8k_prefill_ms"] = (
        round(max(gaps_during) * 1e3, 1) if gaps_during else None)
    del eng
    gc.collect()
    return stats


def _bench_fused(params, cfg, longctx_stats):
    """Fused prefill+decode dispatch vs the interleaved lane: the
    IDENTICAL 8k-prefill-under-load workload as _bench_longctx
    (_gaps_under_8k_prefill on the _longctx_engine geometry) with
    engine.fused_prefill on. Reports the live short streams' inter-
    token gap p95 while the 8k prefill is in flight, the 8k TTFT under
    load, and the ratio against the unfused run's gap (the ~7x stall
    BENCH_r05 measured is the number this lane exists to close)."""
    import gc

    gc.collect()
    if cfg.max_seq_len < 8192 or cfg.vocab_size < 1024:
        return {"fused_skipped":
                f"model geometry too small (max_seq_len={cfg.max_seq_len})"}
    eng = _longctx_engine(params, cfg, (8064,), "fused",
                          fused_prefill=True)
    first, gaps_before, gaps_during = _gaps_under_8k_prefill(eng)
    snap = eng.metrics.snapshot()
    # Perfetto-loadable timeline artifact + stall attribution over the
    # fused run (ISSUE 12 acceptance: the analyzer must name >= 95% of
    # wall, and the artifact lands under build/ for human Perfetto
    # reads of the same workload the gap numbers describe).
    flight_keys = {}
    try:
        from generativeaiexamples_tpu.serving.flight import chrome_trace

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from scripts.analyze_timeline import analyze

        trace = chrome_trace({"fused": eng.flight})
        os.makedirs("build", exist_ok=True)
        path = os.path.join("build", "timeline_fused.json")
        with open(path, "w") as f:
            json.dump(trace, f)
        rep = analyze(trace)
        flight_keys = {
            "flight_timeline_path": path,
            "flight_timeline_beats": sum(v["beats"]
                                         for v in rep["lanes"].values()),
            "flight_attributed_pct": rep["overall"]["attributed_pct"],
            "flight_top_gap_causes": rep["overall"]["top_causes"],
        }
    except Exception as e:
        flight_keys = {"flight_timeline_error": f"{type(e).__name__}: {e}"}
    eng.stop()
    del eng
    gc.collect()

    unfused_gap = longctx_stats.get(
        "short_stream_gap_p95_during_8k_prefill_ms")
    fused_gap = _p95_ms(gaps_during)
    return {
        **flight_keys,
        "fused_ttft_8k_under_load_ms": round(first * 1e3, 1),
        "fused_gap_p95_before_ms": _p95_ms(gaps_before),
        "fused_gap_p95_during_8k_prefill_ms": fused_gap,
        "fused_gap_max_during_8k_prefill_ms": (
            round(max(gaps_during) * 1e3, 1) if gaps_during else None),
        "fused_vs_unfused_gap_ratio": (
            round(fused_gap / unfused_gap, 3)
            if fused_gap and unfused_gap else None),
        "fused_steps": snap["fused_steps"],
        "fused_prefill_tokens": snap["fused_prefill_tokens"],
        "prefill_stall_beats": snap["prefill_stall_beats"],
    }


def _bench_prefix_cache(params, cfg):
    """Warm-prefix vs cold TTFT through the radix prefix cache
    (serving/prefix_cache.py): the same 2k prompt served cold (full
    chunked prefill) and warm (one gather + a 1-token suffix forward).
    Returns prefix_ttft_{cold,warm}_ms, the speedup, and the engine's
    hit/miss counters."""
    import gc

    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    gc.collect()
    if cfg.max_seq_len < 4096 or cfg.vocab_size < 1024:
        return {"prefix_skipped":
                f"model geometry too small (max_seq_len={cfg.max_seq_len})"}
    ecfg = EngineConfig(max_batch_size=8, max_seq_len=4096, page_size=128,
                        prefill_buckets=(1024,), kv_dtype="int8",
                        decode_steps_per_dispatch=8, pipeline_depth=2,
                        prefix_cache=True)
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg)
    t0 = time.perf_counter()
    eng.warmup(long_prompts=True, long_prompt_lengths=(2048,))
    eng.start()
    print(f"[bench] prefix warmup {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)
    prompt = [2 + (i % 1000) for i in range(2048)]

    def ttft():
        t0 = time.perf_counter()
        for ev in eng.generate_stream(prompt, max_new_tokens=2):
            if ev["token_id"] >= 0:
                return time.perf_counter() - t0
        # Surface the real failure (an engine error stream emits only
        # the terminal event) instead of a TypeError on None math.
        raise RuntimeError("prefix bench stream ended without a token")

    cold = ttft()
    ttft()  # throwaway: absorbs the hit path's first-use jit variants
    warm = min(ttft() for _ in range(3))
    snap = eng.metrics.snapshot()
    eng.stop()
    del eng
    gc.collect()
    return {
        "prefix_ttft_cold_ms": round(cold * 1e3, 1),
        "prefix_ttft_warm_ms": round(warm * 1e3, 1),
        "prefix_warm_speedup": round(cold / warm, 2) if warm else None,
        "prefix_hits": snap["prefix_hits"],
        "prefix_miss": snap["prefix_miss"],
        "prefix_hit_tokens": snap["prefix_hit_tokens"],
    }


def _bench_kv_pager(params, cfg):
    """Session KV tiering (serving/kv_pager.py): BENCH_KV_SESSIONS
    distinct 2k-prompt sessions served through a page pool sized for
    ~2 of them, so the pager must park the rest in host RAM / disk.
    Reports how many sessions stay resident vs what HBM alone holds,
    warm-resume TTFT (promote + 1-token suffix forward) vs a cold
    full prefill, and promote ms/page from a standalone pager
    microbench (demote a 16-page prefix to host, time the batched
    promotion scatter back)."""
    import gc

    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    gc.collect()
    if cfg.max_seq_len < 4096 or cfg.vocab_size < 1024:
        return {"kv_tier_skipped":
                f"model geometry too small (max_seq_len={cfg.max_seq_len})"}
    n_sessions = int(os.environ.get("BENCH_KV_SESSIONS", "8"))
    plen = int(os.environ.get("BENCH_KV_PROMPT", "2048"))
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=4096, page_size=128,
                        prefill_buckets=(1024,), kv_dtype="int8",
                        decode_steps_per_dispatch=8, pipeline_depth=2,
                        prefix_cache=True, prefix_cache_capacity=0.6,
                        kv_pager=True,
                        kv_host_budget_mb=int(os.environ.get(
                            "BENCH_KV_HOST_MB", "2048")))
    # Pool sized for ~2 sessions' prefixes beyond the active slots:
    # 2 slots x 32 pages + ~2 x (plen/128) cached.
    pages_per_session = plen // 128
    n_pages = 2 * 32 + 2 * pages_per_session + 2
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg, n_pages=n_pages)
    t0 = time.perf_counter()
    eng.warmup(long_prompts=True, long_prompt_lengths=(plen,))
    eng.start()
    print(f"[bench] kv-pager warmup {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    def ttft(prompt):
        # Consumes the WHOLE stream: the radix tree is scheduler-
        # thread-owned, and the resident-count/match reads below must
        # not race a request still decoding.
        t0 = time.perf_counter()
        first = None
        for ev in eng.generate_stream(prompt, max_new_tokens=2):
            if first is None and ev["token_id"] >= 0:
                first = time.perf_counter() - t0
        if first is None:
            raise RuntimeError(
                "kv-pager bench stream ended without a token")
        return first

    prompts = [[2 + ((i * 31 + s * 7) % 1000) for i in range(plen)]
               for s in range(n_sessions + 1)]
    for p in prompts[:n_sessions]:
        ttft(p)  # serve every session once (cold prefills, demotions)
    resident = sum(
        len(eng.prefix_cache.match_nodes(p)) >= pages_per_session - 1
        for p in prompts[:n_sessions])
    hbm_sessions = max(1, eng.prefix_cache.capacity_pages
                       // pages_per_session)
    cold = ttft(prompts[n_sessions])  # never-seen prompt: full prefill
    warms = sorted(ttft(prompts[s]) for s in range(3))
    snap = eng.metrics.snapshot()
    eng.stop()
    del eng
    gc.collect()

    # Promote-cost microbench: a standalone pager over a small pool —
    # demote a 16-page prefix to host, time the batched promote back.
    from generativeaiexamples_tpu.serving.kv_cache import (
        PageAllocator, PagePool)
    from generativeaiexamples_tpu.serving.kv_pager import (
        KVPager, PagedPrefixCache)

    state = {}
    state["pool"] = PagePool.zeros(cfg, 40, 128, dtype="int8")
    alloc = PageAllocator(40)
    pager = KVPager(state["pool"], host_budget_mb=512)
    cache = PagedPrefixCache(alloc, 128, 100, pager,
                             lambda: state["pool"])
    ids = list(range(16 * 128))
    pages = alloc.alloc(16)
    cache.insert(ids, pages)
    alloc.release(pages)
    promote_s = []
    for _ in range(3):
        demoted = cache.evict(16)  # not in an assert: -O must not skip it
        if demoted != 16:
            raise RuntimeError(f"microbench demoted {demoted}/16 pages")
        nodes = cache.match_nodes(ids)
        t0 = time.perf_counter()
        state["pool"] = cache.promote(state["pool"], nodes)
        jax.block_until_ready(state["pool"].kv)
        promote_s.append(time.perf_counter() - t0)
    pager.close()

    return {
        "kv_sessions": n_sessions,
        "kv_sessions_resident_vs_hbm_only": round(resident / hbm_sessions,
                                                  2),
        "kv_warm_resume_ttft_ms": round(warms[1] * 1e3, 1),
        "kv_cold_resume_ttft_ms": round(cold * 1e3, 1),
        "kv_promote_ms_per_page": round(min(promote_s) / 16 * 1e3, 3),
        "kv_demotions": snap["kv_demotions"],
        "kv_promotions": snap["kv_promotions"],
        "kv_host_pages": snap["kv_host_pages"],
        "kv_spill_pages": snap["kv_spill_pages"],
    }


def _bench_ann():
    """IVF ANN vs exact flat MIPS through TPUVectorStore: per-query
    search QPS at N=100k synthetic clustered vectors, the speedup, and
    recall@4 of the clustered index against the exact scorer. The
    clustered corpus is the RAG shape (document chunks bunch by
    topic/file); queries are drawn near cluster centers like real
    embedded questions."""
    import gc

    import numpy as np

    from generativeaiexamples_tpu.rag.vectorstore import TPUVectorStore

    n = int(os.environ.get("BENCH_ANN_N", "100000"))
    dim = int(os.environ.get("BENCH_ANN_DIM", "96"))
    # nlist 512 / nprobe 24 is the measured CPU sweet spot at 100k
    # (scan ~6%, recall ~0.97); the config defaults (64/16) target
    # smaller corpora.
    nlist = int(os.environ.get("BENCH_ANN_NLIST", "512"))
    nprobe = int(os.environ.get("BENCH_ANN_NPROBE", "24"))
    n_q = 64
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((512, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    data = centers[rng.integers(0, 512, n)] + \
        0.10 * rng.standard_normal((n, dim)).astype(np.float32)
    data /= np.linalg.norm(data, axis=1, keepdims=True)
    queries = centers[rng.integers(0, 512, n_q)] + \
        0.10 * rng.standard_normal((n_q, dim)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    texts = [f"chunk-{i}" for i in range(n)]

    def qps(store):
        for q in queries[:4]:  # warm the jit variants
            store.search(q, top_k=4)
        t0 = time.perf_counter()
        out = [store.search(q, top_k=4) for q in queries]
        return n_q / (time.perf_counter() - t0), out

    flat = TPUVectorStore(dim)
    flat.add(texts, data)
    flat_qps, flat_hits = qps(flat)
    del flat
    gc.collect()

    stats = {"flat_search_qps": round(flat_qps, 1)}
    for tag, quant in (("", False), ("_int8", True)):
        ivf = TPUVectorStore(dim, index_type="ivf", nlist=nlist,
                             nprobe=nprobe, quantize_int8=quant)
        # The recall gauge's every-Nth exact reference scan must stay
        # out of the timed windows (it would deflate IVF QPS only —
        # the flat baseline never samples); recall is measured
        # explicitly below instead.
        ivf.recall_sample_every = 1 << 30
        ivf.add(texts, data)
        ivf_qps, ivf_hits = qps(ivf)
        if not tag:
            recall = np.mean([
                len({r.text for r in a} & {r.text for r in b})
                / max(1, len({r.text for r in a}))
                for a, b in zip(flat_hits, ivf_hits)])
            # the search_batch path at the multi-query retrieval width
            # (8 sub-queries per dispatch — the decomposition/fusion
            # shape), one dispatch per batch
            ivf.search_batch(queries[:8], top_k=4)
            t0 = time.perf_counter()
            for lo in range(0, n_q, 8):
                ivf.search_batch(queries[lo:lo + 8], top_k=4)
            batch_qps = n_q / (time.perf_counter() - t0)
            snap = ivf.stats()
            stats.update({
                "ann_search_qps": round(ivf_qps, 1),
                "ann_vs_flat_speedup": round(ivf_qps / flat_qps, 2),
                "ann_recall_at_4": round(float(recall), 4),
                "ann_batch_qps": round(batch_qps, 1),
                "ann_scanned_rows_per_query": round(
                    snap["ann_scanned_rows"] / max(1, snap["searches"]), 1),
                "ann_n": n, "ann_nlist": nlist, "ann_nprobe": nprobe,
            })
        else:
            stats["ann_int8_qps"] = round(ivf_qps, 1)
        del ivf
        gc.collect()
    return stats


def _bench_ann_tiered():
    """Capacity bench (the first scenario that exercises corpus SIZE
    rather than peak rate): demand-paged tiered IVF through
    TPUVectorStore at BENCH_ANN_TIERED_N synthetic clustered vectors —
    default 10M on TPU (two orders beyond BENCH_ANN's 100k), CPU-scaled
    to 200k elsewhere — with the HBM budget forced BELOW the corpus
    (default: a quarter of the int8 row bytes) so the pager actually
    pages. Measures search p50/p99 and QPS WHILE a concurrent writer
    streams rows into the warm tier, then recall@4 against an exact
    host scan of the final corpus, and reports the pager gauges
    (hbm_resident_fraction < 1.0 is the point: the hot tier is smaller
    than the corpus and recall holds anyway — misses refine on host,
    slower never wrong)."""
    import gc
    import threading

    import numpy as np

    from generativeaiexamples_tpu.rag.vectorstore import TPUVectorStore

    on_tpu = jax.default_backend() == "tpu"
    n = int(os.environ.get("BENCH_ANN_TIERED_N",
                           str(10_000_000 if on_tpu else 200_000)))
    dim = int(os.environ.get("BENCH_ANN_TIERED_DIM", "96"))
    # Mean list ~640 rows keeps the padded refine width (pow2 ladder ->
    # 1024) MXU-friendly while the coarse scan stays one skinny matmul.
    nlist = int(os.environ.get("BENCH_ANN_TIERED_NLIST",
                               str(max(64, min(16384, n // 640)))))
    nprobe = int(os.environ.get("BENCH_ANN_TIERED_NPROBE", "64"))
    write_rows = int(os.environ.get("BENCH_ANN_TIERED_WRITE_ROWS",
                                    str(max(10_000, n // 200))))
    int8_bytes = n * dim
    hbm_mb = int(os.environ.get("BENCH_ANN_TIERED_HBM_MB",
                                str(max(8, int8_bytes // 4 >> 20))))
    n_centers = 1024
    n_meas = 400   # timed searches while the writer streams
    n_rec = 64     # recall queries vs the exact host scan

    rng = np.random.default_rng(7)
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)

    def make_rows(m, seed):
        r = np.random.default_rng(seed)
        rows = centers[r.integers(0, n_centers, m)] + \
            0.10 * r.standard_normal((m, dim)).astype(np.float32)
        rows /= np.linalg.norm(rows, axis=1, keepdims=True)
        return rows

    def make_queries(m, seed):
        # Zipf-ish center popularity: real query streams have hot
        # topics, which is what gives the pager a working set.
        r = np.random.default_rng(seed)
        p = 1.0 / (1.0 + np.arange(n_centers))
        cids = r.choice(n_centers, m, p=p / p.sum())
        qs = centers[cids] + \
            0.10 * r.standard_normal((m, dim)).astype(np.float32)
        return qs / np.linalg.norm(qs, axis=1, keepdims=True)

    store = TPUVectorStore(dim, index_type="ivf", nlist=nlist,
                           nprobe=nprobe, quantize_int8=True, tiered=True,
                           hbm_budget_mb=hbm_mb)
    # The gauge's every-Nth exact reference scan is O(N*D) on the host
    # — at 10M it must stay out of every timed window; recall is
    # measured explicitly below.
    store.recall_sample_every = 1 << 30

    chunk = 500_000
    t0 = time.perf_counter()
    for lo in range(0, n, chunk):
        m = min(chunk, n - lo)
        store.add([f"chunk-{lo + i}" for i in range(m)],
                  make_rows(m, 1000 + lo))
    load_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    store.search(make_queries(1, 2)[0], top_k=4)  # trains inline
    train_s = time.perf_counter() - t0

    # Pager warmup: drive the zipf stream until residency settles
    # (each search's post-lock hook kicks the single-flight
    # maintenance worker; give it beats to land installs).
    warm_qs = make_queries(512, 3)
    for lo in range(0, len(warm_qs), 32):
        for q in warm_qs[lo:lo + 32]:
            store.search(q, top_k=4)
        time.sleep(0.02)

    # Timed window: searches race a live writer streaming rows in.
    meas_qs = make_queries(n_meas, 4)
    wrote = {"rows": 0, "elapsed": 0.0, "error": None}

    def writer():
        t0 = time.perf_counter()
        try:
            wchunk = 2048
            for lo in range(0, write_rows, wchunk):
                m = min(wchunk, write_rows - lo)
                store.add([f"w-{lo + i}" for i in range(m)],
                          make_rows(m, 5000 + lo))
                wrote["rows"] += m
        except Exception as e:  # surfaced in the artifact, not lost
            wrote["error"] = f"{type(e).__name__}: {e}"
        wrote["elapsed"] = time.perf_counter() - t0

    w = threading.Thread(target=writer, name="bench-tiered-writer")
    lats = []
    w.start()
    t0 = time.perf_counter()
    for q in meas_qs:
        t1 = time.perf_counter()
        store.search(q, top_k=4)
        lats.append(time.perf_counter() - t1)
    qps = n_meas / (time.perf_counter() - t0)
    w.join()

    # Recall vs the exact scan of the FINAL corpus (writer included).
    rec_qs = make_queries(n_rec, 6)
    got = [store.search(q, top_k=4) for q in rec_qs]
    vecs = store._vecs  # replaced-not-mutated: the ref is a snapshot
    docs = store.snapshot_docs()
    exact_scores = np.empty((len(vecs), n_rec), np.float32)
    for lo in range(0, len(vecs), 1_000_000):
        exact_scores[lo:lo + 1_000_000] = vecs[lo:lo + 1_000_000] @ rec_qs.T
    recalls = []
    for j in range(n_rec):
        kk = 4
        truth = np.argpartition(exact_scores[:, j], -kk)[-kk:]
        truth_texts = {docs[i]["text"] for i in truth}
        got_texts = {r.text for r in got[j]}
        recalls.append(len(truth_texts & got_texts) / kk)
    lats.sort()
    snap = store.stats()
    out = {
        "tiered_ann_n": n, "tiered_dim": dim,
        "tiered_nlist": snap["nlist"], "tiered_nprobe": nprobe,
        "tiered_hbm_budget_mb": hbm_mb,
        "tiered_recall_at_4": round(float(np.mean(recalls)), 4),
        "tiered_search_qps": round(qps, 1),
        "tiered_search_p50_ms": round(1e3 * lats[len(lats) // 2], 2),
        "tiered_search_p99_ms": round(
            1e3 * lats[min(len(lats) - 1, int(len(lats) * 0.99))], 2),
        "tiered_load_s": round(load_s, 1),
        "tiered_train_s": round(train_s, 1),
        "tiered_write_rows": wrote["rows"],
        "tiered_ingest_rows_per_s": round(
            wrote["rows"] / max(wrote["elapsed"], 1e-6), 1),
        "tiered_hbm_resident_fraction": snap["hbm_resident_fraction"],
        "tiered_pager_hit_rate": snap["pager_hbm_hit_rate"],
        "tiered_promotions": snap["tier_promotions"],
        "tiered_demotions": snap["tier_demotions"],
        "tiered_compactions": snap["tier_compactions"],
        "tiered_tail_rows": snap["tier_tail_rows"],
    }
    if wrote["error"]:
        out["tiered_writer_error"] = wrote["error"]
    # Drain the single-flight pager before teardown: a daemon
    # maintenance thread mid-device-op at interpreter exit aborts the
    # runtime and would cost the whole artifact a clean exit code.
    ivf = store._ivf
    if ivf is not None and hasattr(ivf, "wait_maintenance"):
        ivf.wait_maintenance()
    del store
    gc.collect()
    return out


def _bench_concurrent():
    """Concurrent RAG front half (embed_query -> vector search) with the
    cross-request micro-batcher ON vs the serialize-per-caller baseline:
    N threads, each issuing sequential requests — the chain-server
    concurrency shape. Occupancy is the mean coalesced batch size over
    embed dispatches; wait is what coalescing costs a caller in queue
    time."""
    import dataclasses
    import gc
    import random as pyrandom
    import string
    import threading

    from generativeaiexamples_tpu.models import bert
    from generativeaiexamples_tpu.rag.vectorstore import TPUVectorStore
    from generativeaiexamples_tpu.serving.encoders import EmbeddingEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    n_threads = int(os.environ.get("BENCH_CONCURRENT_THREADS", "16"))
    reqs_each = int(os.environ.get("BENCH_CONCURRENT_REQS", "8"))
    n_rows = int(os.environ.get("BENCH_CONCURRENT_N", "20000"))
    total = n_threads * reqs_each

    # Query-bucket geometry from _bench_encoders; the small encoder keeps
    # the scenario about dispatch amortization, not encoder FLOPs, so it
    # also finishes on CPU CI.
    bcfg = dataclasses.replace(
        bert.BertConfig.tiny(vocab_size=512), max_position=128)
    emb = EmbeddingEngine(bert.init_params(bcfg, jax.random.PRNGKey(3)),
                          bcfg, ByteTokenizer(), max_batch=n_threads,
                          buckets=(64, 128))
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((n_rows, bcfg.dim)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    store = TPUVectorStore(bcfg.dim)
    store.add([f"chunk-{i}" for i in range(n_rows)], corpus)

    pyr = pyrandom.Random(0)
    queries = ["".join(pyr.choice(string.ascii_lowercase + "  ")
                       for _ in range(48)) for _ in range(total)]
    emb.embed_query(queries[0])          # warm the jit variants
    store.search(np.zeros(bcfg.dim, np.float32), top_k=4)

    def drive():
        """All threads run the front half to completion; returns wall."""
        barrier = threading.Barrier(n_threads)

        def worker(t):
            barrier.wait()
            for r in range(reqs_each):
                q = queries[t * reqs_each + r]
                store.search(emb.embed_query(q), top_k=4)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return time.perf_counter() - t0

    serial_wall = drive()  # batcher off: per-caller dispatches

    emb.enable_microbatch(max_batch=n_threads, max_wait_us=2000)
    store.enable_microbatch(max_batch=n_threads, max_wait_us=2000)
    # Untimed warm pass: coalesced groups pad to power-of-two batch
    # shapes the Q=1 warmup above never compiled; without this the
    # timed region eats the XLA compiles and under-reports the speedup.
    drive()
    # Fresh batchers -> fresh counters for the measured window.
    emb.enable_microbatch(max_batch=n_threads, max_wait_us=2000)
    store.enable_microbatch(max_batch=n_threads, max_wait_us=2000)
    batched_wall = drive()
    esnap = emb.microbatch_stats()
    ssnap = store.microbatch_stats()
    emb.disable_microbatch()
    store.disable_microbatch()
    del emb, store
    gc.collect()

    return {
        "concurrent_rag_qps": round(total / batched_wall, 1),
        "serialized_rag_qps": round(total / serial_wall, 1),
        "microbatch_vs_serial_speedup": round(
            serial_wall / batched_wall, 2),
        "microbatch_occupancy": esnap["mean_batch_size"],
        "embed_p99_wait_ms": esnap["queue_wait_p99_ms"],
        "microbatch_dispatches_saved": (esnap["dispatches_saved"]
                                        + ssnap["dispatches_saved"]),
        "concurrent_threads": n_threads,
        "concurrent_requests": total,
    }


def _bench_encoders():
    """Embed QPS (arctic-embed-l geometry, bf16, random init — QPS is
    weight-value-independent) and rerank pairs/sec (reranker_base)."""
    import dataclasses
    import string
    import random as pyrandom

    from generativeaiexamples_tpu.models import bert
    from generativeaiexamples_tpu.serving.encoders import (
        EmbeddingEngine, RerankEngine)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    rng = pyrandom.Random(0)

    def mktext(n_chars):
        return "".join(rng.choice(string.ascii_lowercase + "    ")
                       for _ in range(n_chars))

    stats = {}
    bcfg = dataclasses.replace(bert.BertConfig.arctic_embed_l(),
                               dtype=jnp.bfloat16)
    bparams = bert.init_params(bcfg, jax.random.PRNGKey(0))
    # Buckets: short queries (prefix + ~50 chars ≈ 95 byte-tokens) must
    # not ride the 512 document bucket — the 128 bucket is ~4x cheaper.
    # B=32: with the grouped encoder-attention kernel the per-doc
    # forward cost is LOWER at 32 than 64 (attention VMEM pressure;
    # decompose_bert_forward.py) and readback overlap hides the extra
    # batch boundaries.
    emb = EmbeddingEngine(bparams, bcfg, ByteTokenizer(), max_batch=32,
                          buckets=(64, 128, 512))
    # Documents: reference-default chunk geometry (~510 tokens,
    # configuration.py:92-101). Warm both buckets, then measure.
    docs = [mktext(500) for _ in range(256)]
    queries = [mktext(48) for _ in range(256)]
    emb.embed(docs[:32])
    emb.embed(queries[:32], is_query=True)
    t0 = time.perf_counter()
    emb.embed(docs)
    stats["embed_docs_per_sec"] = round(len(docs) / (time.perf_counter() - t0), 1)
    t0 = time.perf_counter()
    emb.embed(queries, is_query=True)
    stats["embed_queries_per_sec"] = round(
        len(queries) / (time.perf_counter() - t0), 1)
    del bparams, emb
    import gc

    gc.collect()

    rcfg = dataclasses.replace(bert.BertConfig.reranker_base(),
                               dtype=jnp.bfloat16)
    rparams = bert.init_params(rcfg, jax.random.PRNGKey(1))
    rr = RerankEngine(rparams, rcfg, ByteTokenizer(), max_batch=64,
                      buckets=(512,))
    passages = [mktext(400) for _ in range(128)]
    rr.score("warmup query", passages[:16])
    t0 = time.perf_counter()
    rr.score("which passage answers the question", passages)
    stats["rerank_pairs_per_sec"] = round(
        len(passages) / (time.perf_counter() - t0), 1)
    return stats


if __name__ == "__main__":
    main()
