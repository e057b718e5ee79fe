"""Fleet bench child: aggregate throughput + TTFT across N emulated
engine replicas vs 1, and router hit-rate on a conversation-replay
workload. Prints ONE JSON line (the BENCH_FLEET keys bench.py merges
into its artifact).

Runs on the CPU backend BY DESIGN (bench.py spawns it with
JAX_PLATFORMS=cpu): the fleet's data-parallel win is one engine per
chip/host, and a TPU bench process has exactly one chip — two replicas
on it would serialize on the device and measure nothing. Emulated
threads-on-CPU replicas scale with HOST cores instead (each engine's
scheduler + XLA compute runs GIL-free), which is the same emulation
the fleet tests use; `fleet_cpu_count` is reported so a 1-core
container's contention numbers aren't misread as a routing regression.

Workloads:
  uniform burst    BENCH_FLEET_REQS requests from BENCH_FLEET_THREADS
                   threads (prompt/gen BENCH_FLEET_PROMPT/_GEN) through
                   1 replica, then through BENCH_FLEET_REPLICAS — the
                   aggregate-throughput and staggered-TTFT comparison.
  conversation     BENCH_FLEET_CONVS two-turn conversations (turn 2
  replay           replays turn 1 + answer + a new tail) through the
                   fleet — router hit-rate and warm-vs-cold TTFT.

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_fleet.py
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


def _median_ms(vals):
    return round(statistics.median(vals) * 1e3, 1) if vals else None


def _p99_ms(vals):
    if not vals:
        return None
    v = sorted(vals)
    return round(v[min(len(v) - 1, int(0.99 * (len(v) - 1)))] * 1e3, 1)


def main() -> int:
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, LocalReplica)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", "2"))
    n_reqs = int(os.environ.get("BENCH_FLEET_REQS", "48"))
    threads = int(os.environ.get("BENCH_FLEET_THREADS", "12"))
    prompt = int(os.environ.get("BENCH_FLEET_PROMPT", "64"))
    gen = int(os.environ.get("BENCH_FLEET_GEN", "64"))
    convs = int(os.environ.get("BENCH_FLEET_CONVS", "8"))

    # Mid-size geometry: big enough that per-dispatch XLA compute
    # (GIL-free) dominates the scheduler's python time — the regime
    # where replicas scale with cores — small enough to boot fast.
    cfg = llama.LlamaConfig(vocab_size=256, dim=256, n_layers=4,
                            n_heads=4, n_kv_heads=2, head_dim=64,
                            mlp_dim=512, max_seq_len=512,
                            tie_embeddings=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=8, max_seq_len=512, page_size=32,
                        prefill_buckets=(64, 128),
                        decode_steps_per_dispatch=8, prefix_cache=True,
                        pace_emission_max_streams=0)
    tk = ByteTokenizer()

    def engine():
        return LLMEngine(params, cfg, tk, ecfg, use_pallas=False)

    def consume_first_then_rest(req):
        """-> TTFT seconds (first real token), draining the stream."""
        first = None
        while True:
            ev = req.stream.get(timeout=600)
            if first is None and ev["token_id"] >= 0:
                first = time.perf_counter() - req.submit_time
            if ev["finished"]:
                return first

    def burst(target, tag):
        """Uniform burst -> (tok/s, ttft list)."""
        ttfts = []
        lock = threading.Lock()

        def worker(t):
            for k in range(n_reqs // threads):
                ids = [(t * 31 + k * 7 + j) % 250 + 1
                       for j in range(prompt)]
                req = GenRequest(prompt_ids=ids, max_new_tokens=gen)
                target.submit(req)
                ttft = consume_first_then_rest(req)
                with lock:
                    if ttft is not None:
                        ttfts.append(ttft)

        t0 = time.perf_counter()
        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        wall = time.perf_counter() - t0
        total = (n_reqs // threads) * threads * gen
        return total / wall, ttfts, wall

    # -- single replica (the baseline) ----------------------------------
    single = engine().start()
    burst(single, "warm")  # compile + steady-state warm
    single_tps, single_ttfts, single_wall = burst(single, "single")
    single.stop()

    # -- N emulated replicas behind the router ---------------------------
    fleet = EngineFleet(
        [LocalReplica(f"r{i}", engine()) for i in range(replicas)],
        tk, ecfg.page_size).start()
    burst(fleet, "warm")
    fleet_tps, fleet_ttfts, fleet_wall = burst(fleet, "fleet")

    # -- conversation replay through the fleet ---------------------------
    before = fleet.metrics.snapshot()
    cold, warm = [], []
    for c in range(convs):
        turn1 = [(c * 17 + j) % 250 + 1 for j in range(6 * 32)]
        req = GenRequest(prompt_ids=turn1, max_new_tokens=16,
                         session_id=f"conv{c}")
        fleet.submit(req)
        cold.append(consume_first_then_rest(req))
        turn2 = turn1 + [7] * 32
        req2 = GenRequest(prompt_ids=turn2, max_new_tokens=16,
                          session_id=f"conv{c}")
        fleet.submit(req2)
        warm.append(consume_first_then_rest(req2))
    after = fleet.metrics.snapshot()
    fleet.stop()
    replay_reqs = after["router_requests"] - before["router_requests"]
    replay_hits = after["router_prefix_hits"] - before["router_prefix_hits"]

    out = {
        "fleet_replicas": replicas,
        "fleet_cpu_count": os.cpu_count(),
        "fleet_single_tok_s": round(single_tps, 1),
        "fleet_agg_tok_s": round(fleet_tps, 1),
        "fleet_speedup": round(fleet_tps / single_tps, 3),
        "fleet_qps_single": round(n_reqs / single_wall, 2),
        "fleet_qps": round(n_reqs / fleet_wall, 2),
        "fleet_ttft_p99_1rep_ms": _p99_ms(single_ttfts),
        "fleet_ttft_p99_ms": _p99_ms(fleet_ttfts),
        "fleet_router_hit_rate": round(replay_hits / replay_reqs, 3)
        if replay_reqs else 0.0,
        "fleet_hit_tokens": after["router_hit_tokens"],
        "fleet_cold_ttft_ms": _median_ms([t for t in cold if t]),
        "fleet_warm_ttft_ms": _median_ms([t for t in warm if t]),
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
