"""Multi-host scale evidence: sharded serving bench + planner dryruns.

Extends the MULTICHIP artifact lane (MULTICHIP_r01..r05 were mesh
dryruns of train/prefill/decode shards) with the serving-engine legs
this repo's multi-host bring-up actually ships:

  1. serving   — one engine over every local device (TP mesh, the
                 serving default): tok/s/chip, TTFT p50/p95, and
                 planner-predicted vs MEASURED per-device HBM
                 (device.memory_stats() where the backend reports it;
                 null on CPU). Geometry: 8B random-init where the
                 devices can hold it (TPU), tiny on the CPU backend —
                 BENCH_MULTIHOST_SIZE=tiny|1b|8b overrides.
  2. dryrun_8b / dryrun_70b — analytic memory plans from
                 serving/memory_plan.py, no devices needed: the
                 70B-int8 example geometry (tensor=8, 95 GiB/device)
                 must fit with its per-host breakdown recorded, and an
                 undersized budget must fail fast with the breakdown +
                 smallest-fitting-mesh hint (both captured verbatim).
  3. cpu_sim   — the 2-process jax.distributed CPU bring-up
                 (scripts/smoke_multihost.py) run as a subprocess; its
                 gate results ride along so the artifact proves the
                 init path + replay lockstep, not just arithmetic.
                 BENCH_MULTIHOST_SIM=0 skips (CI runs it standalone).
  4. features_serving — the serving leg again under the FULL profile
                 the generalized replay protocol now carries
                 (speculative tree + step plans + fused prefill +
                 fused sampling + prefix cache + kv pager): the same
                 past-the-bucket prompt set served twice through one
                 engine. Keys: tok_s / tok_s_per_chip (both passes
                 pooled), ttft_cold_p50_ms (pass 1, full prefill) vs
                 ttft_warm_p50_ms (pass 2, prefix-cache promote),
                 ttft_warm_speedup, prefix_hits — compare
                 tok_s_per_chip and ttft_* against the plain `serving`
                 leg for the feature win.

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_multihost.py
    python scripts/bench_multihost.py --out MULTICHIP_r07.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402

GiB = float(1 << 30)


def _engine_cfg(size: str, features: bool = False):
    from generativeaiexamples_tpu.config.schema import EngineConfig

    extra = dict(speculative_k=2, speculative_tree_branches=2,
                 step_plans=True, fused_prefill=True, fused_sampling=True,
                 prefix_cache=True, kv_pager=True) if features else {}
    # With prefix_cache on, auto_pool_pages fills every spare device
    # byte — on the CPU backend that is host RAM, and the resulting
    # multi-million-page pool makes each scatter take ~40 s. The
    # features leg uses the legacy worst-case sizing instead
    # (max_batch_size * max_pages + 1), which is identical on any
    # device where that bound fits.
    auto = not features
    if size == "tiny":
        return EngineConfig(max_batch_size=4, max_seq_len=128, page_size=8,
                            prefill_buckets=(16, 32),
                            pace_emission_max_streams=0, auto_pool_pages=auto,
                            **extra)
    return EngineConfig(auto_pool_pages=auto, pace_emission_max_streams=0,
                        **extra)


def _measured_hbm() -> int | None:
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_in_use"):
            return int(stats["bytes_in_use"])
    except Exception:
        pass
    return None


def _build_serving_engine(size: str, features: bool = False):
    from generativeaiexamples_tpu.config.schema import MeshConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.parallel.mesh import build_mesh
    from generativeaiexamples_tpu.serving import sharding as shd
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    lcfg = {"tiny": llama.LlamaConfig.tiny,
            "1b": llama.LlamaConfig.llama3_2_1b,
            "8b": llama.LlamaConfig.llama3_8b}[size]()
    mesh = build_mesh(MeshConfig()) if len(jax.devices()) > 1 else None
    if mesh is not None:
        mesh = shd.compatible_mesh(lcfg, mesh)
    params = llama.init_params(lcfg, jax.random.PRNGKey(0))
    if mesh is not None:
        params = shd.shard_llama_params(params, lcfg, mesh)
    return LLMEngine(params, lcfg, ByteTokenizer(),
                     _engine_cfg(size, features),
                     mesh=mesh, use_pallas=False), mesh


def _run_batch(eng, prompts, max_new: int):
    """Submit all `prompts`, drain every stream. Returns (ttfts,
    n_tokens, wall_s)."""
    from generativeaiexamples_tpu.serving.engine import GenRequest

    ttfts, t0 = [], time.perf_counter()
    n_tokens = 0
    reqs = []
    for p in prompts:
        req = GenRequest(prompt_ids=list(p), max_new_tokens=max_new)
        req._bench_t0 = time.perf_counter()
        eng.submit(req)
        reqs.append(req)
    for req in reqs:
        first = None
        while True:
            ev = req.stream.get(timeout=600)
            if ev["token_id"] >= 0:
                if first is None:
                    first = time.perf_counter() - req._bench_t0
                n_tokens += 1
            if ev["finished"]:
                break
        ttfts.append(first if first is not None else float("nan"))
    return ttfts, n_tokens, time.perf_counter() - t0


def serving_leg(size: str, n_reqs: int, max_new: int) -> dict:
    eng, mesh = _build_serving_engine(size)
    plan = eng.memory_plan
    eng.warmup()
    measured = _measured_hbm()
    eng.start()

    prompt_len = 12 if size == "tiny" else 128
    prompts = [[(13 * i + 5 * j) % 250 + 1 for j in range(prompt_len)]
               for i in range(n_reqs)]
    ttfts, n_tokens, wall = _run_batch(eng, prompts, max_new)
    eng.stop()

    n_dev = len(jax.devices())
    predicted = plan.total_bytes_per_device if plan else None
    return {
        "size": size,
        "n_devices": n_dev,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "requests": n_reqs,
        "tokens_out": n_tokens,
        "tok_s": round(n_tokens / wall, 2),
        "tok_s_per_chip": round(n_tokens / wall / n_dev, 2),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
        "ttft_p95_ms": round(float(np.percentile(ttfts, 95)) * 1e3, 1),
        "pool_pages": int(eng.pool.n_pages),
        "planner_predicted_bytes_per_device": predicted,
        "measured_bytes_per_device": measured,
        "planner_vs_measured_pct": (
            round(100.0 * predicted / measured, 1)
            if predicted and measured else None),
    }


def features_serving_leg(size: str, n_reqs: int, max_new: int) -> dict:
    """The serving leg under the full replayable profile: speculative
    tree + step plans + fused prefill/sampling + prefix cache + kv
    pager. The same past-the-bucket prompt set is served twice through
    one engine — pass 1's TTFT is a full chunked prefill, pass 2's is
    a prefix-cache promote, and the delta is the warm-resume win the
    multihost pod path now gets too."""
    eng, mesh = _build_serving_engine(size, features=True)
    prompt_len = 48 if size == "tiny" else 192
    eng.warmup(long_prompts=True, long_prompt_lengths=(prompt_len,))
    eng.start()

    prompts = [[(13 * i + 5 * j) % 250 + 1 for j in range(prompt_len)]
               for i in range(n_reqs)]
    cold_ttfts, n_cold, wall_cold = _run_batch(eng, prompts, max_new)
    warm_ttfts, n_warm, wall_warm = _run_batch(eng, prompts, max_new)
    snap = eng.metrics.snapshot()
    eng.stop()

    n_dev = len(jax.devices())
    n_tokens, wall = n_cold + n_warm, wall_cold + wall_warm
    cold_p50 = float(np.percentile(cold_ttfts, 50)) * 1e3
    warm_p50 = float(np.percentile(warm_ttfts, 50)) * 1e3
    return {
        "size": size,
        "n_devices": n_dev,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "requests": 2 * n_reqs,
        "prompt_len": prompt_len,
        "tokens_out": n_tokens,
        "tok_s": round(n_tokens / wall, 2),
        "tok_s_per_chip": round(n_tokens / wall / n_dev, 2),
        "ttft_cold_p50_ms": round(cold_p50, 1),
        "ttft_warm_p50_ms": round(warm_p50, 1),
        "ttft_warm_speedup": (round(cold_p50 / warm_p50, 2)
                              if warm_p50 > 0 else None),
        "prefix_hits": int(snap["prefix_hits"]),
        "fused_sample_dispatches": int(snap["fused_sample_dispatches"]),
        "spec_tokens_per_step": snap["spec_tokens_per_step"],
    }


def dryrun_leg(size: str) -> dict:
    """Analytic plan, no devices: the named geometry must fit, and an
    undersized budget must fail fast with breakdown + mesh hint."""
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.memory_plan import (
        MemoryPlanError, plan_engine_memory)

    lcfg = {"8b": llama.LlamaConfig.llama3_8b,
            "70b": llama.LlamaConfig.llama3_70b}[size]()
    # The 70B-class example deployment: int8 weights + fused-int8 KV,
    # tensor=8 (one host's ICI domain), v5p-class 95 GiB devices.
    tp = 8
    ecfg = EngineConfig(quantize_weights="int8", kv_dtype="int8",
                        hbm_gb_per_device=95.0, auto_pool_pages=True)
    plan = plan_engine_memory(lcfg, ecfg, axis_sizes={"tensor": tp},
                              n_processes=2, devices_per_host=tp // 2)
    out = {
        "size": size, "tensor": tp, "hbm_gb_per_device": 95.0,
        "fits": True,
        "weights_gib_per_device": round(
            plan.lines[0].bytes_per_device / GiB, 3),
        "fixed_gib_per_device": round(plan.fixed_bytes_per_device / GiB, 3),
        "pool_pages": plan.pool_pages,
        "pool_gib_per_device": round(plan.pool_bytes_per_device / GiB, 3),
        "total_gib_per_device": round(plan.total_bytes_per_device / GiB, 3),
        "breakdown": plan.breakdown(),
    }
    # Fail-fast leg: the same model on a budget that cannot hold it
    # (tensor=1 int8 weights alone exceed it: ~8 GiB for 8B, ~66 GiB
    # for 70B).
    try:
        plan_engine_memory(lcfg, ecfg, axis_sizes={"tensor": 1},
                           hbm_bytes_per_device=(8 if size == "8b"
                                                 else 16) << 30)
        out["fail_fast"] = "MISSED — tensor=1/16GiB plan was accepted"
    except MemoryPlanError as e:
        msg = str(e)
        out["fail_fast"] = ("raised, breakdown+hint present"
                           if "memory plan" in msg
                           and "smallest mesh" in msg else
                           f"raised but incomplete: {msg[:200]}")
    return out


def cpu_sim_leg() -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts/smoke_multihost.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    tail = proc.stdout.strip().splitlines()
    summary = {}
    for line in reversed(tail):
        if line.startswith("{"):
            try:
                summary = json.loads(line)
            except ValueError:
                pass
            break
    return {"rc": proc.returncode, **summary}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "MULTICHIP_r07.json"))
    ap.add_argument("--json", action="store_true",
                    help="print the artifact to stdout too")
    args = ap.parse_args()

    size = os.environ.get(
        "BENCH_MULTIHOST_SIZE",
        "tiny" if jax.default_backend() == "cpu" else "8b")
    n_reqs = int(os.environ.get("BENCH_MULTIHOST_REQS", "8"))
    max_new = int(os.environ.get("BENCH_MULTIHOST_NEW", "32"))

    tail = []
    serving = serving_leg(size, n_reqs, max_new)
    tail.append(f"[serving] {size} x{serving['n_devices']}dev: "
                f"{serving['tok_s_per_chip']} tok/s/chip, "
                f"TTFT p50 {serving['ttft_p50_ms']} ms, "
                f"planner {serving['planner_predicted_bytes_per_device']} B"
                f" vs measured {serving['measured_bytes_per_device']} B")
    feat = features_serving_leg(size, n_reqs, max_new)
    tail.append(f"[features_serving] {size}: "
                f"{feat['tok_s_per_chip']} tok/s/chip, "
                f"TTFT cold p50 {feat['ttft_cold_p50_ms']} ms vs warm "
                f"{feat['ttft_warm_p50_ms']} ms "
                f"(x{feat['ttft_warm_speedup']}), "
                f"{feat['prefix_hits']} prefix hits")
    dry8 = dryrun_leg("8b")
    dry70 = dryrun_leg("70b")
    for d in (dry8, dry70):
        tail.append(f"[dryrun] {d['size']} int8 tensor={d['tensor']}: "
                    f"weights {d['weights_gib_per_device']} GiB/dev, "
                    f"total {d['total_gib_per_device']} GiB/dev, "
                    f"{d['pool_pages']} pages; fail-fast: {d['fail_fast']}")
    sim = None
    if os.environ.get("BENCH_MULTIHOST_SIM", "1") != "0":
        sim = cpu_sim_leg()
        tail.append(f"[cpu_sim] rc={sim['rc']} "
                    f"{sim.get('multihost_smoke', '?')} "
                    f"failures={sim.get('failures')}")

    ok = (serving["tokens_out"] > 0
          and feat["tokens_out"] > 0 and feat["prefix_hits"] > 0
          and dry8["fits"] and dry70["fits"]
          and dry8["fail_fast"].startswith("raised, ")
          and dry70["fail_fast"].startswith("raised, ")
          and (sim is None or sim["rc"] == 0))
    artifact = {
        "n_devices": len(jax.devices()),
        "rc": 0 if ok else 1,
        "ok": ok,
        "skipped": False,
        "tail": "\n".join(tail) + "\n",
        "serving": serving,
        "features_serving": feat,
        "dryrun_8b": dry8,
        "dryrun_70b": dry70,
        "cpu_sim": sim,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print("\n".join(tail))
    print(f"wrote {args.out}")
    if args.json:
        print(json.dumps(artifact, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
