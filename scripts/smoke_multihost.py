"""Multi-host smoke: 2-process jax.distributed serving on the CPU backend.

Orchestrates three subprocesses to gate the multi-host engine runtime
(serving/multihost.py + engine.multihost) without a TPU pod:

  ref    — single process, 2 emulated CPU devices
           (--xla_force_host_platform_device_count=2), TP=2 mesh,
           engine.multihost=false: the byte-identity reference.
  rank 0 — jax.distributed leader (1 CPU device), TP=2 mesh spanning
           both processes, gloo collectives; serves the same greedy
           prompts through the real scheduler, publishing dispatch
           records.
  rank 1 — follower: identical build + warmup, then replays rank 0's
           records via multihost.run_follower until the stop record.

Gates:
  (a) distributed init: both ranks see process_count==2 and a 2-device
      global mesh built over mesh.coordinator_address config (the
      --coordinator serve-flag path, not env);
  (b) planner-sized pool: engine.auto_pool_pages=true sizes the page
      pool to memory_plan.pool_pages, and the planner/multihost gauges
      (planner_headroom_bytes, multihost_processes) are live;
  (c) sharded decode byte-identical: every token stream from the
      2-process engine equals the single-process reference exactly;
  (d) streaming load: both ranks load the checkpoint through
      stream_load_llama against the cross-process mesh (each host
      placing only its addressable shards);
  (e) clean shutdown: rank 0's stop() publishes the stop record, the
      follower's replay loop exits, both ranks terminate with code 0;
  (f) features-on leg: the same comparison with the FULL serving
      profile (speculative tree + step plans + fused prefill + fused
      sampling + prefix cache + kv pager) — two turns over a
      past-the-bucket prompt so the warm turn must count a prefix hit
      (prefix_hits > 0 on rank 0, replaying the pool_to_cache seed
      record on rank 1) with zero replay divergences on either rank.

CI-grade: exits nonzero on any violation, prints one JSON summary.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_multihost.py
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PS = 8
MAX_NEW = 12
PROMPTS = [[(11 * i + 3 * j) % 250 + 1 for j in range(10 + 5 * i)]
           for i in range(3)]
# Features leg: one prompt past the largest bucket (chunked fused
# prefill) served TWICE — the warm turn must hit the prefix cache and
# replay its pool_to_cache seed record on the follower.
LONG_PROMPT = [(7 * j) % 250 + 1 for j in range(48)]
# With prefix_cache on, the planner deliberately sizes the pool to
# fill every spare device byte — on the CPU backend "device memory"
# is host RAM, which would make a multi-million-page pool whose
# per-dispatch scatters take ~40 s each. The features leg therefore
# pins an explicit tight pool (max_pages + 1 sink page: one
# max-length sequence fits, cached prefixes must compete), which also
# puts real eviction pressure on the prefix cache + kv pager; the
# plain leg keeps auto_pool_pages so gate (b) still covers the
# planner path.
FEATURE_POOL_PAGES = 128 // PS + 1


def engine_config(multihost: bool, features: bool = False):
    from generativeaiexamples_tpu.config.schema import EngineConfig

    extra = dict(speculative_k=2, speculative_tree_branches=2,
                 step_plans=True, fused_prefill=True, fused_sampling=True,
                 prefix_cache=True, kv_pager=True) if features else {}
    return EngineConfig(max_batch_size=2, max_seq_len=128, page_size=PS,
                        prefill_buckets=(16, 32),
                        pace_emission_max_streams=0,
                        multihost=multihost, auto_pool_pages=True, **extra)


def build_engine(ckpt: str, mesh, multihost: bool, features: bool = False):
    from generativeaiexamples_tpu.models.hf_loader import (
        llama_config_from_hf, load_llama)
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    lcfg = llama_config_from_hf(ckpt)
    params, lcfg = load_llama(ckpt, cfg=lcfg, mesh=mesh)
    eng = LLMEngine(params, lcfg, ByteTokenizer(),
                    engine_config(multihost, features),
                    n_pages=FEATURE_POOL_PAGES if features else None,
                    mesh=mesh, use_pallas=False)
    # Identical warmup on every rank: cross-process collectives pair by
    # launch order, so the warmup program sequence must match exactly.
    if features:
        eng.warmup(long_prompts=True,
                   long_prompt_lengths=(len(LONG_PROMPT),))
    else:
        eng.warmup()
    return eng


def serve_prompts(eng, prompts=None):
    from generativeaiexamples_tpu.serving.engine import GenRequest

    out = []
    for p in (PROMPTS if prompts is None else prompts):
        req = GenRequest(prompt_ids=list(p), max_new_tokens=MAX_NEW)
        eng.submit(req)
        toks = []
        while True:
            ev = req.stream.get(timeout=300)
            if ev["token_id"] >= 0:
                toks.append(ev["token_id"])
            if ev["finished"]:
                break
        out.append(toks)
    return out


def serve_leg(eng, features: bool):
    """The leg's full request schedule: the plain leg serves PROMPTS
    once; the features leg serves PROMPTS + LONG_PROMPT twice (cold
    turn populates the prefix cache, warm turn must hit it)."""
    if not features:
        return serve_prompts(eng)
    sched = PROMPTS + [LONG_PROMPT]
    return serve_prompts(eng, sched) + serve_prompts(eng, sched)


def run_ref(args) -> int:
    from generativeaiexamples_tpu.config.schema import MeshConfig
    from generativeaiexamples_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(ici_tensor=2))
    eng = build_engine(args.ckpt, mesh, multihost=False,
                       features=args.features).start()
    toks = serve_leg(eng, args.features)
    eng.stop()
    with open(args.out, "w") as f:
        json.dump({"tokens": toks}, f)
    return 0


def run_rank(args) -> int:
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from generativeaiexamples_tpu.config.schema import MeshConfig
    from generativeaiexamples_tpu.parallel.mesh import (
        build_mesh, maybe_initialize_distributed)
    from generativeaiexamples_tpu.serving import multihost as mh

    # The config-driven init path (the --coordinator serve flags), not
    # the JAX_COORDINATOR_ADDRESS env path.
    mcfg = MeshConfig(ici_tensor=2, coordinator_address=args.coordinator,
                      num_processes=2, process_id=args.process_id)
    maybe_initialize_distributed(mcfg)
    assert jax.process_count() == 2, jax.process_count()
    mesh = build_mesh(mcfg)
    eng = build_engine(args.ckpt, mesh, multihost=True,
                       features=args.features)

    if args.process_id == 0:
        eng.start()
        toks = serve_leg(eng, args.features)
        snap = eng.metrics.snapshot()
        result = {
            "tokens": toks,
            "process_count": jax.process_count(),
            "pool_pages": int(eng.pool.n_pages),
            # The features leg pins an explicit n_pages, so the engine
            # never builds a MemoryPlan there; only the plain leg's
            # planner gate reads this.
            "plan_pool_pages": (int(eng.memory_plan.pool_pages)
                                if eng.memory_plan is not None else -1),
            "multihost_processes": int(snap["multihost_processes"]),
            "planner_headroom_bytes": int(snap["planner_headroom_bytes"]),
            "prefix_hits": int(snap["prefix_hits"]),
            "replay_records_published":
                int(snap["replay_records_published"]),
            "replay_divergence": int(snap["replay_divergence"]),
        }
        eng.stop()  # publishes the stop record for rank 1
        with open(args.out, "w") as f:
            json.dump(result, f)
    else:
        mh.run_follower(eng, timeout_s=600)
        eng.stop()
        # The follower's divergence counter must also land in the gate:
        # report it through a sibling file next to rank 0's.
        with open(args.out + ".rank1", "w") as f:
            json.dump({"replay_divergence":
                       int(eng.metrics.replay_divergence)}, f)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("main", "ref", "rank"),
                    default="main")
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--coordinator", default="")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--features", action="store_true",
                    help="full serving profile: speculation + step plans"
                         " + fused prefill/sampling + prefix cache +"
                         " kv pager")
    args = ap.parse_args()
    if args.role == "ref":
        return run_ref(args)
    if args.role == "rank":
        return run_rank(args)

    failures = []

    def gate(name, ok, detail=""):
        print(f"  [{'PASS' if ok else 'FAIL'}] {name}"
              + (f" ({detail})" if detail else ""))
        if not ok:
            failures.append(name)

    with tempfile.TemporaryDirectory() as tmp:
        from tests.test_checkpoint_e2e import write_tiny_hf_checkpoint

        ckpt = os.path.join(tmp, "ckpt")
        os.makedirs(ckpt)
        write_tiny_hf_checkpoint(ckpt)

        # A caller's emulated-device-count flag must not leak into the
        # children: the ref needs exactly 2 devices in ONE process, the
        # ranks exactly 1 local device each (2 global via distributed).
        base_flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                            "", os.environ.get("XLA_FLAGS", "")).strip()
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": base_flags}

        def run_leg(leg: str, features: bool):
            """One ref + 2-rank comparison; returns rank 0's summary
            dict (empty on subprocess failure). Gate names are prefixed
            with the leg on the features pass."""
            pfx = f"{leg}_" if features else ""
            fflag = ["--features"] if features else []
            print(f"multihost smoke [{leg}]: single-process TP=2 "
                  f"reference ...")
            ref_out = os.path.join(tmp, f"ref_{leg}.json")
            ref = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--role",
                 "ref", "--ckpt", ckpt, "--out", ref_out] + fflag,
                env={**env, "XLA_FLAGS":
                     (base_flags +
                      " --xla_force_host_platform_device_count=2")},
                timeout=1200)
            gate(pfx + "reference_ran", ref.returncode == 0,
                 f"exit {ref.returncode}")
            if ref.returncode != 0:
                return {}

            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                coord = f"127.0.0.1:{s.getsockname()[1]}"
            print(f"multihost smoke [{leg}]: 2-process jax.distributed "
                  f"@ {coord} ...")
            rank_out = os.path.join(tmp, f"rank0_{leg}.json")
            procs = []
            for pid in (0, 1):
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--role",
                     "rank", "--process-id", str(pid), "--coordinator",
                     coord, "--ckpt", ckpt, "--out", rank_out] + fflag,
                    env=env))
            codes = []
            try:
                for p in procs:
                    codes.append(p.wait(timeout=1200))
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                gate(pfx + "ranks_exited", False,
                     "timeout — slice deadlocked?")
                return {}
            gate(pfx + "ranks_exited", codes == [0, 0],
                 f"exit codes {codes}")

            want = json.load(open(ref_out))["tokens"]
            got = (json.load(open(rank_out))
                   if os.path.exists(rank_out) else {})
            gate(pfx + "distributed_init", got.get("process_count") == 2)
            gate(pfx + "streams_byte_identical",
                 got.get("tokens") == want,
                 f"{sum(len(t) for t in want)} reference tokens")
            r1 = rank_out + ".rank1"
            got["rank1_replay_divergence"] = (
                json.load(open(r1)).get("replay_divergence", -1)
                if os.path.exists(r1) else -1)
            return got

        got = run_leg("plain", features=False)
        gate("planner_sized_pool",
             got.get("pool_pages", -1) == got.get("plan_pool_pages", -2)
             and got.get("pool_pages", 0) > 0,
             f"{got.get('pool_pages')} pages")
        gate("gauges_live",
             got.get("multihost_processes") == 2
             and got.get("planner_headroom_bytes", 0) > 0,
             f"headroom {got.get('planner_headroom_bytes')} B")

        # Features-on leg: the full serving profile replays — warm-turn
        # prefix hit on rank 0, zero divergences on either rank.
        feat = run_leg("features", features=True)
        gate("features_prefix_hits", feat.get("prefix_hits", 0) > 0,
             f"{feat.get('prefix_hits')} hits")
        gate("features_records_published",
             feat.get("replay_records_published", 0) > 0,
             f"{feat.get('replay_records_published')} records")
        gate("features_zero_divergence",
             feat.get("replay_divergence", -1) == 0
             and feat.get("rank1_replay_divergence", -1) == 0)

    print(json.dumps({
        "multihost_smoke": "pass" if not failures else "fail",
        "failures": failures,
        "pool_pages": got.get("pool_pages"),
        "planner_headroom_bytes": got.get("planner_headroom_bytes"),
        "features_prefix_hits": feat.get("prefix_hits"),
        "features_records_published":
            feat.get("replay_records_published"),
    }))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
