"""Disagg smoke: the disaggregated prefill/decode contract, CPU-grade.

A prefill-role + decode-role replica pair behind the router
(fleet.disagg on) versus a colocated single engine. Gates:

  (a) byte-identical streams: every greedy request served through the
      two-stage plan (prefill on r0 -> KV page transfer -> decode on
      r1) produces EXACTLY the single-engine token stream;
  (b) pages actually moved: fleet kv_transfer_pages > 0, plans > 0,
      and the decode replica's radix tree gained the transferred
      prefix (its engine scores real prefix hits — zero re-prefill);
  (c) role discipline: the prefill-role replica never serves decode
      traffic (its engine generated exactly one stage token per
      transferred plan, never a client stream);
  (d) fallback: with the transfer path broken mid-fleet, the SAME
      stream still completes byte-identically via colocated serving
      and disagg_fallbacks counts it — disagg is an optimization,
      never a correctness dependency;
  (e) pipelined transfer (disagg_pipeline + 1-page chunks): streams
      stay byte-identical, chunks outnumber plans (the transfer
      really was windowed), and decode admission landed BEFORE the
      final chunk (disagg_early_admits > 0 — the overlap the
      tentpole buys);
  (f) device path (disagg_device_path): pages move device-to-device
      (kv_transfer_device_pages > 0), streams byte-identical;
  (g) device-path fault: with the device import forced to raise, the
      SAME stream completes byte-identically over the GKVT host
      bounce and disagg_device_fallbacks counts the broken pair;
  (h) process spawn: a `python -m generativeaiexamples_tpu.serving`
      worker spawned via the autoscaler's process lane
      (spawn_process_replica) serves one request end-to-end and
      terminates cleanly. SMOKE_DISAGG_SPAWN=0 skips just this gate
      (it boots a real subprocess).

CI-grade: exits nonzero on any violation, prints one JSON summary.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_disagg.py
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

PS = 8


def build_engine():
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = llama.LlamaConfig.tiny()
    params = build_engine.params
    if params is None:
        params = build_engine.params = llama.init_params(
            cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=PS,
                        prefill_buckets=(16, 32), prefix_cache=True,
                        pace_emission_max_streams=0)
    return LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)


build_engine.params = None


def collect(req, timeout=180):
    toks = []
    while True:
        ev = req.stream.get(timeout=timeout)
        if ev["token_id"] >= 0:
            toks.append(ev["token_id"])
        if ev["finished"]:
            return toks, ev["finish_reason"]


def run_one(target, prompt, max_new=16):
    from generativeaiexamples_tpu.serving.engine import GenRequest

    req = GenRequest(prompt_ids=list(prompt), max_new_tokens=max_new)
    target.submit(req)
    return collect(req)


def main() -> int:
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, LocalReplica)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    failures = []

    def gate(name, ok, detail=""):
        print(f"  [{'PASS' if ok else 'FAIL'}] {name}"
              + (f" ({detail})" if detail else ""))
        if not ok:
            failures.append(name)

    prompts = [[(7 * i + j) % 250 + 1 for j in range(20 + 4 * i)]
               for i in range(4)]

    # Colocated single-engine reference.
    single = build_engine().start()
    want = [run_one(single, p) for p in prompts]
    single.stop()

    # (a)+(b)+(c): disagg pair.
    reps = [LocalReplica("r0", build_engine(), role="prefill"),
            LocalReplica("r1", build_engine(), role="decode")]
    fleet = EngineFleet(reps, ByteTokenizer(), PS, disagg=True).start()
    got = [run_one(fleet, p) for p in prompts]
    snap = fleet.metrics.snapshot()
    print("disagg smoke:")
    gate("streams_byte_identical", got == want)
    gate("kv_transfer_pages", snap["kv_transfer_pages"] > 0,
         f"{snap['kv_transfer_pages']} pages, "
         f"{snap['kv_transfer_ms']:.1f} ms")
    gate("disagg_plans", snap["router_disagg_plans"] == len(prompts),
         str(snap["router_disagg_plans"]))
    gate("no_fallbacks", snap["disagg_fallbacks"] == 0)
    gate("decode_tree_gained_prefix",
         reps[1].engine.prefix_cache.n_cached_pages > 0
         and reps[1].engine.metrics.prefix_hits == len(prompts),
         f"{reps[1].engine.prefix_cache.n_cached_pages} pages, "
         f"{reps[1].engine.metrics.prefix_hits} hits")
    # The prefill engine ran one single-token stage per plan and no
    # client decode stream (role discipline).
    gate("prefill_role_never_decodes",
         reps[0].engine.metrics.tokens_out
         == snap["router_disagg_plans"],
         f"{reps[0].engine.metrics.tokens_out} stage tokens")
    transfer_pages = snap["kv_transfer_pages"]
    transfer_ms = snap["kv_transfer_ms"]
    fleet.stop()

    # (d): break the transfer -> colocated fallback, same stream.
    reps2 = [LocalReplica("r0", build_engine(), role="prefill"),
             LocalReplica("r1", build_engine(), role="decode")]

    def broken_import(ids, codes, scales, timeout_s=60.0):
        raise RuntimeError("injected transfer fault")

    reps2[1].import_kv_pages = broken_import
    fleet2 = EngineFleet(reps2, ByteTokenizer(), PS, disagg=True).start()
    got2 = [run_one(fleet2, p) for p in prompts]
    snap2 = fleet2.metrics.snapshot()
    gate("fallback_streams_byte_identical", got2 == want)
    gate("fallback_counted",
         snap2["disagg_fallbacks"] == len(prompts),
         str(snap2["disagg_fallbacks"]))
    gate("fallback_moved_no_pages", snap2["kv_transfer_pages"] == 0)
    fleet2.stop()

    # (e): pipelined chunk-ship transfer — byte-identical, windowed,
    # decode admitted before the final chunk landed.
    reps3 = [LocalReplica("r0", build_engine(), role="prefill"),
             LocalReplica("r1", build_engine(), role="decode")]
    fleet3 = EngineFleet(reps3, ByteTokenizer(), PS, disagg=True,
                         disagg_pipeline=True,
                         disagg_transfer_chunk_pages=1).start()
    got3 = [run_one(fleet3, p) for p in prompts]
    snap3 = fleet3.metrics.snapshot()
    gate("pipelined_streams_byte_identical", got3 == want)
    gate("pipelined_chunked",
         snap3["kv_transfer_chunks"] > snap3["router_disagg_plans"] > 0,
         f"{snap3['kv_transfer_chunks']} chunks / "
         f"{snap3['router_disagg_plans']} plans")
    gate("pipelined_early_admit", snap3["disagg_early_admits"] > 0,
         f"{snap3['disagg_early_admits']} early admits, "
         f"{snap3['disagg_overlap_ms']:.1f} ms overlapped")
    gate("pipelined_no_fallbacks", snap3["disagg_fallbacks"] == 0)
    fleet3.stop()

    # (f): device path — pages move device-to-device, byte-identical.
    reps4 = [LocalReplica("r0", build_engine(), role="prefill"),
             LocalReplica("r1", build_engine(), role="decode")]
    fleet4 = EngineFleet(reps4, ByteTokenizer(), PS, disagg=True,
                         disagg_device_path=True).start()
    got4 = [run_one(fleet4, p) for p in prompts]
    snap4 = fleet4.metrics.snapshot()
    gate("device_streams_byte_identical", got4 == want)
    gate("device_pages_moved", snap4["kv_transfer_device_pages"] > 0,
         f"{snap4['kv_transfer_device_pages']} device pages")
    gate("device_no_fallbacks", snap4["disagg_device_fallbacks"] == 0)
    fleet4.stop()

    # (g): device-path fault -> host-bounce fallback on the SAME
    # stream; the broken pair is counted and the bytes still match.
    reps5 = [LocalReplica("r0", build_engine(), role="prefill"),
             LocalReplica("r1", build_engine(), role="decode")]

    def broken_device_import(*a, **k):
        raise RuntimeError("injected device-path fault")

    reps5[1].import_kv_pages_device = broken_device_import
    fleet5 = EngineFleet(reps5, ByteTokenizer(), PS, disagg=True,
                         disagg_device_path=True).start()
    got5 = [run_one(fleet5, p) for p in prompts]
    snap5 = fleet5.metrics.snapshot()
    gate("device_fault_streams_byte_identical", got5 == want)
    gate("device_fault_counted", snap5["disagg_device_fallbacks"] > 0,
         str(snap5["disagg_device_fallbacks"]))
    gate("device_fault_host_bounce_moved_pages",
         snap5["kv_transfer_pages"] > 0
         and snap5["kv_transfer_device_pages"] == 0,
         f"{snap5['kv_transfer_pages']} host pages")
    gate("device_fault_no_colocated_fallbacks",
         snap5["disagg_fallbacks"] == 0)
    fleet5.stop()

    # (h): process-per-replica spawn serves end-to-end.
    spawn_note = "skipped"
    if os.environ.get("SMOKE_DISAGG_SPAWN", "1") != "0":
        from generativeaiexamples_tpu.serving.engine import GenRequest
        from generativeaiexamples_tpu.serving.fleet import (
            spawn_process_replica)

        rep = None
        try:
            # warm=False: the full warmup compiles every bucket,
            # minutes on a 1-CPU CI host; one request compiles what
            # it touches.
            rep = spawn_process_replica("smoke-spawn", model_size="tiny",
                                        warm=False, ready_timeout_s=120.0)
            req = GenRequest(prompt_ids=list(prompts[0]),
                             max_new_tokens=8)
            rep.submit(req)
            toks, reason = collect(req, timeout=300)
            gate("process_spawn_served",
                 reason == "length" and len(toks) > 0,
                 f"{len(toks)} chunks, reason={reason}")
            spawn_note = "served"
        except Exception as e:
            gate("process_spawn_served", False,
                 f"{type(e).__name__}: {e}")
        finally:
            if rep is not None:
                rep.stop()
                gate("process_spawn_terminated",
                     rep.proc.poll() is not None)

    print(json.dumps({
        "disagg_smoke": "pass" if not failures else "fail",
        "failures": failures,
        "kv_transfer_pages": int(transfer_pages),
        "kv_transfer_ms": round(float(transfer_ms), 1),
        "transfer_ms_per_page": round(float(transfer_ms)
                                      / max(1, transfer_pages), 2),
        "pipelined_chunks": int(snap3["kv_transfer_chunks"]),
        "pipelined_early_admits": int(snap3["disagg_early_admits"]),
        "pipelined_overlap_ms": round(
            float(snap3["disagg_overlap_ms"]), 1),
        "device_pages": int(snap4["kv_transfer_device_pages"]),
        "device_fallbacks_after_fault": int(
            snap5["disagg_device_fallbacks"]),
        "process_spawn": spawn_note,
    }))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
