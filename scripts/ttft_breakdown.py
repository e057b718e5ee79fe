"""TTFT stage breakdown on real TPU (VERDICT r2 next-step #2: hit
<=200 ms p50 or publish a measured per-stage table).

Boots the deployment-config engine (llama3-8b int8 weights, int8 KV,
B=128), warms it, then reads one request's path through the scheduler
FROM THE FLIGHT RECORDER (serving/flight.py): submit -> admit (slot
reserved) -> prefill dispatched -> first token emitted. The recorder
is always on, so this script no longer monkeypatches scheduler
internals — the same stage table works on any engine config (fused,
speculative, prefix-cached), and `/debug/timeline` shows the same
requests as Perfetto spans.

Usage: python scripts/ttft_breakdown.py [n_requests]
Prints one stage table per request plus the median summary row for
docs/ENGINEERING_NOTES.md.
"""

from __future__ import annotations

import os
import statistics
import sys
import time

import jax  # noqa: E402

from generativeaiexamples_tpu.config.schema import EngineConfig  # noqa: E402
from generativeaiexamples_tpu.models import llama  # noqa: E402
from generativeaiexamples_tpu.serving import flight  # noqa: E402
from generativeaiexamples_tpu.serving.engine import (  # noqa: E402
    GenRequest, LLMEngine)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer  # noqa: E402

STAGES = ["admit", "prefill_dispatched", "first_token"]
_STAGE_KINDS = {
    flight.EV_ADMIT: "admit",
    flight.EV_PREFILL_DISPATCH: "prefill_dispatched",
    # Chunked/prefix-hit prompts dispatch chunks instead of one group;
    # the FIRST chunk marks the same "prefill started" stage.
    flight.EV_PREFILL_CHUNK: "prefill_dispatched",
    flight.EV_FIRST_TOKEN: "first_token",
}


def stage_rows(recorder, rids):
    """Per-request stage tables (ms from submit) read from the
    recorder's lifecycle ring."""
    by_rid = {}
    for ev in recorder.snapshot_events():
        by_rid.setdefault(ev["rid"], []).append(ev)
    rows = []
    for rid in rids:
        evs = by_rid.get(rid, [])
        submit = next((e["ts"] for e in evs
                       if e["kind"] == flight.EV_SUBMIT), None)
        if submit is None:
            rows.append({})
            continue
        row = {}
        prev = submit
        for stage in STAGES:
            ts = next((e["ts"] for e in evs
                       if _STAGE_KINDS.get(e["kind"]) == stage), None)
            if ts is not None:
                row[stage] = (ts - prev) * 1e3
                prev = ts
        last = next((e["ts"] for e in evs
                     if e["kind"] == flight.EV_FIRST_TOKEN), prev)
        row["total"] = (last - submit) * 1e3
        rows.append(row)
    return rows


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    n_req = int(sys.argv[1]) if len(sys.argv) > 1 else 9
    cfg = llama.LlamaConfig.llama3_8b()
    params = llama.init_params_on_device(cfg, quantize=True)
    jax.block_until_ready(params["layers"]["wq"].q)
    ecfg = EngineConfig(max_batch_size=128, max_seq_len=384, page_size=128,
                        prefill_buckets=(128,), kv_dtype="int8",
                        decode_steps_per_dispatch=8, pipeline_depth=2)
    eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg)
    eng.warmup()
    eng.start()
    prompt = list(range(2, 130))
    list(eng.generate_stream(prompt, max_new_tokens=4))  # e2e warm
    print("[ttft] engine warm", file=sys.stderr)

    rids = []
    for r in range(n_req):
        req = GenRequest(prompt_ids=list(prompt), max_new_tokens=2,
                         request_id=f"ttft-{r}")
        rids.append(req.request_id)
        eng.submit(req)
        while True:
            ev = req.stream.get()
            if ev["token_id"] >= 0 or ev["finished"]:
                break
        # Drain the stream so the next request sees an idle engine.
        while not ev["finished"]:
            ev = req.stream.get()
        time.sleep(0.2)
    rows = stage_rows(eng.flight, rids)
    eng.stop()

    for r, row in enumerate(rows):
        print(f"[ttft] req {r}: " + "  ".join(
            f"{s}={row.get(s, float('nan')):.1f}ms"
            for s in STAGES + ["total"]))
    med = {s: statistics.median([r[s] for r in rows if s in r])
           for s in STAGES + ["total"] if any(s in r for r in rows)}
    print("[ttft] MEDIAN  " + "  ".join(f"{s}={v:.1f}ms"
                                        for s, v in med.items()))


if __name__ == "__main__":
    main()
