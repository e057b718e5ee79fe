"""Flight-recorder smoke: boot a default-config tiny engine (recorder
ON by default), drive deterministic traffic, and assert the recorder's
whole contract on CPU:

- the recorder is on by default and beat records >= decode_steps
  (K=1 engine: one landed block = one decode step = one record);
- recorder-on vs recorder-off token streams are byte-identical under
  the same deterministic dispatch schedule (recording must observe,
  never steer);
- /debug/timeline's Chrome trace JSON round-trips through json and its
  request spans NEST (children contained in parents per thread lane);
- scripts/analyze_timeline.py attributes ~100% of wall time;
- recorder overhead <= SMOKE_FLIGHT_MAX_OVERHEAD_PCT (default 1%) on
  a threaded throughput burst, best-of-N per config so scheduler noise
  lowers neither side.

CI-grade: exits nonzero on any violation, prints one JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_flight.py
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from scripts.analyze_timeline import analyze  # noqa: E402


def _engine(params, cfg, recorder: bool, batch: int = 2):
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    ecfg_kw = dict(max_batch_size=batch, max_seq_len=128, page_size=8,
                   prefill_buckets=(16,), decode_steps_per_dispatch=1,
                   pace_emission_max_streams=0)
    if not recorder:
        ecfg_kw["flight_recorder"] = False
    return LLMEngine(params, cfg, ByteTokenizer(), EngineConfig(**ecfg_kw),
                     use_pallas=False)


def run_inline(params, cfg, recorder: bool):
    """Single-thread deterministic drive (no wall-clock scheduling):
    identical dispatch schedules across the on/off pair."""
    from generativeaiexamples_tpu.serving.engine import GenRequest

    eng = _engine(params, cfg, recorder)
    reqs = [GenRequest(prompt_ids=[3 + i, 5, 7], max_new_tokens=24,
                       request_id=f"smoke-{i}") for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(400):
        eng._admit_waiting()
        eng._advance_long_prefills()
        eng._emit_ready_first_tokens()
        while (len(eng._inflight) < eng.pipeline_depth
               and any(s is not None for s in eng.slots)):
            if not eng._dispatch_decode():
                break
        if eng._inflight:
            eng._land_next_block()
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._inflight and not eng._pending_first):
            break

    def drain(req):
        out = []
        while True:
            try:
                ev = req.stream.get_nowait()
            except queue.Empty:
                return out
            if ev["token_id"] >= 0:
                out.append(ev["token_id"])

    streams = [drain(r) for r in reqs]
    return streams, eng


def _burst_tok_s(eng, enabled: bool) -> float:
    """One threaded burst's tok/s with the recorder toggled at runtime
    (same engine both ways, so compile state is shared)."""
    eng.flight.set_enabled(enabled)
    results = []
    lock = threading.Lock()

    def worker():
        n = 0
        for ev in eng.generate_stream([2, 3, 4], max_new_tokens=96):
            if ev["token_id"] >= 0:
                n += 1
        with lock:
            results.append(n)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return sum(results) / wall


def measure_overhead_pct(eng, pairs: int):
    """Two estimators over PAIRED off/on bursts, both robust to a
    noisy 1-core box in a different way: the MEDIAN pairwise delta
    (pairing cancels host drift, the median kills hiccup outliers)
    and the BEST-OF comparison (max tok/s per config estimates the
    noise-free capability — scheduler noise only ever lowers a
    burst). The gate takes the smaller: a real regression moves BOTH
    up, while a single unlucky burst moves at most one."""
    deltas = []
    best_on = best_off = 0.0
    for _ in range(pairs):
        off = _burst_tok_s(eng, False)
        on = _burst_tok_s(eng, True)
        best_on, best_off = max(best_on, on), max(best_off, off)
        deltas.append((off - on) / off * 100.0 if off else 0.0)
    deltas.sort()
    median = deltas[len(deltas) // 2]
    best = ((best_off - best_on) / best_off * 100.0) if best_off else 0.0
    return min(median, best), best_on, best_off


def main() -> int:
    from generativeaiexamples_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    failures = []
    out = {}

    # -- determinism + record contract (inline drive) ----------------------
    streams_on, eng_on = run_inline(params, cfg, recorder=True)
    streams_off, eng_off = run_inline(params, cfg, recorder=False)
    if streams_on != streams_off:
        failures.append("token streams diverged recorder-on vs -off")
    if any(len(s) != 24 for s in streams_on):
        failures.append("stream under-generated")
    snap_on = eng_on.metrics.snapshot()
    snap_off = eng_off.metrics.snapshot()
    out["flight_beats"] = snap_on["flight_beats"]
    out["decode_steps"] = snap_on["decode_steps"]
    if not snap_on["flight_enabled"]:
        failures.append("recorder not enabled by default")
    if snap_on["flight_beats"] < snap_on["decode_steps"]:
        failures.append(
            f"beat records {snap_on['flight_beats']} < decode_steps "
            f"{snap_on['decode_steps']} (K=1: every step must record)")
    if snap_off["flight_beats"] != 0 or snap_off["flight_enabled"]:
        failures.append("recorder-off engine recorded beats")
    for key in ("flight_beats", "flight_events", "hist_ttft_ms",
                "hist_e2e_ms", "hist_beat_gap_ms"):
        if key not in snap_off:
            failures.append(f"always-present key {key} missing when off")

    # -- timeline JSON + nesting + attribution -----------------------------
    from generativeaiexamples_tpu.serving.flight import (chrome_trace,
                                                         spans_nest)

    trace = json.loads(json.dumps(chrome_trace({"r0": eng_on.flight})))
    n_beat_slices = sum(1 for e in trace["traceEvents"]
                        if e.get("cat") == "beat")
    n_req_spans = sum(1 for e in trace["traceEvents"]
                      if e.get("cat") == "request" and e.get("ph") == "X")
    out["timeline_beats"] = n_beat_slices
    out["timeline_request_spans"] = n_req_spans
    if n_beat_slices < snap_on["decode_steps"]:
        failures.append("timeline lost beat slices")
    if n_req_spans < 2:  # outer spans for both requests at minimum
        failures.append("timeline missing request spans")
    if not spans_nest(trace):
        failures.append("timeline spans do not nest")
    report = analyze(trace)
    out["attributed_pct"] = report["overall"]["attributed_pct"]
    if abs(report["overall"]["attributed_pct"] - 100.0) > 1.0:
        failures.append(
            f"attribution sums to {report['overall']['attributed_pct']}%")
    if "device_busy" not in report["overall"]["categories"]:
        failures.append("no device_busy attribution")

    # -- overhead pin (threaded, best-of-N, runtime toggle) ----------------
    max_overhead = float(os.environ.get("SMOKE_FLIGHT_MAX_OVERHEAD_PCT",
                                        "1.0"))
    pairs = int(os.environ.get("SMOKE_FLIGHT_PAIRS", "5"))
    eng = _engine(params, cfg, recorder=True, batch=4).start()
    try:
        _burst_tok_s(eng, True)  # compile + thread warm
        overhead = on = off = 0.0
        for _round in range(3):  # retry rounds: noise only ever
            overhead, on, off = measure_overhead_pct(eng, pairs)
            if overhead <= max_overhead:  # raises the reading
                break
        out["flight_overhead_pct"] = round(overhead, 3)
        out["tok_s_on"] = round(on, 1)
        out["tok_s_off"] = round(off, 1)
        if overhead > max_overhead:
            failures.append(
                f"recorder overhead {overhead:.2f}% > {max_overhead}%")
    finally:
        eng.stop()

    out["ok"] = not failures
    if failures:
        out["failures"] = failures
    print(json.dumps(out))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
