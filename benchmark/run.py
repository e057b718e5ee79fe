"""The benchmark's one command:

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data that this file finds by the names in
BENCHMARK.json: benchmark/configs/<config>.json, benchmark/traffic/<mix>.json,
benchmark/metrics/<metric>.json and the reader it names under
benchmark/readers/, and benchmark/architectures/<name>.py by the name in
the configuration file. See benchmark/README.md.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()  # the restart a user waits for starts here

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

DRAIN_TIMEOUT_S = 240.0


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics this run reports: the cell's end-to-end metrics with
    --trace 0, its per-layer metrics with --trace 1."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, ctx: dict, bench_dir: str = BENCH_DIR):
    """One metric through its own reader; None where it finds nothing."""
    with open(os.path.join(bench_dir, "metrics", name + ".json")) as fh:
        spec = json.load(fh)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(ctx, **spec.get("params", {}))


def _engine_counters(llm) -> dict:
    m = llm.metrics
    return {"t": time.monotonic(), "tokens_out": m.tokens_out,
            "decode_steps": m.decode_steps,
            "busy_slots_acc": m.busy_slots_acc,
            "prefill_tokens": m.prefill_tokens}


def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


class CompileCounter:
    """Compilations and persistent-cache misses, from JAX's own monitoring
    events (a miss is a program compiled here and written to the cache:
    one that took 0.5 s or more to compile)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def check_reference(b, url: str, config: dict, seed: int) -> dict:
    """A seeded prompt through the served path, greedy, against the float32
    reference of the configuration's architecture entry, outside the
    window."""
    import numpy as np

    from benchmark import architectures
    from benchmark.harness import reference, system

    chk = config.get("reference_check", {})
    n_prompt = int(chk.get("prompt_tokens", 48))
    n_new = int(chk.get("new_tokens", 4))
    rng = np.random.default_rng([int(seed), 0xEF])
    vocab = int(config["vocab_size"])
    prompt = [int(t) for t in rng.integers(0, vocab, n_prompt)]
    status, raw = system.http_json("POST", url + "/v1/completions", {
        "model": "bench", "prompt": prompt, "max_tokens": n_new,
        "temperature": 0.0})
    if status != 200:
        return {"ok": False, "why": f"/v1/completions -> {status}"}
    out = json.loads(raw)
    served = b.tokenizer.encode(out["choices"][0]["text"])
    if len(served) != n_new or out["usage"]["completion_tokens"] != n_new:
        return {"ok": False, "why": f"asked {n_new} tokens, got {served}"}
    logits = functools.partial(
        architectures.load(config).reference_logits, config, b.params)
    rel_tol = float(chk.get("rel_tol", 0.05))
    ok, worst = reference.check_greedy(logits, prompt, served, rel_tol=rel_tol)
    return {"ok": bool(ok), "worst_shortfall": worst, "rel_tol": rel_tol,
            "served": served}


def _candidates(records: list, seconds: float) -> dict:
    """Statistics every run prints whether or not they are metrics of
    the cell, so that the spread of a candidate that was not admitted
    stays on record (PERF.md, section 2)."""
    from benchmark.harness import stats

    ttft = stats.ttft_ms(records, seconds)
    gaps = stats.pooled_gaps_ms(records, seconds)
    out = {"out_tokens_per_s":
           stats.tokens_in_window(records, seconds) / seconds}
    for q in (25, 50, 90):
        out[f"ttft_p{q}_ms"] = stats.percentile(ttft, q)
    for q in (50, 99):
        out[f"gap_p{q}_ms"] = stats.percentile(gaps, q)
    out["longest_silence_ms"] = stats.longest_silence_ms(records, seconds)
    return out


def _reached_buckets(llm, schedule: dict, config: dict, endpoint: str) -> list:
    """The prefill buckets this run's prompts reach: the only ones warmed.
    A chain cell's served prompts are assembled by the chain server, so
    every bucket the configuration lists is warmed there."""
    if endpoint != "completions":
        return list(llm.buckets)
    check = int(config.get("reference_check", {}).get("prompt_tokens", 48))
    lengths = {len(r["prompt_ids"]) for r in schedule["requests"]} | {check}
    reached = {llm._bucket_for(n) for n in lengths}
    return [x for x in llm.buckets if x in reached]


def _trace_stretch(llm, traffic: dict, t_open: float, seconds: float) -> dict:
    """Profile a short stretch inside the window (traces are large and
    tracing slows the host); the engine's counters at both ends."""
    import jax

    from benchmark.harness import system

    tr = traffic.get("trace", {})
    t_start = t_open + min(float(tr.get("start_s", 5.0)), seconds / 3)
    length = min(float(tr.get("seconds", 4.0)), seconds / 3)
    trace_dir = os.path.join(system.OUT_DIR, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    _sleep_until(t_start)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    at_start = _engine_counters(llm)
    _sleep_until(t_start + length)
    at_stop = _engine_counters(llm)
    jax.profiler.stop_trace()
    return {"open": at_start, "close": at_stop, "dir": trace_dir}


def run_cell(cell: dict, config: dict, traffic: dict, metrics: list, *,
             seed: int, seconds: float, trace: bool, allow_cpu: bool = False,
             bench_dir: str = BENCH_DIR) -> dict:
    """Set up, ramp, measure, drain, reduce. Returns the printed object."""
    from benchmark.harness import roofline, stats, system, xplane
    from benchmark.harness import traffic as traffic_mod
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    devices = system.require_devices(int(cell["chips"]), allow_cpu)
    if not allow_cpu:
        setup_compile_cache()
    compiles = CompileCounter()
    vocab = int(config["vocab_size"])
    schedule = traffic_mod.build_schedule(traffic, seed, seconds, vocab)

    b = system.build(config, seed, devices)
    endpoint = traffic.get("endpoint", "completions")
    system.warm_up(b, _reached_buckets(b.llm, schedule, config, endpoint))
    b.llm.start()
    server = system.serve(b, config)
    chain = gen = None
    try:
        t0 = time.monotonic()
        ref = check_reference(b, server.url, config, seed)
        b.phases["reference_s"] = time.monotonic() - t0
        target = server.url
        if endpoint == "chain_generate":
            t0 = time.monotonic()
            chain = system.ChainChild(config, server.url)
            chain.wait_healthy()
            chain.ingest(traffic_mod.corpus_files(traffic, seed, vocab))
            b.phases["ingest_s"] = time.monotonic() - t0
            target = chain.url
        gen = system.LoadGen(target, endpoint, "bench", schedule)
        misses_at_ready = compiles.cache_misses
        ramp_s = float(schedule["ramp_s"])
        t_open = time.monotonic() + ramp_s + 0.25
        t_close = t_open + seconds
        started = _engine_counters(b.llm)
        gen.go(t_open)
        _sleep_until(t_open)
        compiles_at_open = compiles.compiles
        at_open = _engine_counters(b.llm)
        setup_s = t_open - T_PROCESS_START
        traced = (_trace_stretch(b.llm, traffic, t_open, seconds)
                  if trace else None)
        _sleep_until(t_close)
        at_close = _engine_counters(b.llm)
        records = gen.result(DRAIN_TIMEOUT_S + max(0.0, t_close
                                                   - time.monotonic()))
        gen = None
        time.sleep(0.3)  # let the last retire reach the engine's counters
        ended = _engine_counters(b.llm)
        compiles_in_run = compiles.compiles - compiles_at_open
        events = b.llm.flight.snapshot_events()
        clock_skew = time.perf_counter() - time.monotonic()
        for ev in events:
            ev["t"] = ev["ts"] - clock_skew - t_open
    finally:
        if gen is not None:
            gen.stop()
        if chain is not None:
            chain.stop()
        server.stop()
        b.llm.stop()

    dev0 = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    summary = None
    if traced is not None:
        path = xplane.find_xplane(traced["dir"])
        summary = xplane.reduce(xplane.load(path)) if path else None
        shutil.rmtree(traced["dir"], ignore_errors=True)
    peaks = None
    if dev0.platform == "tpu":
        peaks = roofline.load_peaks(bench_dir, dev0.device_kind)

    attempted = len(records)
    failed = sum(1 for r in records if not r["ok"])
    # (the reference check's tokens were generated before `started`)
    asked = sum(r["asked"] for r in records)
    made = ended["tokens_out"] - started["tokens_out"]
    correct = (ref["ok"] and failed == 0 and made == asked
               and compiles_in_run == 0)
    ctx = {
        "cell": cell, "config": config, "traffic": traffic,
        "seconds": float(seconds), "records": records, "chips": len(devices),
        "phases": dict(b.phases, total_s=setup_s, ramp_s=ramp_s),
        "counters": {"cache_misses": misses_at_ready,
                     "compiles_in_window": compiles_in_run},
        "engine": {"open": at_open, "close": at_close, "events": events,
                   "trace_open": traced and traced["open"],
                   "trace_close": traced and traced["close"]},
        "trace": summary, "peaks": peaks,
    }
    out_metrics = {}
    for m in metrics:
        value = read_metric(m["name"], ctx, bench_dir)
        if value is not None:
            out_metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": out_metrics, "device": device,
              "checks": {"reference": ref, "tokens_asked": asked,
                         "tokens_generated": made,
                         "compiles_in_window": compiles_in_run,
                         "requests_due_in_window": len(
                             stats.due_in_window(records, seconds)),
                         "candidates": _candidates(records, seconds)}}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    return result


def check_lines(result: dict) -> list:
    """Each number `correct` compared, beside its limit: the last lines of
    standard error, which the driver keeps where a run is not correct."""
    chk = result["checks"]
    ref = chk["reference"]
    return [
        "check reference.worst_shortfall %s limit %s%s" % (
            ref.get("worst_shortfall"), ref.get("rel_tol"),
            " (%s)" % ref["why"] if "why" in ref else ""),
        "check failed_requests %d limit 0" % result["failed"],
        "check tokens_generated %d must equal tokens_asked %d" % (
            chk["tokens_generated"], chk["tokens_asked"]),
        "check compiles_in_window %d limit 0" % chk["compiles_in_window"],
        "correct %s" % result["correct"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import system, traffic

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json "
              f"(known: {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    result = run_cell(
        cell, system.load_config(BENCH_DIR, cell["config"]),
        traffic.load_traffic(BENCH_DIR, cell["traffic"]),
        cell_metrics(bench, cell["name"], bool(args.trace)),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    sys.stdout.flush()
    print("\n".join(check_lines(result)), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
