#!/usr/bin/env python3
"""What the host was doing in a benchmark cell's window, run by run, and
what the instrumentation that says so costs (PERF.md section 6, PR 54):

    chiprun --timeout 3500 -- python3 scripts/measure_host_pauses.py \\
        --cell mistral7b.decode-closed64 \\
        --runs parent:101,on:101,off:101,off:102,parent:102,on:102 \\
        [--parent .scratch/parent] [--seconds 45]

One child process a run (a chip belongs to one process; this parent
imports no JAX), each under `timeout 900`, all sharing one compile cache.
`<side>:<seed>[:t]`, `t` for a traced run:

  parent  the plain command (`benchmark/run.py`) in `--parent`, a `git
          archive` of another commit: its result line's metrics
  on      this tree, `benchmark/run.py::run_cell` in the child with the
          cell's end-to-end AND per-layer metrics, and from the flight
          ring: the high-water mark `flight_events`, every `host_pause`
          event of the window, the collections by generation between
          the window's edges (`gc.get_stats()`), the container's CPU
          throttling between them (`cpu.stat`), the engine's own sums,
          the window's longest programs with their `call`, the host's
          cover by cause and the pauses that overlap each, and every
          `device program stalled` line whole
  off     the same with `engine.flight_recorder` false: the end-to-end
          metrics alone (nothing is stamped)

One JSON line a run (also chiprun_out/host_pauses/<cell>.jsonl), then a
table of the end-to-end metrics by side and seed. `--rehearse` is the
control flow on the CPU at the tests' tiny size, never a measurement.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "host_pauses")
LONGEST = 4         # programs listed a run
FREEZE_MS = 100.0   # ... and every one that ran longer than this


def child(args) -> int:
    """One run of the cell in this process."""
    import gc
    import logging

    from benchmark import run as bench_run
    from benchmark.harness import system
    from benchmark.harness import traffic as traffic_mod
    from benchmark.readers import host_pause
    from generativeaiexamples_tpu.serving import flight

    bench = bench_run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.cell)
    if args.rehearse:
        from benchmark.tests import test_rehearsal
        config = json.loads(json.dumps(test_rehearsal.TINY))
        traffic = (test_rehearsal.CLOSED if "closed" in args.cell
                   else test_rehearsal.OPEN)
        cell = dict(cell, chips=1)
    else:
        config = system.load_config(bench_run.BENCH_DIR, cell["config"])
        traffic = traffic_mod.load_traffic(bench_run.BENCH_DIR,
                                           cell["traffic"])
    on = args.recorder == "on"
    if not on:
        config["serving"].setdefault("engine", {})["flight_recorder"] = False
    metrics = bench_run.cell_metrics(bench, args.cell, bool(args.trace))
    if on and not args.trace:
        metrics = metrics + bench_run.cell_metrics(bench, args.cell, True)

    seen, read, counters = {}, bench_run.read_metric, bench_run._engine_counters
    edges = []

    def spy_read(name, ctx, *a, **kw):
        seen["ctx"] = ctx
        return read(name, ctx, *a, **kw)

    def cpu_stat():
        """The container's CPU accounting, where the kernel shows it: a
        process the scheduler THROTTLES stands still without a cause of
        its own (`nr_throttled`, `throttled_usec`)."""
        for path in ("/sys/fs/cgroup/cpu.stat",
                     "/sys/fs/cgroup/cpu/cpu.stat"):
            try:
                with open(path) as fh:
                    pairs = dict(ln.split() for ln in fh if ln.strip())
            except OSError:
                continue
            return {k: int(v) for k, v in pairs.items()
                    if k in ("nr_periods", "nr_throttled", "throttled_usec",
                             "throttled_time", "usage_usec")}
        return {}

    def spy_counters(llm):
        # started, the window's opening, its close, ended
        m = llm.metrics
        edges.append({"gc": [s["collections"] for s in gc.get_stats()],
                      "cpu": cpu_stat(),
                      "host_gc_collections": m.host_gc_collections,
                      "host_gc_pause_ms": m.host_gc_pause_ms,
                      "host_gc_pauses": m.host_gc_pauses,
                      "host_late_wakes": m.host_late_wakes,
                      "program_stalls": m.program_stalls,
                      "program_stalls_host": m.program_stalls_host})
        return counters(llm)

    stalls = []

    class Keep(logging.Handler):
        def emit(self, record):
            if "device program stalled" in record.getMessage():
                stalls.append(record.getMessage())

    logging.getLogger("generativeaiexamples_tpu.serving.engine").addHandler(
        Keep(level=logging.WARNING))
    bench_run.read_metric, bench_run._engine_counters = spy_read, spy_counters
    out = bench_run.run_cell(cell, config, traffic, metrics, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             allow_cpu=args.rehearse)
    line = {"side": args.recorder, "cell": args.cell, "seed": args.seed,
            "trace": args.trace, "correct": out["correct"],
            "failed": out["failed"], "device": out["device"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "stall_lines": stalls}
    if "breakdown" in out:
        line["idle_gaps"] = out["breakdown"]["idle_gaps"]
    if on and "ctx" in seen:
        ctx = seen["ctx"]
        events = ctx["engine"]["events"]
        opened, closed = edges[1], edges[2]
        line["flight_events"] = max(e["seq"] for e in events) + 1
        line["ring"] = len(events)
        line["window"] = {
            "gc_collections_by_generation": [
                b - a for a, b in zip(opened["gc"], closed["gc"])],
            "cpu_stat": {k: closed["cpu"][k] - opened["cpu"][k]
                         for k in opened["cpu"]},
            **{k: closed[k] - opened[k] for k in opened
               if k not in ("gc", "cpu")}}
        held = [e for e in events if e["kind"] == host_pause.HOST_PAUSE]
        line["pauses_in_window"] = [
            {"t": round(e["t"], 3), "ms": round(e["a"], 2),
             "cause": flight.PAUSE_CAUSES[e["code"]], "aux": e["aux"]}
            for e in held if 0.0 <= e["t"] < args.seconds]
        progs = [e for e in events if e["kind"] == host_pause.PROGRAM
                 and 0.0 <= e["t"] < args.seconds]
        known = [(e["t"] - e["a"] / 1e3, e["t"],
                  flight.PAUSE_CAUSES[e["code"]]) for e in held]
        calls = []
        for e in events:
            if e["kind"] == host_pause.PROGRAM:
                aux = flight.parse_program_aux(e["aux"])
                t_enq = e["t"] - e["a"] / 1e3
                calls.append((t_enq, t_enq + float(aux["call"]) / 1e3,
                              flight.CAUSE_DISPATCH_CALL))
        by_b = sorted(progs, key=lambda e: -e["b"])
        listed = by_b[:LONGEST] + [e for e in by_b[LONGEST:]
                                   if e["b"] >= FREEZE_MS]
        line["longest_programs"] = []
        for e in listed:
            aux = flight.parse_program_aux(e["aux"])
            t0, t1 = e["t"] - e["b"] / 1e3, e["t"]
            host_ms, by = flight.host_cover(t0, t1, known + calls)
            after = sorted((p for p in progs if p["t"] > e["t"]),
                           key=lambda p: p["t"])[:3]
            line["longest_programs"].append({
                "t": round(e["t"], 3), "cls": flight.PROGRAM_CLASSES[e["code"]],
                "shape": aux["shape"], "seq": int(aux["seq"]),
                "ran_ms": round(e["b"], 2), "waited_ms": round(e["a"], 2),
                "call_ms": float(aux["call"]), "stalled": "stalled" in aux,
                "host_ms": round(host_ms, 2),
                "by_cause": {k: round(v, 2) for k, v in by.items()},
                "overlapping": [
                    {"cause": c, "ms": round((b - a) * 1e3, 2),
                     "at": round(a - t0, 3)}
                    for a, b, c in sorted(known + calls)
                    if b > t0 and a < t1 and (b - a) * 1e3 >= 1.0],
                "next_ran_ms": [round(p["b"], 2) for p in after]})
    print(json.dumps(line), flush=True)
    return 0


def run_one(args, side, seed, trace, env):
    if side == "parent":
        cmd = ["timeout", "900", sys.executable, "benchmark/run.py",
               "--workload", args.cell, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(trace)]
        cwd = os.path.join(ROOT, args.parent)
    else:
        cmd = ["timeout", "900", sys.executable,
               os.path.join("scripts", "measure_host_pauses.py"), "--child",
               "--cell", args.cell, "--seed", str(seed), "--recorder", side,
               "--seconds", str(args.seconds), "--trace", str(trace)]
        if args.rehearse:
            cmd.append("--rehearse")
        cwd = ROOT
    t0 = time.monotonic()
    proc = subprocess.run(cmd, cwd=cwd, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True)
    took = time.monotonic() - t0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        line = json.loads(last)
    except ValueError:
        line = {"error": proc.stderr[-2000:]}
    if side == "parent" and "metrics" in line:
        line = {"side": "parent", "cell": args.cell, "seed": seed,
                "trace": trace, "correct": line["correct"],
                "failed": line["failed"], "device": line["device"],
                "metrics": {k: v["value"]
                            for k, v in line["metrics"].items()},
                **({"idle_gaps": line["breakdown"]["idle_gaps"]}
                   if "breakdown" in line else {})}
    line.update(side=side, seed=seed, rc=proc.returncode,
                took_s=round(took, 1))
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--runs", default="")
    ap.add_argument("--parent", default=".scratch/parent")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--recorder", choices=("on", "off"), default="on")
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.seconds = min(args.seconds, 3.0)
    if args.child:
        return child(args)

    os.makedirs(OUT_DIR, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    lines = []
    with open(os.path.join(OUT_DIR, args.cell + ".jsonl"), "a") as fh:
        for run in args.runs.split(","):
            side, seed, *rest = run.split(":")
            line = run_one(args, side, int(seed), int(bool(rest)), env)
            lines.append(line)
            fh.write(json.dumps(line) + "\n")
            fh.flush()
            print(json.dumps(line), flush=True)
    names = sorted({k for ln in lines for k in ln.get("metrics", {})
                    if "." not in k})
    print("\n" + " ".join(["side", "seed"] + names))
    for ln in lines:
        print(" ".join([ln["side"], str(ln["seed"])] + [
            repr(ln.get("metrics", {}).get(k)) for k in names]))
    return 0 if all(ln.get("rc") == 0 and ln.get("correct")
                    for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
