#!/usr/bin/env python3
"""A configuration with gated attention over window and global layers
beside a share of the experts on the chip (Trinity-Large-Preview's share
of an 8-chip expert-parallel group, `trinity-large-preview-int8-ep8`), of
the family of scripts/check_window_on_chip.py:

    chiprun -- timeout 3000 python3 scripts/check_gated_window_on_chip.py \
        [--phases memory,buckets,compare,streams,step,ramp] [--seeds 1] \
        [--buckets 4608,20480] [--prompts 4608,20480]
    chiprun -- timeout 3400 python3 scripts/check_gated_window_on_chip.py \
        --phases cell [--cell-seeds 3] [--base-seed 2147520000]

One JSON line each result (also chiprun_out/gated_window/check.jsonl):

  memory   the memory plan (serving/memory_plan.py) beside what the device
           reports once weights and both pools are on it.
  buckets  each prefill program alone (`prefill_batch_step`, one prompt
           that fills the bucket) over the configuration's whole pool, both
           groups of rows written through their own tables: ms a program
           and tokens a second, so that a bucket off the line its
           neighbours make is seen before it is served (PERF.md section 5's
           rule).
  compare  the logits of the step programs at the published widths (a
           prompt of each of `--prompts` tokens through `prefill_step`,
           default the LONGEST bucket: past the window, the pages behind
           it never taken; then four decode steps through both tables,
           teacher forced) against the benchmark's plain reference computed
           in blocks, each number beside its limit; and two negative
           controls, the reference with no gate and the reference with no
           window, which must miss.
  streams  32 prompts of the shortest bucket prefilled into the 32 slots,
           then 256 greedy steps in blocks of 8: the distinct tokens a
           stream emits, the distinct tokens the slots hold in a step, and
           the share of (expert layer, held expert, step) triples in which
           the expert took a pair, beside what uniform routing would give.
  ramp     (last: it builds the ENGINE, which takes the chip's memory)
           the traffic file's 32 prompts submitted at once through
           `LLMEngine`, as the cell's 32 clients do: seconds until the last
           has its first token, which is what the cell's `ramp_s` covers.
  window   (alone) ONE untraced run of the cell in this process with the
           ledger's metrics, and what the flight recorder holds: seconds
           between the last first token and the window's opening, the
           prefill programs and retires that fell inside the window.
  step     `decode_multi_step` (blocks of 1, 2 and 8 steps) at 32 live
           slots and contexts near 16k over a pool of zeros: ms a step by
           the host's clock.
  cell     NO JAX in this process: the benchmark's own command for
           `trinity-large-ep8.longctx-closed32`, one child a run, six
           untraced runs on `--cell-seeds` seeds (each twice) and one
           traced; every result line, and the spread of `out_tokens_per_s`.

`--rehearse` is the control flow on the CPU at the tests' tiny size, never
a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "trinity-large-ep8.longctx-closed32"
CONFIG = "trinity-large-preview-int8-ep8"

# The logits of prefill and decode against the reference, as shares of the
# largest logit. The program multiplies bf16 activations into int8 weights
# and reads K and V back from int8 pages where the reference keeps float32
# throughout. Each limit lies between two readings on the chip (my chip
# runs, PR 52; a 20,480-token prompt and four decode steps): the program
# reads 0.0056 on the median row and 0.0076 at worst (0.0076 / 0.0081 with
# the branch-ending norms at the cut's own depth); the reference with NO
# GATE reads 0.086-0.097 on every row and the reference with NO WINDOW
# 0.16-0.19 (0.21-0.23 and 0.39-0.49 at the cut's own depth), and each
# must fail both.
MEDIAN_TOL = 0.02   # the median row
LOGIT_TOL = 0.05    # the worst row


def run_cell(args) -> int:
    """The cell itself, a child a run (a chip belongs to one process)."""
    out_dir = os.path.join(ROOT, "chiprun_out", "gated_window")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "cell.jsonl"), "a")
    seeds = [args.base_seed + i for i in range(args.cell_seeds)]
    runs = [(s, 0) for s in seeds for _ in range(max(1, 6 // len(seeds)))]
    runs.append((args.base_seed + len(seeds), 1))
    tokens, rc = [], 0
    for seed, trace in runs:
        cmd = ["timeout", "900", sys.executable, "benchmark/run.py",
               "--workload", CELL, "--seed", str(seed), "--seconds", "45",
               "--trace", str(trace)]
        t0 = time.perf_counter()
        res = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        last = (res.stdout.strip().splitlines() or [""])[-1]
        line = {"phase": "cell", "seed": seed, "trace": trace,
                "rc": res.returncode, "wall_s": time.perf_counter() - t0}
        try:
            line["result"] = json.loads(last)
        except ValueError:
            line["stdout_tail"] = res.stdout[-2000:]
        if res.returncode or not line.get("result", {}).get("correct"):
            line["stderr_tail"] = res.stderr[-4000:]
            rc = 1
        elif not trace:
            tokens.append(line["result"]["metrics"]["out_tokens_per_s"][
                "value"])
        print(json.dumps(line), flush=True)
        log.write(json.dumps(line) + "\n")
        log.flush()
        if rc:  # the next run would fail the same way
            break
    if len(tokens) >= 3:
        q = statistics.quantiles(tokens, n=4)
        line = {"phase": "cell", "out_tokens_per_s": tokens,
                "median": statistics.median(tokens),
                "spread": (q[2] - q[0]) / statistics.median(tokens),
                "half_bound": 0.0075}
        print(json.dumps(line), flush=True)
        log.write(json.dumps(line) + "\n")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", default="memory,buckets,compare,streams,step")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--buckets", default="",
                    help="prefill buckets to time; default: the file's")
    ap.add_argument("--prompts", default="",
                    help="prompt lengths `compare` runs; default: the "
                         "longest bucket")
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--gain-scales", default="",
                    help="`streams` with the seeded gains of the norms "
                         "that end a branch scaled by each of these")
    ap.add_argument("--bias-scales", default="",
                    help="`streams` with the selection's seeded bias "
                         "scaled by each of these; default: as drawn")
    ap.add_argument("--cell-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=2147520000)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    phases = args.phases.split(",")
    if phases == ["cell"]:
        return run_cell(args)
    if "cell" in phases:
        raise SystemExit("`cell` runs alone: its children need the chip")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.architectures import afmoe as entry
    from benchmark.harness import system
    from generativeaiexamples_tpu.models import gated_window_moe as gwm
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving import memory_plan
    from generativeaiexamples_tpu.serving.kv_cache import (
        PageAllocator, WindowPool, WindowSequencePages, WindowTables,
        engine_window_table_pages, window_pool_pages)
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("check_gated_window_on_chip: no TPU; refusing")
    if args.rehearse:
        from benchmark.tests.test_afmoe import tiny_file
        config = tiny_file()
        step_context, n_new = 40, 4
        args.steps = min(args.steps, 16)
    else:
        setup_compile_cache()
        config = system.load_config(os.path.join(ROOT, "benchmark"), CONFIG)
        step_context, n_new = 16384, 4
    use_pallas = not args.rehearse
    mcfg = entry.model_config(config)
    ecfg = system.engine_config(config)
    ps, B = ecfg.page_size, ecfg.max_batch_size
    maxp = ecfg.max_seq_len // ps
    wr = mcfg.window_rows
    maxw = engine_window_table_pages(wr.window, ecfg)
    n_pages = int(config["serving"]["n_pages"])
    n_win = window_pool_pages(wr.window, ecfg)
    vocab = mcfg.vocab_size
    out_dir = os.path.join(ROOT, "chiprun_out", "gated_window")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "check.jsonl"), "a")

    def say(**kw):
        line = json.dumps(dict(kw, device=dev.device_kind,
                               rehearsal=bool(args.rehearse)))
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    def timed(fn, *a, reps=args.reps):
        """Seconds a call, after one that compiles."""
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = fn(*a)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps

    def slot_tables(lengths, more=0):
        """Page tables as the engine builds them for slots of `lengths`
        cached tokens (each the step's length, its token included) with
        room for `more` tokens: slot b's global pages 1 + b * maxp .., its
        window pages 1 + b * maxw .. from the page its window starts in."""
        glob = np.zeros((B, maxp), np.int32)
        win = np.zeros((B, maxw), np.int32)
        base = np.zeros((B,), np.int32)
        for b, n in enumerate(lengths):
            pages = -(-(int(n) + more) // ps)
            glob[b, :pages] = 1 + b * maxp + np.arange(pages)
            first = max(0, int(n) - wr.window) // ps
            win[b, :pages - first] = 1 + b * maxw + np.arange(pages - first)
            base[b] = first * ps
        return WindowTables(jnp.asarray(glob), jnp.asarray(win),
                            jnp.asarray(base))

    def prompt_tables(slot, bucket, n):
        """A prefill's tables for a prompt of `n` tokens in `slot`, as
        `slot_tables` lays the slot out for the step at length n + 1."""
        rows = np.zeros((1, bucket // ps), np.int32)
        win = np.zeros_like(rows)
        pages = -(-n // ps)
        rows[0, :pages] = 1 + slot * maxp + np.arange(pages)
        first = max(0, n + 1 - wr.window) // ps
        win[0, first:pages] = 1 + slot * maxw + np.arange(pages - first)
        return WindowTables(jnp.asarray(rows), jnp.asarray(win))

    params = None

    def model(seed):
        nonlocal params
        params = None  # one set of weights on the device at a time
        params = jax.block_until_ready(gwm.init_params_on_device(
            mcfg, seed, quantize=ecfg.quantize_weights == "int8"))
        return params

    state = {"pool": None, "last": None}  # ONE pool on the device at a time

    def new_pool():
        state["pool"] = None
        state["pool"] = jax.block_until_ready(
            WindowPool.zeros(mcfg, n_pages, n_win, ps))

    def prefill_program(ids, n, tables):
        toks, state["pool"] = em.prefill_batch_step(
            params, mcfg, state["pool"], ids, jnp.asarray([n], jnp.int32),
            tables, jnp.zeros(1), jnp.ones(1), jnp.zeros(1, jnp.int32),
            jax.random.PRNGKey(0), use_pallas,
            sampling_flags=(True, False, False))
        return toks

    if "memory" in phases:
        model(0)
        new_pool()
        plan = memory_plan.plan_engine_memory(
            mcfg, ecfg, axis_sizes={}, strict=False,
            hbm_bytes_per_device=(dev.memory_stats() or {}).get(
                "bytes_limit", 16 * 2**30))
        stats = dev.memory_stats() or {}
        held = sum(x.nbytes for x in jax.tree.leaves((params,
                                                      state["pool"])))
        say(phase="memory",
            plan=dict({l.name: l.bytes_per_device for l in plan.lines},
                      paged_pool=n_pages * plan.page_bytes_per_device,
                      fit_pages=plan.fit_pages),
            weights_and_pools_bytes=held,
            device_bytes_in_use=stats.get("bytes_in_use"),
            device_bytes_limit=stats.get("bytes_limit"),
            share_of_limit=held / stats["bytes_limit"]
            if stats.get("bytes_limit") else None)

    if "buckets" in phases:
        if params is None:
            model(0)
        new_pool()
        buckets = [int(b) for b in args.buckets.split(",") if b] \
            or list(ecfg.prefill_buckets)
        for bucket in buckets:
            tables = prompt_tables(0, bucket, bucket)
            ids = jnp.asarray(np.random.default_rng(bucket).integers(
                0, vocab, (1, bucket)), jnp.int32)
            t0 = time.perf_counter()
            jax.block_until_ready(prefill_program(ids, bucket, tables))
            first_s = time.perf_counter() - t0
            sec = timed(prefill_program, ids, bucket, tables, reps=3)
            stats = dev.memory_stats() or {}
            say(phase="buckets", bucket=bucket, ms=sec * 1e3,
                tokens_per_s=bucket / sec, first_call_s=first_s,
                peak_bytes=stats.get("peak_bytes_in_use"))

    if "compare" in phases:
        lengths = [int(p) for p in args.prompts.split(",") if p] \
            or [max(ecfg.prefill_buckets)]
        for seed in range(args.seeds):
            for prompt_n in lengths:
                state["pool"] = None
                model(1000 + seed)
                new_pool()
                rng = np.random.default_rng([seed, 0xC0, prompt_n])
                ids = rng.integers(0, vocab, prompt_n + n_new).astype(
                    np.int32)
                seq = WindowSequencePages(
                    PageAllocator(n_pages), PageAllocator(n_win), ps, maxp,
                    wr.window, maxw)
                seq.ensure(prompt_n)
                bucket = next(b for b in sorted(ecfg.prefill_buckets)
                              if b >= prompt_n)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :prompt_n] = ids[:prompt_n]
                rows = np.zeros((bucket // ps,), np.int32)
                rows[:len(seq.pages)] = seq.pages
                win = np.zeros_like(rows)
                win[seq.window_first: seq.window_first
                    + len(seq.window_pages)] = seq.window_pages
                logits, state["pool"] = em.prefill_step(
                    params, mcfg, state["pool"], jnp.asarray(toks),
                    jnp.int32(prompt_n),
                    WindowTables(jnp.asarray(rows), jnp.asarray(win)),
                    use_pallas)
                got = [np.asarray(logits)]
                for j in range(n_new):       # teacher forced, step by step
                    n = prompt_n + 1 + j
                    seq.ensure(n)
                    row, base = seq.window_row()
                    tables = WindowTables(
                        jnp.asarray(seq.table_row())[None],
                        jnp.asarray(row)[None],
                        jnp.asarray([base], jnp.int32))
                    step, state["pool"] = em.decode_step(
                        params, mcfg, state["pool"],
                        jnp.asarray(ids[n - 1])[None],
                        tables, jnp.asarray([n], jnp.int32), use_pallas)
                    got.append(np.asarray(step[0]))
                    seq.slide(n + 1 - wr.window)
                state["pool"] = None  # room for the reference
                got = np.stack(got)
                at = slice(prompt_n - 1, prompt_n + n_new)
                t0 = time.perf_counter()
                want = entry.reference_forward(config, params, ids)[0][at]
                ref_s = time.perf_counter() - t0
                ungated = entry.reference_forward(config, params, ids,
                                                  gate=False)[0][at]
                dense = entry.reference_forward(config, params, ids,
                                                windowed=False)[0][at]

                def rel(a, b):
                    return (np.abs(a - b).max(-1) / np.abs(b).max()).tolist()

                r = rel(got, want)
                misses = {"no_gate": rel(got, ungated),
                          "no_window": rel(got, dense)}
                ok = bool(np.median(r) <= MEDIAN_TOL and max(r) <= LOGIT_TOL
                          and all(np.median(m) > MEDIAN_TOL
                                  and max(m) > LOGIT_TOL
                                  for m in misses.values()))
                say(phase="compare", seed=1000 + seed, prompt=prompt_n,
                    bucket=bucket, rows_prefill_then_decode=r,
                    median=float(np.median(r)), median_limit=MEDIAN_TOL,
                    worst=max(r), worst_limit=LOGIT_TOL,
                    argmax_agree=int((got.argmax(-1) == want.argmax(-1)
                                      ).sum()), of=len(r),
                    another_models_reference_misses_by=misses,
                    window_pages_not_taken=int(seq.window_first),
                    largest_logit=float(np.abs(want).max()),
                    reference_s=ref_s, ok=ok)
                if not ok:
                    return 1

    def streams_phase(seed, bias_scale, gain_scale):
        state["pool"] = None
        model(seed)
        params["layers"]["router_bias"] = \
            params["layers"]["router_bias"] * bias_scale
        for stack in ("dense", "layers"):
            for name in ("ln1_post", "ln2_post"):
                params[stack][name] = (params[stack][name].astype(
                    jnp.float32) * gain_scale).astype(mcfg.dtype)
        new_pool()
        bucket = min(ecfg.prefill_buckets)
        rng = np.random.default_rng(3)
        first = []
        for b in range(B):
            n = bucket - int(rng.integers(0, 2 * ps))
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :n] = rng.integers(0, vocab, n)
            first.append((n, prefill_program(
                jnp.asarray(ids), n, prompt_tables(b, bucket, n))))
        lengths = np.asarray([n + 1 for n, _ in first], np.int32)
        state["last"] = jnp.concatenate([t for _, t in first]).astype(
            jnp.int32)
        K = ecfg.decode_steps_per_dispatch
        E, Lm = mcfg.experts_held, mcfg.n_moe_layers
        streams, hits = [], []
        for _ in range(args.steps // K):
            tables = slot_tables(lengths, more=K)
            blk, state["last"], state["pool"] = em.decode_multi_step(
                params, mcfg, state["pool"], state["last"], tables,
                jnp.asarray(lengths), jnp.ones((B,), bool), jnp.zeros(B),
                jnp.ones(B), jnp.zeros(B, jnp.int32), jax.random.PRNGKey(0),
                K, use_pallas, sampling_flags=(True, False, False))
            blk = np.asarray(blk)
            streams.append(blk[:B, 1:])
            hits.append((blk[B:, 1:] > 0).reshape(Lm, E, K))
            lengths = lengths + K
        streams = np.concatenate(streams, axis=1)        # [B, steps]
        hits = np.concatenate(hits, axis=2)              # [Lm, E, steps]
        freq = hits.mean(axis=2).reshape(-1)  # steps a held expert is hit in
        say(phase="streams", seed=seed, bias_scale=bias_scale,
            post_norm_gain_scale=gain_scale, slots=B,
            steps=int(streams.shape[1]),
            hit_frequency_of_an_expert_quartiles=np.percentile(
                freq, [0, 25, 50, 75, 100]).tolist(),
            distinct_tokens_a_stream=[int(len(set(s))) for s in streams],
            distinct_tokens_a_step_mean=float(np.mean(
                [len(set(c)) for c in streams.T])),
            experts_hit_share=float(hits.mean()),
            experts_hit_share_by_layer=hits.mean(axis=(1, 2)).tolist(),
            uniform_routing_would_hit=entry.experts_hit(config, B) / E,
            experts_never_hit=int((~hits.any(axis=2)).sum()),
            of_experts=Lm * E)

    if "streams" in phases:
        scales = [float(x) for x in args.bias_scales.split(",") if x] \
            or [1.0]
        gains = [float(x) for x in args.gain_scales.split(",") if x] \
            or [1.0]
        for gain in gains:
            for scale in scales:
                for seed in range(args.seeds):
                    streams_phase(seed, scale, gain)

    if "window" in phases:
        # ONE untraced run of the cell in this process (benchmark/run.py's
        # `run_cell`, the ledger's and the counters' metrics with it) and
        # what the flight recorder holds of its ramp and window: when the
        # last of the 32 had its first token, and every prefill program
        # and retire that fell INSIDE the window (there should be none)
        from benchmark import run as bench_run
        from benchmark.harness import traffic as traffic_mod
        bench = bench_run.load_benchmark()
        cell = next(w for w in bench["workloads"] if w["name"] == CELL)
        if args.rehearse:
            from benchmark.tests import test_rehearsal
            traffic = test_rehearsal.CLOSED
        else:
            traffic = traffic_mod.load_traffic(
                os.path.join(ROOT, "benchmark"), cell["traffic"])
        metrics = bench_run.cell_metrics(bench, CELL, False) \
            + bench_run.cell_metrics(bench, CELL, True)
        seen, read = {}, bench_run.read_metric

        def spy(name, ctx, *a, **kw):
            seen["ctx"] = ctx
            return read(name, ctx, *a, **kw)

        bench_run.read_metric = spy
        seconds = 3.0 if args.rehearse else 45.0
        out = bench_run.run_cell(cell, config, traffic, metrics,
                                 seed=args.base_seed, seconds=seconds,
                                 trace=False, allow_cpu=args.rehearse)
        events = seen["ctx"]["engine"]["events"]
        firsts = sorted(e["t"] for e in events if e["kind"] == 6)
        inside = [e for e in events if 0.0 <= e["t"] < seconds]
        say(phase="window", seed=args.base_seed, correct=out["correct"],
            metrics={k: v["value"] for k, v in out["metrics"].items()},
            ramp_s=float(traffic["ramp_s"]),
            first_tokens=len(firsts),
            last_first_token_before_the_window_s=-firsts[-1]
            if firsts else None,
            prefill_programs_in_window=[
                (round(e["t"], 2), round(e["b"], 1)) for e in inside
                if e["kind"] == 20 and e["code"] == 1],
            retires_in_window=sum(e["kind"] == 7 for e in inside),
            memory_peak_bytes=out["device"].get("memory_peak_bytes"))
        return 0

    if "ramp" in phases:
        # the cell's ramp through the ENGINE: the traffic file's 32 prompts
        # submitted at once, as its 32 clients do, and the time until the
        # last of them has its first token (every prompt prefilled, one
        # program at a time between the live slots' decode blocks)
        import threading

        from benchmark.harness import traffic as traffic_mod
        state["pool"] = params = None
        bench_dir = os.path.join(ROOT, "benchmark")
        if args.rehearse:
            from benchmark.tests import test_rehearsal
            traffic = test_rehearsal.CLOSED
        else:
            traffic = traffic_mod.load_traffic(bench_dir, "longctx-closed32")
        schedule = traffic_mod.build_schedule(traffic, args.base_seed, 45.0,
                                              vocab)
        b = system.build(config, 0, [dev])
        system.warm_up(b, sorted(ecfg.prefill_buckets))
        b.llm.start()
        reqs = schedule["requests"][:int(traffic["clients"])]
        firsts, t0 = [None] * len(reqs), time.perf_counter()

        def client(i, ids):
            for _ in b.llm.generate_stream(list(ids), max_new_tokens=min(
                    600, ecfg.max_seq_len - len(ids) - 1), temperature=0.0):
                if firsts[i] is None:
                    firsts[i] = time.perf_counter() - t0

        threads = [threading.Thread(target=client, args=(i, r["prompt_ids"]))
                   for i, r in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b.llm.stop()
        last = max(firsts)
        say(phase="ramp", clients=len(reqs),
            prompt_tokens=sum(len(r["prompt_ids"]) for r in reqs),
            last_first_token_s=last, first_token_s=sorted(firsts),
            ramp_plus_a_fifth_to_whole_4_s=4 * -(-last * 1.2 // 4),
            memory_peak_bytes=(dev.memory_stats() or {}).get(
                "peak_bytes_in_use"))
        return 0

    if "step" in phases:
        state["pool"] = None
        if params is None:
            model(0)
        new_pool()
        rng = np.random.default_rng(2)
        lengths = np.clip(step_context - rng.integers(0, 2 * ps, B), 1,
                          maxp * ps - 16).astype(np.int32)
        state["last"] = jnp.zeros((B,), jnp.int32)
        for K in (1, 2, ecfg.decode_steps_per_dispatch):
            tables = slot_tables(lengths, more=K)

            def block():
                blk, state["last"], state["pool"] = em.decode_multi_step(
                    params, mcfg, state["pool"], state["last"], tables,
                    jnp.asarray(lengths), jnp.ones((B,), bool),
                    jnp.zeros(B), jnp.ones(B), jnp.zeros(B, jnp.int32),
                    jax.random.PRNGKey(0), K, use_pallas,
                    sampling_flags=(True, False, False))
                return blk

            sec = timed(block, reps=5)
            say(phase="step", slots=B, context=int(lengths.mean()), K=K,
                ms_per_step=sec / K * 1e3, tokens_per_s=B * K / sec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
