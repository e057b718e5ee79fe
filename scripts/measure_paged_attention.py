#!/usr/bin/env python3
"""The int8 paged-attention kernel alone (serving/paged_attention_int8.py),
on the chip, over pools of the benchmark cells' real shapes:

    chiprun -- python3 scripts/measure_paged_attention.py \
        [--shapes mistral,ouro,tp4] [--blocks 4,5,8] [--parent DIR]

    mistral  32 rows,  8 KV heads of 32, 64 slots,  768 pages, tables of 20
    ouro    192 rows, 16 KV heads of 16, 32 slots,  112 pages, tables of 4
            (a half of the pool is over SPLIT_KV_BYTES: split descriptors)
    tp4      40 rows,  2 KV heads of 8,  64 slots, 3072 pages, tables of 4
            (one chip's share of Mistral-Small-24B under TP=4, no mesh)

For every shape it compiles one program a form that calls the kernel once
a cache row, as a decode step does, and runs it over six sets of lengths
and live rows: `one` (every row idle, as the engine sends an idle slot:
length 1, `active` False; what an idle slot costs), `mix` (the closed
cells' contexts: a prompt of 32-128 plus a uniform share of an answer of
192-320, mean about 208, every row live), `mix60` (the same with 60 of 64
rows live, or 30 of 32: a closed cell's occupancy), `full` (every row at
the table's width: what the guards cost where nothing is dead), `chain`
(three rows of 1,700 tokens, the rest idle: `rag.chain-open`) and `open`
(three rows of 250 tokens, two pages each, apart among idle ones: the
open mix, `mistral7b.chat-open`; the append's side of both mixes is
scripts/measure_kv_append.py `--live 3,60,64`). The
forms: the kernel of this tree at each of `--blocks` pages a block, given
the step's mask, and with `--parent DIR` (a checkout of another commit,
e.g. `git archive` into .scratch/parent) that tree's kernel as it is; a
parent that takes no mask computes every row, an idle one at length 1.
Times are the device's: `--reps` executions by the host's clock around
`block_until_ready`, and three traced ones summed by operation. It also
reads how far each form's output is from the first form's, over the live
rows.

One JSON object a line on stdout and in chiprun_out/paged_attention/
probe.jsonl; never a measurement on the CPU (`--rehearse` is the same
control flow there at a tiny size, the kernel interpreted).
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PS, HD = 128, 128
KERNEL = "generativeaiexamples_tpu/serving/paged_attention_int8.py"
# name: (cache rows, kv heads, query heads, slots, pages, table width)
SHAPES = {"mistral": (32, 8, 32, 64, 768, 20),
          "ouro": (192, 16, 16, 32, 112, 4),
          "tp4": (40, 2, 8, 64, 3072, 4)}
TINY = {"mistral": (2, 2, 4, 4, 9, 5), "ouro": (3, 2, 2, 4, 7, 4),
        "tp4": (2, 1, 2, 4, 9, 4)}


def length_sets(rng, slots: int, width: int) -> dict:
    """name: (lengths, live rows). An idle row is what the engine sends:
    length 1, `active` False."""
    top = width * PS
    prompt = rng.integers(32, 129, slots)
    answer = rng.integers(192, 321, slots)
    mix = [min(int(x), top)
           for x in prompt + (rng.random(slots) * answer).astype(int)]
    every = [True] * slots
    idle = set(rng.permutation(slots)[:slots // 16].tolist())  # 4 of 64
    live60 = [b not in idle for b in range(slots)]
    chain = [min(1700 + 13 * b, top) if b < 3 else 1 for b in range(slots)]
    apart = set(range(2, slots, max(slots // 3, 1))[:3])  # three, apart
    open_mix = [min(250 + b, top) if b in apart else 1 for b in range(slots)]
    return {"one": ([1] * slots, [False] * slots), "mix": (mix, every),
            "mix60": ([x if a else 1 for x, a in zip(mix, live60)], live60),
            "full": ([top] * slots, every),
            "chain": (chain, [x > 1 for x in chain]),
            "open": (open_mix, [x > 1 for x in open_mix])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,ouro,tp4")
    ap.add_argument("--blocks", default="4,5,8",
                    help="pages a block to try for this tree's kernel")
    ap.add_argument("--parent", default=None,
                    help="a checkout whose kernel is measured beside it")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import xplane
    from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8
    from scripts.measure_qkv_forms import by_operation

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("measure_paged_attention: no TPU; refusing")
    parent_form = {}
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_paged_attention_int8", os.path.join(args.parent, KERNEL))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        parent_form["parent"] = (parent.paged_attention_int8, None)
    shapes = TINY if args.rehearse else SHAPES
    out_dir = os.path.join(ROOT, "chiprun_out", "paged_attention")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "probe.jsonl"), "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say(device=dev.device_kind, rehearsal=args.rehearse, reps=args.reps)
    for name in args.shapes.split(","):
        rows, KH, H, B, P, width = shapes[name]
        forms = dict(parent_form)
        for blk in sorted({min(int(x), width)
                           for x in args.blocks.split(",")}):
            forms[f"change{blk}"] = (pa8.paged_attention_int8, blk)
        shape = (2, rows, KH, P, PS, HD)

        @jax.jit
        def fresh_pool():
            def mix(shape, weights):
                return sum(w * jax.lax.broadcasted_iota(jnp.int32, shape, a)
                           for a, w in enumerate(weights))
            kv = (mix(shape, (131, 7, 29, 13, 3, 1)) % 255 - 127)
            s = (mix(shape[:-1], (11, 5, 3, 7, 1)) % 97).astype(jnp.float32)
            return kv.astype(jnp.int8), s * 1e-4 + 0.002

        kv, s = jax.block_until_ready(fresh_pool())
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.bfloat16)
        table = jnp.asarray(rng.integers(1, P, (B, width)), jnp.int32)
        sets = length_sets(rng, B, width)
        first = {}
        for form, (fn, blk) in forms.items():
            takes_mask = "live" in inspect.signature(fn).parameters

            def attend_rows(q, kv, s, table, lengths, active, fn=fn, blk=blk,
                            takes_mask=takes_mask):
                # the mask becomes the walk's order once, outside the
                # rows, as a step program takes it outside its layers
                kw = ({"live": pa8.live_rows(active)} if takes_mask else {})

                def row(l, acc):
                    return acc + fn(
                        q, kv, s, table, lengths, l,
                        pages_per_compute_block=blk,
                        interpret=args.rehearse, **kw).astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, rows, row, jnp.zeros((B, H, HD), jnp.float32))

            t0 = time.perf_counter()
            compiled = jax.jit(attend_rows).lower(
                q, kv, s, table, jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), bool)).compile()
            compile_s = time.perf_counter() - t0
            for set_name, (lens, mask) in sets.items():
                lengths = jnp.asarray(lens, jnp.int32)
                active = jnp.asarray(mask)
                got = np.asarray(compiled(q, kv, s, table, lengths,
                                          active))[np.asarray(mask)]
                want = first.setdefault(set_name, got)
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    res = compiled(q, kv, s, table, lengths, active)
                jax.block_until_ready(res)
                host_us = (time.perf_counter() - t0) * 1e6 / args.reps / rows
                live, walked = pa8.page_counts(
                    np.asarray(lens), PS, width, blk,
                    mask=np.asarray(mask) if takes_mask else None)
                line = dict(
                    shape=name, form=form, lengths=set_name, rows=rows,
                    kv_heads=KH, slots=B, table_width=width,
                    rows_live=int(np.sum(mask)) if takes_mask else B,
                    pages_live=live, pages_in_whole_blocks=walked,
                    compile_s=round(compile_s, 1), host_us_per_call=host_us,
                    max_abs_diff_to_first_form=float(
                        np.max(np.abs(got - want), initial=0.0)),
                    finite=bool(np.isfinite(got).all()))
                if dev.platform == "tpu":
                    tdir = tempfile.mkdtemp(prefix="paged_attention_trace_")
                    with jax.profiler.trace(tdir):
                        for _ in range(3):
                            res = compiled(q, kv, s, table, lengths, active)
                        jax.block_until_ready(res)
                    red = by_operation(xplane.find_xplane(tdir),
                                       "attend_rows", {})
                    shutil.rmtree(tdir, ignore_errors=True)
                    calls = max(red["executions"], 1) * rows
                    line.update(
                        device_us_per_call=red["device_ms"] * 1e3 / calls,
                        op_us_per_call={k: v * 1e3 / calls
                                        for k, v in red["ops"].items()})
                say(**line)
        del kv, s
    return 0


if __name__ == "__main__":
    sys.exit(main())
