#!/usr/bin/env python3
"""The int8 paged-attention kernel alone (serving/paged_attention_int8.py),
on the chip, over pools of the benchmark cells' real shapes:

    chiprun -- python3 scripts/measure_paged_attention.py \
        [--shapes mistral,ouro,tp4,granite] [--blocks 4,5,8] \
        [--folds 1,2] [--aheads 2,4] [--parent DIR[,DIR2]] [--append]

    mistral  32 rows,  8 KV heads of 32, 64 slots,  768 pages, tables of 20
    ouro    192 rows, 16 KV heads of 16, 32 slots,  112 pages, tables of 4
            (a half of the pool is over SPLIT_KV_BYTES: split descriptors)
    tp4      40 rows,  2 KV heads of 8,  64 slots, 3072 pages, tables of 4
            (one chip's share of Mistral-Small-24B under TP=4, no mesh)
    granite   1 row,   8 KV heads of 32, 96 slots, 1056 pages, tables of 11
            (granite-4.0-h-small's one attention layer of a period; `half`
            is its cell's mean context: the call whose roofline share that
            cell reads at 100 %)

For every shape it compiles one program a form that calls the kernel once
a cache row, as a decode step does, and runs it over seven sets of lengths
and live rows: `one` (every row idle, as the engine sends an idle slot:
length 1, `active` False; what an idle slot costs), `mix` (the closed
cells' contexts: a prompt of 32-128 plus a uniform share of an answer of
192-320, mean about 208, every row live), `mix60` (the same with 60 of 64
rows live, or 30 of 32: a closed cell's occupancy), `full` (every row at
the table's width: what the guards cost where nothing is dead), `chain`
(three rows of 1,700 tokens, the rest idle: `rag.chain-open`), `open`
(three rows of 250 tokens, two pages each, apart among idle ones: the
open mix, `mistral7b.chat-open`; the append's side of both mixes is
scripts/measure_kv_append.py `--live 3,60,64`) and `half` (every row at
half the table's width: rows of a block and a bit). The
forms: the kernel of this tree at each of `--blocks` pages a block, given
the step's mask (`change4`: the served one), at the first of those also
with each of `--folds` pages a softmax update in place of what the
kernel's own rule gives the shape (`change4f1` is a chain a page; the
probe patches `fold_pages`, which no caller can set), likewise with each of
`--aheads` blocks' copies in flight in place of `blocks_ahead`'s
(`change4a2`: the two every shape had until PR 53), and with
`--parent DIR` (a checkout of another commit,
e.g. `git archive` into .scratch/parent; several with commas, `parent`,
`parent1`, ...: any directory that holds a kernel file under this tree's
path will do, which is how a kernel not in the tree is read beside this
one) that tree's kernel as it is; a parent that takes no mask computes
every row, an idle one at length 1 (the entry points' signatures have not
changed since the mask, PR 41).
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
import contextlib
import importlib.util
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
APPEND = "generativeaiexamples_tpu/serving/kv_append_int8.py"
# name: (cache rows, kv heads, query heads, slots, pages, table width)
SHAPES = {"mistral": (32, 8, 32, 64, 768, 20),
          "ouro": (192, 16, 16, 32, 112, 4),
          "tp4": (40, 2, 8, 64, 3072, 4),
          "granite": (1, 8, 32, 96, 1056, 11)}
TINY = {"mistral": (2, 2, 4, 4, 9, 5), "ouro": (3, 2, 2, 4, 7, 4),
        "tp4": (2, 1, 2, 4, 9, 4), "granite": (1, 2, 4, 6, 9, 7)}


@contextlib.contextmanager
def folding(pa8, width, ahead=None):
    """The kernel module's programs traced inside fold `width` pages into a
    softmax update whatever `fold_pages` gives their shape, and keep
    `ahead` blocks' copies in flight whatever `blocks_ahead` gives it
    (None: as it is); a probe's way in, since neither rule is a caller's
    to set."""
    rules = pa8.fold_pages, pa8.blocks_ahead
    programs = (pa8.paged_attention_int8, pa8.paged_attention_int8_window)
    if width is not None:
        pa8.fold_pages = lambda kv_heads, group, ppcb: min(width, ppcb)
    if ahead is not None:
        pa8.blocks_ahead = lambda *shape: ahead
    for fn in programs:
        fn.clear_cache()
    try:
        yield
    finally:
        pa8.fold_pages, pa8.blocks_ahead = rules
        for fn in programs:
            fn.clear_cache()


def load_kernel(checkout: str, path: str = KERNEL):
    """The int8 kernel's module (or the one at `path`) as another
    checkout has it."""
    spec = importlib.util.spec_from_file_location(
        "parent_" + os.path.basename(path)[:-3], os.path.join(checkout, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def pages_an_update(mod, fold, kv_heads: int, group: int, ppcb: int) -> int:
    """What a form folds into a softmax update: the probe's `fold`, else
    the rule of the form's own module; a checkout from before the rule
    made every page an update."""
    return fold or getattr(mod, "fold_pages", lambda *_: 1)(kv_heads, group,
                                                            ppcb)


def blocks_in_flight(mod, ahead, kv_heads: int, ppcb: int) -> int:
    """What a form keeps in flight: the probe's `ahead`, else the rule of
    the form's own module; a checkout from before the rule had a
    constant."""
    return ahead or getattr(mod, "blocks_ahead", lambda *_: mod.BLOCKS_AHEAD)(
        kv_heads, PS, HD, ppcb)


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
            "open": (open_mix, [x > 1 for x in open_mix]),
            "half": ([top // 2] * slots, every)}


def pool_maker(shape):
    """A jitted maker of an int8 pool of `shape` and its scales, no two
    neighbours alike (a tile written back wrong shows)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fresh_pool():
        def mix(shape, weights):
            return sum(w * jax.lax.broadcasted_iota(jnp.int32, shape, a)
                       for a, w in enumerate(weights))
        kv = (mix(shape, (131, 7, 29, 13, 3, 1)) % 255 - 127)
        s = (mix(shape[:-1], (11, 5, 3, 7, 1)) % 97).astype(jnp.float32)
        return kv.astype(jnp.int8), s * 1e-4 + 0.002
    return fresh_pool


def traced_us(run, program: str, rows: int) -> dict:
    """Device us a call, whole and by operation, of three traced
    executions of `run()` (a program named `program` of `rows` calls)."""
    import jax

    from benchmark.harness import xplane
    from scripts.measure_qkv_forms import by_operation

    tdir = tempfile.mkdtemp(prefix="paged_attention_trace_")
    with jax.profiler.trace(tdir):
        for _ in range(3):
            res = run()
        jax.block_until_ready(res)
    red = by_operation(xplane.find_xplane(tdir), program, {})
    shutil.rmtree(tdir, ignore_errors=True)
    calls = max(red["executions"], 1) * rows
    return dict(device_us_per_call=red["device_ms"] * 1e3 / calls,
                op_us_per_call={k: v * 1e3 / calls
                                for k, v in red["ops"].items()})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,ouro,tp4")
    ap.add_argument("--blocks", default="4,5,8",
                    help="pages a block to try for this tree's kernel")
    ap.add_argument("--folds", default="",
                    help="pages a softmax update to try in place of the "
                         "rule's, at the first of --blocks")
    ap.add_argument("--aheads", default="",
                    help="blocks in flight to try in place of the rule's, "
                         "at the first of --blocks")
    ap.add_argument("--parent", default=None,
                    help="a checkout whose kernel is measured beside it "
                         "(several, with commas: parent, parent1, ...)")
    ap.add_argument("--append", action="store_true",
                    help="the step's pair: the new row's write and the "
                         "attention, in series and fused")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("measure_paged_attention: no TPU; refusing")
    parent_form = {}
    checkouts = [x for x in (args.parent or "").split(",") if x]
    for at, checkout in enumerate(checkouts):
        parent_form["parent" + str(at or "")] = (
            load_kernel(checkout), None, None, None)
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
    if args.append:
        return append_pairs(args, shapes, say)
    for name in args.shapes.split(","):
        rows, KH, H, B, P, width = shapes[name]
        forms = dict(parent_form)
        blocks = sorted({min(int(x), width) for x in args.blocks.split(",")})
        for blk in blocks:
            forms[f"change{blk}"] = (pa8, blk, None, None)
        for fold in sorted({min(int(x), blocks[0])
                            for x in args.folds.split(",") if x}):
            forms[f"change{blocks[0]}f{fold}"] = (pa8, blocks[0], fold, None)
        for ahead in sorted({int(x) for x in args.aheads.split(",") if x}):
            forms[f"change{blocks[0]}a{ahead}"] = (pa8, blocks[0], None,
                                                   ahead)
        shape = (2, rows, KH, P, PS, HD)

        kv, s = jax.block_until_ready(pool_maker(shape)())
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.bfloat16)
        table = jnp.asarray(rng.integers(1, P, (B, width)), jnp.int32)
        sets = length_sets(rng, B, width)
        first = {}
        for form, (mod, blk, fold, ahead) in forms.items():
            fn = mod.paged_attention_int8
            takes_mask = hasattr(mod, "live_rows")

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
            with folding(pa8, fold, ahead):
                compiled = jax.jit(attend_rows).lower(
                    q, kv, s, table, jnp.zeros((B,), jnp.int32),
                    jnp.zeros((B,), bool)).compile()
            compile_s = time.perf_counter() - t0
            folded = pages_an_update(mod, fold, KH, H // KH, blk or width)
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
                live, walked, updates, _ = pa8.page_counts(
                    np.asarray(lens), PS, width, blk,
                    mask=np.asarray(mask) if takes_mask else None,
                    fold=folded)
                line = dict(
                    shape=name, form=form, lengths=set_name, rows=rows,
                    kv_heads=KH, slots=B, table_width=width,
                    rows_live=int(np.sum(mask)) if takes_mask else B,
                    pages_live=live, pages_in_whole_blocks=walked,
                    pages_an_update=folded, softmax_updates=updates,
                    blocks_ahead=blocks_in_flight(mod, ahead, KH,
                                                  blk or width),
                    compile_s=round(compile_s, 1), host_us_per_call=host_us,
                    max_abs_diff_to_first_form=float(
                        np.max(np.abs(got - want), initial=0.0)),
                    finite=bool(np.isfinite(got).all()))
                if dev.platform == "tpu":
                    line.update(traced_us(
                        lambda: compiled(q, kv, s, table, lengths, active),
                        "attend_rows", rows))
                say(**line)
        del kv, s
    return 0


def append_pairs(args, shapes, say) -> int:
    """`--append`: the new row's write and the attention over it, in
    series and fused, a cache row at a time on a pool the rows carry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.serving import kv_append_int8 as ka8
    from generativeaiexamples_tpu.serving import paged_attention_int8 as pa8

    dev = jax.devices()[0]
    forms = {}  # name: (the append's module or None: fused, the kernel's)
    if args.parent:
        checkout = args.parent.split(",")[0]
        parent = load_kernel(checkout)
        forms["parent_series"] = (load_kernel(checkout, APPEND), parent)
        if hasattr(parent, "NewRow"):  # it writes the row itself (PR 46)
            forms["parent_fused"] = (None, parent)
    forms.update(series=(ka8, pa8), fused=(None, pa8))
    for name in args.shapes.split(","):
        rows, KH, H, B, P, width = shapes[name]
        blk = min(int(args.blocks.split(",")[0]), width)
        fresh_pool = pool_maker((2, rows, KH, P, PS, HD))

        @jax.jit
        def digest(kv, s, page_idx, offset):
            """What a step may have touched, and sums over the rest less
            the sink page (a slice a slot: a gather copies the pool)."""
            tiles = [jax.lax.dynamic_slice(
                kv, (0, 0, 0, page_idx[b], offset[b] // 32 * 32, 0),
                (2, rows, KH, 1, 32, HD)) for b in range(B)]
            scales = [jax.lax.dynamic_slice(
                s, (0, 0, 0, page_idx[b], 0), (2, rows, KH, 1, PS))
                for b in range(B)]
            return (jnp.concatenate(tiles, 3), jnp.concatenate(scales, 3),
                    jnp.sum(kv[:, :, :, 1:].astype(jnp.int32)),
                    jnp.sum(s[:, :, :, 1:]))

        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((B, H, HD)), jnp.bfloat16)
        codes = jnp.asarray(rng.integers(-127, 128, (2, KH, B, HD)), jnp.int8)
        scales = jnp.asarray(rng.random((2, KH, B)) * 0.01 + 0.002,
                             jnp.float32)
        sets = length_sets(rng, B, width)
        # row b's LAST page is page 1 + b, whatever its length; the pages
        # before it are anybody's but never a last one
        others = rng.integers(1 + B, P, (B, width))
        compiled = {}
        for form, (append_mod, mod) in forms.items():
            def append_attend_rows(q, kv, s, table, lengths, active, codes,
                                   scales, append_mod=append_mod, mod=mod):
                live = pa8.live_rows(active)
                at = lengths - 1
                page_idx = table[jnp.arange(B), at // PS]
                kw = dict(pages_per_compute_block=blk,
                          interpret=args.rehearse, live=live)

                def row(l, carry):
                    acc, kv, s = carry
                    if append_mod is None:
                        out, kv, s = mod.paged_attention_int8(
                            q, kv, s, table, lengths, l,
                            new=(codes, scales), **kw)
                    else:
                        kv, s = append_mod.kv_append_int8(
                            kv, s, l, page_idx, at % PS, codes, scales, live,
                            interpret=args.rehearse)
                        out = mod.paged_attention_int8(
                            q, kv, s, table, lengths, l, **kw)
                    return acc + out.astype(jnp.float32), kv, s
                return jax.lax.fori_loop(
                    0, rows, row, (jnp.zeros((B, H, HD), jnp.float32), kv, s))

            t0 = time.perf_counter()
            kv, s = fresh_pool()
            compiled[form] = (jax.jit(
                append_attend_rows, donate_argnums=(1, 2)).lower(
                    q, kv, s, jnp.zeros((B, width), jnp.int32),
                    jnp.ones((B,), jnp.int32), jnp.zeros((B,), bool), codes,
                    scales).compile(), time.perf_counter() - t0)
            del kv, s
        for set_name, (lens, mask) in sets.items():
            lens, mask = np.asarray(lens), np.asarray(mask)
            last = np.clip(-(-lens // PS), 1, width) - 1
            table = others.copy()
            table[np.arange(B), last] = 1 + np.arange(B)
            table, lengths = jnp.asarray(table, jnp.int32), jnp.asarray(lens)
            active = jnp.asarray(mask)
            page_idx = jnp.asarray(np.where(mask, 1 + np.arange(B), 0))
            offset = jnp.asarray(np.where(mask, (lens - 1) % PS, 0))
            left, outs = {}, {}
            for form, (program, compile_s) in compiled.items():
                def run(kv, s):
                    return program(q, kv, s, table, lengths, active, codes,
                                   scales)

                res, kv, s = run(*jax.block_until_ready(fresh_pool()))
                outs[form] = np.asarray(res)[mask]
                got = [np.asarray(x) for x in digest(kv, s, page_idx, offset)]
                left[form] = [got[0][:, :, :, mask],
                              got[1][:, :, :, mask]] + got[2:]
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    res, kv, s = run(kv, s)
                jax.block_until_ready(res)
                host_us = (time.perf_counter() - t0) * 1e6 / args.reps / rows
                mem = program.memory_analysis()
                line = dict(
                    shape=name, form=form, lengths=set_name, rows=rows,
                    kv_heads=KH, slots=B, table_width=width,
                    rows_live=int(mask.sum()), temp_bytes=mem.temp_size_in_bytes,
                    compile_s=round(compile_s, 1), host_us_per_call=host_us,
                    finite=bool(np.isfinite(outs[form]).all()))
                if dev.platform == "tpu":
                    held = [kv, s]  # the donated pool, from run to run

                    def again():
                        res, *held[:] = run(*held)
                        return res
                    line.update(traced_us(again, "append_attend_rows", rows))
                    kv, s = held
                    del held[:]  # or the next form's pool finds no room
                say(**line)
                del res, kv, s
            first, want = next(iter(left.values())), next(iter(outs.values()))
            say(shape=name, lengths=set_name, forms=list(left),
                same_bytes=all(
                    x.shape == y.shape and np.array_equal(x, y)
                    for other in left.values() for x, y in zip(first, other)),
                max_abs_output_diff=max(
                    float(np.max(np.abs(o - want), initial=0.0))
                    for o in outs.values()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
