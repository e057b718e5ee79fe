#!/usr/bin/env python3
"""Both forms of the int8 pool's append (kv_cache.QuantPagePool.append),
on the chip, a decode step's worth of calls on a pool of the real size:

    chiprun -- python3 scripts/measure_kv_append.py \
        [--shapes mistral,ouro,tp4] [--live 3,5,16,60,64] [--parent DIR] \
        [--forms scatter,kernel,attend,fused]

`scatter`: XLA's four scatters a cache row, what every program lowered
off the chip keeps. `kernel`: one in-place Pallas call a row
(serving/kv_append_int8.py), given the step's mask: it walks the live
slots alone. `parent` (with `--parent DIR`, a `git archive` of another
commit, e.g. .scratch/parent): that tree's kernel as it is, called with
the codes this tree's pool quantizes; one that takes no mask writes every
slot, an idle one to the sink page. `attend` and `fused` (with
`--forms`): the step's pair, a row's write AND its attention, as two
calls in series (the kernel's append, then paged_attention_int8 over the
slot's one page: what a step ran until PR 46) and as the one call that
writes the row itself (`QuantPagePool.attend_appending`); their last line
also says how far their outputs are apart (0.0: bit for bit;
scripts/measure_paged_attention.py `--append` runs the pair at the cells'
contexts). Every form runs at each of `--live`
counts of live slots (capped at the shape's slots), the idle ones as the
engine sends them: page 0, offset 0, `active` False. The shapes are the
benchmark cells':

    mistral  32 rows,  8 KV heads, 64 slots,  768 pages   (Mistral-7B)
    ouro    192 rows, 16 KV heads, 32 slots,  112 pages   (Ouro-2.6B: 48
            blocks in a `fori_loop` of 4 passes, so the row is traced,
            and a half of the pool is over SPLIT_KV_BYTES)
    tp4      40 rows,  2 KV heads, 64 slots, 3072 pages   (one chip's
            share of Mistral-Small-24B under TP=4, without the mesh)

For every shape and form it compiles one program that appends a new K
and V row of every slot to every cache row, as a decode step does
(quantization included, the same in both forms), prints
`memory_analysis()`'s temporary bytes (a temporary the size of the pool
is the copy trap of docs/ENGINEERING_NOTES.md, "two scatter traps"),
checks that both forms leave the SAME bytes in every tile and scale row
the LIVE slots touch and the same sums over the whole pool less the sink
page, times `--reps` executions by the host's clock, then traces three
and sums device time by operation. This tree's forms are chosen through
`token_slots(use_pallas=...)`, the step programs' own argument; nothing
else is switched.

One JSON object a line on stdout and in chiprun_out/kv_append/probe.jsonl;
never a measurement on the CPU (`--rehearse` is the same control flow
there at a tiny size, the kernel interpreted).
"""

from __future__ import annotations

import argparse
import contextlib
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
# name: (blocks, passes, kv heads, slots, pages)
SHAPES = {"mistral": (32, 1, 8, 64, 768),
          "ouro": (48, 4, 16, 32, 112),
          "tp4": (40, 1, 2, 64, 3072)}
TINY = {"mistral": (2, 1, 2, 4, 6), "ouro": (2, 2, 2, 4, 5),
        "tp4": (3, 1, 1, 4, 9)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,ouro,tp4")
    ap.add_argument("--forms", default="scatter,kernel")
    ap.add_argument("--live", default="3,5,16,60,64",
                    help="counts of live slots to run every form at")
    ap.add_argument("--parent", default=None,
                    help="a checkout whose kernel is measured beside them")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from benchmark.harness import xplane
    from generativeaiexamples_tpu.serving.kv_cache import (
        QuantPagePool, kernel_append, kernel_live_rows, token_slots)
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_dispatch)
    from scripts.measure_qkv_forms import by_operation

    forms = args.forms.split(",")
    parent_append = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_kv_append_int8", os.path.join(
                args.parent,
                "generativeaiexamples_tpu/serving/kv_append_int8.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        parent_append = parent.kv_append_int8
        forms.append("parent")

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("measure_kv_append: no TPU; refusing")
    shapes = TINY if args.rehearse else SHAPES
    interpreted = (pltpu.force_tpu_interpret_mode if args.rehearse
                   else contextlib.nullcontext)
    out_dir = os.path.join(ROOT, "chiprun_out", "kv_append")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "probe.jsonl"), "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say(device=dev.device_kind, rehearsal=args.rehearse, reps=args.reps)
    for name in args.shapes.split(","):
        blocks, passes, KH, B, P = shapes[name]
        R = blocks * passes
        shape = (2, R, KH, P, PS, HD)

        @jax.jit
        def fresh_pool():
            """A pool of the real size with no two neighbours alike, so
            that a tile written back wrong shows."""
            def mix(shape, weights):
                return sum(w * jax.lax.broadcasted_iota(jnp.int32, shape, a)
                           for a, w in enumerate(weights))
            kv = (mix(shape, (131, 7, 29, 13, 3, 1)) % 255 - 127)
            s = (mix(shape[:-1], (11, 5, 3, 7, 1)) % 97).astype(jnp.float32)
            return QuantPagePool(kv.astype(jnp.int8), s * 0.01 + 0.25, PS)

        def step(form):
            def kv_append_step(pool, page_idx, offset, k_new, v_new, active):
                on = form in ("kernel", "attend", "fused")
                # the mask becomes the walk's order once, outside the rows
                slots = token_slots(KH, page_idx, offset, use_pallas=on,
                                    live=kernel_live_rows(pool, active, on))
                assert kernel_append(pool, slots.use_pallas) == on
                kw = {}
                if form == "parent" and "live" in inspect.signature(
                        parent_append).parameters:
                    kw["live"] = kernel_live_rows(pool, active, True)

                def attend(k_pages, v_pages, k_scales, layer, new=None):
                    # the slot's one page, up to the row just written
                    return paged_attention_dispatch(
                        q, k_pages, v_pages, page_idx[:, None], offset + 1,
                        k_scales=k_scales, layer=layer, use_pallas=True,
                        live=slots.live, new=new)

                def append(pool, row, k, v):
                    """-> (the pool, what the row's attention gave)"""
                    if form == "fused":
                        return pool.attend_appending(row, k, v, attend)[::-1]
                    if form == "attend":
                        pool = pool.append(row, slots, k, v)
                        return pool, attend(*pool.attention_operands(row))
                    if form != "parent":
                        return pool.append(row, slots, k, v), 0.0
                    (kq, ks), (vq, vs) = pool._quantize(k), pool._quantize(v)
                    kv, s = parent_append(
                        pool.kv, pool.s, row, page_idx, offset,
                        jnp.stack([kq, vq]), jnp.stack([ks, vs]),
                        interpret=args.rehearse, **kw)
                    return QuantPagePool(kv, s, PS), 0.0

                def one_pass(p, carry):
                    pool, acc = carry
                    for l in range(blocks):
                        row = p * blocks + l
                        add = jnp.asarray(row, jnp.float32) / R
                        pool, out = append(
                            pool, row, (k_new + add).astype(jnp.bfloat16),
                            (v_new - add).astype(jnp.bfloat16))
                        acc = acc + out
                    return pool, acc

                carry = (pool, jnp.zeros(q.shape, jnp.float32))
                if passes == 1:
                    return one_pass(0, carry)
                return jax.lax.fori_loop(0, passes, one_pass, carry)
            return jax.jit(kv_append_step, donate_argnums=(0,))

        @jax.jit
        def digest(pool, page_idx, offset):
            """What a call may have touched (an idle slot's entry is the
            sink's first tile: skipped by the comparison), and sums over
            the rest less the sink page."""
            # a slice a slot: a gather of this shape copies the pool
            tiles = [jax.lax.dynamic_slice(
                pool.kv, (0, 0, 0, page_idx[b], offset[b] // 32 * 32, 0),
                (2, R, KH, 1, 32, HD)) for b in range(B)]
            scales = [jax.lax.dynamic_slice(
                pool.s, (0, 0, 0, page_idx[b], 0), (2, R, KH, 1, PS))
                for b in range(B)]
            return (jnp.concatenate(tiles, 3), jnp.concatenate(scales, 3),
                    jnp.sum(pool.kv[:, :, :, 1:].astype(jnp.int32)),
                    jnp.sum(pool.s[:, :, :, 1:]))

        rng = np.random.default_rng(7)
        pages = 1 + rng.permutation(P - 1)[:B]
        base = rng.integers(0, PS, B)
        base[:4] = (0, 31, 32, 127)
        k_new, v_new = (jnp.asarray(rng.standard_normal((KH, B, HD)),
                                    jnp.float32) for _ in range(2))
        q = jnp.asarray(rng.standard_normal((B, 4 * KH, HD)), jnp.bfloat16)
        spread = rng.permutation(B)  # the live slots, apart as a batch's are
        counts = sorted({min(int(x), B) for x in args.live.split(",")})
        digests, outs = {}, {}
        compiled_forms = {}
        for form, n_live in [(f, n) for n in counts for f in forms]:
            mask = np.zeros((B,), bool)
            mask[np.sort(spread[:n_live])] = True
            active = jnp.asarray(mask)
            page_idx = jnp.asarray(np.where(mask, pages, 0), jnp.int32)

            def offset(i):
                return jnp.asarray(np.where(mask, (base + i) % PS, 0),
                                   jnp.int32)

            pool = fresh_pool()
            jax.block_until_ready(pool)
            if form not in compiled_forms:
                t0 = time.perf_counter()
                with interpreted():
                    c = step(form).lower(pool, page_idx, offset(0), k_new,
                                         v_new, active).compile()
                compiled_forms[form] = (c, time.perf_counter() - t0)
            compiled, compile_s = compiled_forms[form]
            mem = compiled.memory_analysis()
            text = compiled.as_text()

            def run(pool, i):
                pool, acc = compiled(pool, page_idx, offset(i), k_new, v_new,
                                     active)
                if i == 1 and form in ("attend", "fused"):
                    outs.setdefault(n_live, {})[form] = np.asarray(acc)[mask]
                # the TPU interpret mode's callbacks dispatch operations
                # of their own and deadlock against this thread's next
                # dispatch: a rehearsal lets each step finish first
                return jax.block_until_ready(pool) if args.rehearse else pool

            # two steps running into the same tiles, then what they left
            pool = run(run(pool, 0), 1)
            got = [np.asarray(x) for x in digest(pool, page_idx, offset(0))]
            digests.setdefault(n_live, {})[form] = [
                got[0][:, :, :, mask], got[1][:, :, :, mask]] + got[2:]
            t0 = time.perf_counter()
            for i in range(args.reps):
                pool = run(pool, 2 + i)
            jax.block_until_ready(pool)
            host_ms = (time.perf_counter() - t0) * 1e3 / args.reps
            line = dict(
                shape=name, form=form, rows=R, kv_heads=KH, slots=B, pages=P,
                slots_live=n_live,
                pool_bytes=int(np.prod(shape)) + int(np.prod(shape[:-1])) * 4,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                scatters=text.count(" scatter("),
                kernel_calls=text.count('custom_call_target="tpu_custom_call"'),
                compile_s=round(compile_s, 1), host_ms_per_step=host_ms)
            if dev.platform == "tpu":
                tdir = tempfile.mkdtemp(prefix="kv_append_trace_")
                with jax.profiler.trace(tdir):
                    for i in range(3):
                        pool = run(pool, i)
                    jax.block_until_ready(pool)
                red = by_operation(xplane.find_xplane(tdir),
                                   "kv_append_step", {})
                shutil.rmtree(tdir, ignore_errors=True)
                n = max(red["executions"], 1)
                line.update(device_ms_per_step=red["device_ms"] / n,
                            op_ms_per_step={k: v / n
                                            for k, v in red["ops"].items()})
            say(**line)
            del pool
        for n_live, by_form in digests.items():
            first = next(iter(by_form.values()))
            pair = list(outs.get(n_live, {}).values())
            say(shape=name, slots_live=n_live, forms=list(by_form),
                same_bytes=all(
                    x.shape == y.shape and np.array_equal(x, y)
                    for other in by_form.values()
                    for x, y in zip(first, other)),
                **({"max_abs_output_diff": float(np.max(
                    np.abs(pair[0] - pair[1]), initial=0.0))}
                   if len(pair) == 2 else {}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
