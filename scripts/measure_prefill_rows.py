#!/usr/bin/env python3
"""One batched prefill program alone (engine_model.prefill_batch_step) on
the chip, at a benchmark configuration's widths, over prompts shorter than
their bucket:

    chiprun -- python3 scripts/measure_prefill_rows.py \
        [--config mistral-7b-v0.3-int8] [--steps 256,128] [--parent DIR]

    chain  N 1, bucket 2048, a prompt of 1590 (rag.chain-open's every one)
    chat   N 1 / 2 / 4, bucket 512, prompts of 130-500; N 1, bucket 2048,
           prompts of 520-2000 (mistral7b.chat-open's two upper buckets)
    short  N 1, bucket 128, a prompt of 100 (one height: the old program)

The forms, each one compiled program a (N, bucket) that takes every set of
lengths of that shape:
  parent      with `--parent DIR` (a checkout of another commit, e.g. a
              `git archive` under .scratch/parent) that tree's program:
              every row of the bucket
  kept        this tree's program as it is served: ONE lax.switch on the
              prompt's length over the program on tokens[:, :S_k], S_k of
              engine_model.prefill_row_counts (N = 1 only, the bucket's
              top half)
  switch<s>   the same switch over EVERY multiple of the row step s, for
              every group size (prefill_row_counts replaced here for the
              probe's sake): what the rows alone buy. Sixteen heights make
              XLA copy the donated pool, and the line then holds the
              compiler's refusal
  chunks<s>   the form NOT kept, written here only: a loop of
              cdiv(longest, s) row chunks inside the layer body around the
              row-wise halves (norm and q/k/v; out-projection and MLP),
              attention between them over the whole array with its dead
              blocks skipped, the whole bucket encoded, dead table entries
              dropped by the scatter

Times are the device's: `--reps` executions by the host's clock around
`block_until_ready`, and three traced ones summed by operation. Every form
is also compared with the first: the first tokens, and a checksum of the
codes and scales of the pages the prompts hold.

One JSON object a line on stdout and in chiprun_out/prefill_rows/
probe.jsonl; never a measurement on the CPU (`--rehearse` is the same
control flow there at a tiny size, the kernel off).
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (name, N, bucket, sets of lengths)
SHAPES = [
    ("chain", 1, 2048, [[1590]]),
    ("chat", 1, 512, [[130], [300], [500]]),
    ("chat", 2, 512, [[130, 260], [140, 500]]),
    ("chat", 4, 512, [[130, 140, 150, 200], [130, 200, 260, 500]]),
    ("chat", 1, 2048, [[520], [946], [2000]]),
    ("short", 1, 128, [[100]]),
]
TINY = [("chain", 1, 64, [[40]]), ("chat", 2, 32, [[5, 20], [9, 31]]),
        ("short", 1, 8, [[5]])]


def load_parent(parent_dir: str):
    """The parent's engine_model over the parent's flash kernel; every
    other module it imports is this tree's (none of them changed)."""
    mods = {}
    for name, rel in (("attention", "ops/attention.py"),
                      ("engine_model", "serving/engine_model.py")):
        spec = importlib.util.spec_from_file_location(
            "parent_" + name,
            os.path.join(parent_dir, "generativeaiexamples_tpu", rel))
        mods[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mods[name])
    mods["engine_model"].attn_ops = mods["attention"]
    return mods["engine_model"]


def chunked_prefill(step: int):
    """Form (ii), the one not kept (see the module's docstring)."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models.llama import (
        finish_block, project_qkv, rms_norm, walk_passes)
    from generativeaiexamples_tpu.ops import attention as attn_ops
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.sampling import (
        SamplingParams, sample)

    def prefill_chunks(params, cfg, pool, tokens, lengths, table_rows,
                       temperature, top_p, top_k, key, use_pallas=None,
                       sampling_flags=(True, False, False), mesh=None):
        N, S = tokens.shape
        ps = pool.page_size
        npages = S // ps
        H, KH, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        st = min(step, S)
        n_chunks = (lengths.max() + st - 1) // st
        x = params["tok_emb"][tokens].astype(cfg.residual_dtype)

        def body(x, w):
            def qkv_chunk(c, qkv):
                r0 = c * st
                xs = jax.lax.dynamic_slice_in_dim(x, r0, st, axis=1)
                h = rms_norm(xs, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
                pos = jnp.broadcast_to(r0 + jnp.arange(st)[None], (N, st))
                return tuple(
                    jax.lax.dynamic_update_slice_in_dim(a, b, r0, axis=2)
                    for a, b in zip(qkv, project_qkv(cfg, h, w, pos)))

            q, k, v = jax.lax.fori_loop(0, n_chunks, qkv_chunk, (
                jnp.zeros((N, H, S, Hd), cfg.dtype),
                jnp.zeros((N, KH, S, Hd), cfg.dtype),
                jnp.zeros((N, KH, S, Hd), cfg.dtype)))
            out = attn_ops.attention(q, k, v, causal=True, lengths=lengths,
                                     use_pallas=use_pallas)

            def finish_chunk(c, x):
                r0 = c * st
                xs = finish_block(
                    cfg, jax.lax.dynamic_slice_in_dim(x, r0, st, axis=1),
                    jax.lax.dynamic_slice_in_dim(out, r0, st, axis=2), w)
                return jax.lax.dynamic_update_slice_in_dim(x, xs, r0, axis=1)

            x = jax.lax.fori_loop(0, n_chunks, finish_chunk, x)
            return x, pool.encode_pages(k.transpose(0, 2, 1, 3),
                                        v.transpose(0, 2, 1, 3))

        x, _, kv_out = walk_passes(cfg, params, x,
                                   em._scanned_pass(params, body))
        L = cfg.cache_rows

        def paged(t):
            rest = t.shape[4:]
            t = t.reshape(L, N, npages, ps, KH, *rest)
            order = (0, 4, 1, 2, 3) + tuple(5 + i for i in range(len(rest)))
            return t.transpose(*order).reshape(L, KH, N * npages, ps, *rest)

        # a table entry past the live chunks: out of bounds, dropped
        dead = jnp.arange(npages)[None, :] * ps >= n_chunks * st
        flat_rows = jnp.where(dead, pool.n_pages, table_rows).reshape(-1)
        pool = pool.write_pages(tuple(paged(t) for t in kv_out), flat_rows)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        logits = em._logits(cfg, params, last)[:, 0]
        return sample(logits, SamplingParams(temperature, top_p, top_k), key,
                      all_greedy=True, any_top_k=False, any_top_p=False), pool

    return prefill_chunks


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mistral-7b-v0.3-int8")
    ap.add_argument("--steps", default="256,128", help="row steps to try")
    ap.add_argument("--forms", default="switch,chunks")
    ap.add_argument("--shapes", default="chain,chat,short")
    ap.add_argument("--parent", default=None,
                    help="a checkout whose program is measured beside them")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import architectures
    from benchmark.harness import system, xplane
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import PagePool
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache
    from scripts.measure_qkv_forms import by_operation

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("measure_prefill_rows: no TPU; refusing")
    setup_compile_cache()
    if args.rehearse:
        cfg, ps, n_pages, kv_dtype = llama.LlamaConfig.tiny(), 8, 24, "int8"
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        shapes, steps = TINY, [16, 8]
    else:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               args.config + ".json")) as fh:
            config = json.load(fh)
        ecfg = system.engine_config(config)
        cfg = architectures.load(config).model_config(config)
        ps, kv_dtype = ecfg.page_size, ecfg.kv_dtype
        n_pages = config["serving"]["n_pages"]
        params = llama.init_params_on_device(
            cfg, 1, quantize=ecfg.quantize_weights == "int8")
        shapes, steps = SHAPES, [int(s) for s in args.steps.split(",")]
    shapes = [s for s in shapes if s[0] in args.shapes.split(",")]
    use_pallas = not args.rehearse
    # cfg and use_pallas by position: every form is jitted under a wrapper
    # of its own (a jit's trace cache keys on the function, and the row
    # step is read while tracing)
    static = dict(static_argnums=(1, 10), donate_argnums=(2,))

    forms = {}  # name -> (function to jit, its row step: None = as served)
    if args.parent:
        forms["parent"] = (load_parent(args.parent)
                           .prefill_batch_step.__wrapped__, None)
    forms["kept"] = (em.prefill_batch_step.__wrapped__, None)
    for step in steps:
        if "switch" in args.forms:
            forms[f"switch{step}"] = (em.prefill_batch_step.__wrapped__, step)
        if "chunks" in args.forms:
            forms[f"chunks{step}"] = (chunked_prefill(step), step)

    def every_multiple(step):
        return lambda bucket, page_size, group=1: tuple(
            range(step, bucket, step)) + (bucket,)

    out_dir = os.path.join(ROOT, "chiprun_out", "prefill_rows")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "probe.jsonl"), "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say(device=dev.device_kind, rehearsal=args.rehearse, reps=args.reps,
        config="tiny" if args.rehearse else args.config)
    pool = PagePool.zeros(cfg, n_pages, ps, dtype=jnp.dtype(kv_dtype))
    kept_counts = em.prefill_row_counts
    rng = np.random.default_rng(7)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def checksum(pool, pages):
        return [jnp.take(leaf, pages, axis=3).astype(jnp.float32).sum()
                for leaf in jax.tree.leaves(pool)]

    for name, N, bucket, length_sets in shapes:
        width = bucket // ps
        zeros = jnp.zeros((N,), jnp.float32)
        first = {}
        for form, (fn, step) in forms.items():
            # read while tracing (and by prefill_live_index)
            em.prefill_row_counts = (every_multiple(step) if step
                                     else kept_counts)
            jitted = jax.jit(functools.wraps(fn)(
                lambda *a, fn=fn: fn(*a)), **static)
            operands = lambda tokens, lens, tables: (  # noqa: E731
                params, cfg, pool, tokens, lens, tables, zeros, zeros + 1,
                jnp.zeros((N,), jnp.int32), key, use_pallas)
            t0 = time.perf_counter()
            try:
                compiled = jitted.lower(*operands(
                    jnp.zeros((N, bucket), jnp.int32),
                    jnp.ones((N,), jnp.int32),
                    jnp.zeros((N, width), jnp.int32))).compile()
                error = None
            except Exception as e:  # the chip's compiler refused the form
                error = str(e).strip().splitlines()[0][:300]
            compile_s = time.perf_counter() - t0
            heights = ((bucket,) if form == "parent"
                       else em.prefill_row_counts(bucket, ps, N))
            em.prefill_row_counts = kept_counts
            if error:
                say(shape=name, form=form, N=N, bucket=bucket,
                    heights=len(heights), compile_s=round(compile_s, 1),
                    compile_error=error)
                continue
            for lens in length_sets:
                tokens = np.zeros((N, bucket), np.int32)
                tables = np.zeros((N, width), np.int32)
                page = 1
                for b, n in enumerate(lens):
                    tokens[b, :n] = np.random.default_rng(n).integers(
                        1, cfg.vocab_size, n)
                    held = -(-n // ps)
                    tables[b, :held] = np.arange(page, page + held)
                    page += held
                held_pages = jnp.arange(1, page)
                call = (jnp.asarray(tokens), jnp.asarray(lens, jnp.int32),
                        jnp.asarray(tables), zeros, zeros + 1,
                        jnp.zeros((N,), jnp.int32), key)
                toks, pool = compiled(params, pool, *call)
                got = ([int(t) for t in np.asarray(toks)],
                       [float(c) for c in checksum(pool, held_pages)])
                want = first.setdefault(str(lens), got)
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    toks, pool = compiled(params, pool, *call)
                jax.block_until_ready(toks)
                host_ms = (time.perf_counter() - t0) * 1e3 / args.reps
                line = dict(
                    shape=name, form=form, N=N, bucket=bucket, lengths=lens,
                    rows_computed=next(h for h in heights if h >= max(lens)),
                    compile_s=round(compile_s, 1), host_ms_per_call=host_ms,
                    first_tokens_as_first_form=got[0] == want[0],
                    page_checksums=got[1],
                    page_checksums_first_form=want[1])
                if dev.platform == "tpu":
                    tdir = tempfile.mkdtemp(prefix="prefill_rows_trace_")
                    with jax.profiler.trace(tdir):
                        for _ in range(3):
                            toks, pool = compiled(params, pool, *call)
                        jax.block_until_ready(toks)
                    red = by_operation(xplane.find_xplane(tdir),
                                       fn.__name__, {})
                    shutil.rmtree(tdir, ignore_errors=True)
                    n = max(red["executions"], 1)
                    line.update(
                        device_ms_per_call=red["device_ms"] / n,
                        op_ms_per_call={k: v / n
                                        for k, v in red["ops"].items()})
                say(**line)
            del compiled
    return 0


if __name__ == "__main__":
    sys.exit(main())
