#!/usr/bin/env python3
"""A configuration with learned sparse attention on the chip
(Keye-VL-2.0-30B-A3B's stage, `keye-vl-2.0-30b-a3b-int8`):

    chiprun -- timeout 3000 python3 scripts/check_sparse_on_chip.py \
        [--phases hazard,kernels,compare,step] [--seeds 1] \
        [--kernels index,select,attention] [--attn-widths 4,8,16] \
        [--parent DIR[,DIR2]]

Four phases, one JSON line each result (also chiprun_out/sparse/check.jsonl):

  hazard   the largest prefill program (`prefill_batch_step`, one prompt in
           the 12,288 bucket: 98,304 token-expert pairs over 128 experts
           through the grouped matmul in tiles of 64 rows) twenty times in a
           row over the configuration's whole pool, each with another
           prompt: does the device stop (PERF.md section 7, OPEN since PR
           35), what does a program take.
  kernels  the three decode kernels alone at 16 slots and contexts of 6k,
           10k and 16k, twelve calls a program as a step makes them
           (`--kernels index` reads one of the three alone): the
           index scores, pages a call, us a call and us a page, with
           `--parent` each of those trees' kernel beside this one's (and
           whether its scores and the selection on them are this one's,
           bit for bit) and two forms the probe alone builds, the walk's
           copies without the dot and the dot without the copies, which
           say whose a page's time is; the selection as the kernel, as the
           XLA bitwise partial sort and as `jax.lax.top_k`; the selected
           attention as
           the kernel that walks the slot's pages whole under the mask and
           as a GATHER of the 2,048 selected rows in XLA. The walk's line
           says the width, tail and look-ahead it ran with and us a page
           beside us a call; `--attn-widths 4,8,16` reads it again at each
           of those widths (an entry `width/tail/ahead` sets all three of
           `paged_attention_sparse._walk`; left out, the tail is the
           width, or 4 past a width of 8, and the look-ahead the
           module's), and `--parent DIR` (a `git archive` of another
           commit, e.g. `.scratch/parent`; several with commas, which is
           also how a kernel file kept outside the tree is read: `DIR/
           generativeaiexamples_tpu/serving/sparse_index_scores.py`)
           that commit's kernels beside them.
  compare  ISSUE 42's three-part comparison at the published widths on what
           the step programs produce (a 4,096-token prompt through
           `prefill_step` in the 6,144 bucket, then eight decode steps
           through the cache) against the benchmark's plain reference:
           (a) layer 0's index scores, (b) the selected sets where the
           reference's margin allows, (c) logits; and the negative control,
           the reference with selection switched off.
  step     `decode_multi_step` (a block of 8) at 16 live slots and contexts
           near 10k over a pool of random rows: ms a step by the host's
           clock.

`--rehearse` is the control flow on the CPU at the tests' tiny size, never
a measurement.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCORE_TOL = 0.01    # (a): bf16 queries and keys, 64 products a head
MEDIAN_TOL = 0.02   # (c): the median row, of the largest logit
LOGIT_TOL = 0.10    # (c): the worst row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", default="hazard,kernels,compare,step")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--hazard-runs", type=int, default=20)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--attn-widths", default="",
                    help="widths (or width/tail/ahead) to time the selected "
                         "attention's walk at, beside the module's own")
    ap.add_argument("--kernels", default="index,select,attention",
                    help="which of the three the kernels phase reads")
    ap.add_argument("--contexts", default="",
                    help="the kernels phase's contexts in place of 6144,"
                         "10240,16384; `mix`: a length a slot, drawn once "
                         "between the first and the last of them")
    ap.add_argument("--parent", default=None,
                    help="checkouts of other commits, with commas: their "
                         "sparse_index_scores and paged_attention_sparse "
                         "timed beside this one's")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.architectures import keyevl2 as entry
    from benchmark.harness import system
    from generativeaiexamples_tpu.models import sparse_attn_moe as sm
    from generativeaiexamples_tpu.models.llama import rms_norm
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import PagePool
    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        every_row, paged_attention_int8)
    from generativeaiexamples_tpu.serving import paged_attention_sparse as pas
    from generativeaiexamples_tpu.serving.paged_attention_sparse import (
        paged_attention_sparse)
    from generativeaiexamples_tpu.serving import sparse_index_scores as sis
    from generativeaiexamples_tpu.serving.sparse_index_scores import (
        sparse_index_scores)
    from generativeaiexamples_tpu.serving.sparse_select import sparse_select
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit("check_sparse_on_chip: no TPU; refusing")
    if args.rehearse:
        from benchmark.tests.test_keyevl2 import tiny_file
        config = tiny_file()
        contexts, prompt_n, prompt_bucket, big = (40, 70, 100), 40, 64, 64
    else:
        setup_compile_cache()
        config = system.load_config(os.path.join(ROOT, "benchmark"),
                                    "keye-vl-2.0-30b-a3b-int8")
        contexts, prompt_n, prompt_bucket, big = (
            (6144, 10240, 16384), 4096, 6144, 12288)
    use_pallas = not args.rehearse
    mcfg = entry.model_config(config)
    ecfg = system.engine_config(config)
    ps, B = ecfg.page_size, ecfg.max_batch_size
    maxp = ecfg.max_seq_len // ps
    vocab = mcfg.vocab_size
    phases = args.phases.split(",")
    out_dir = os.path.join(ROOT, "chiprun_out", "sparse")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "check.jsonl"), "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say(device=dev.device_kind, rehearsal=args.rehearse, phases=phases)
    greedy = (True, False, False)

    def timed(fn, *a, reps=args.reps):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(reps):
            res = fn(*a)
        jax.block_until_ready(res)
        return (time.perf_counter() - t0) / reps

    # -- kernels alone (no model: random rows of the pool's shapes) --------
    if "kernels" in phases:
        L, KH, H, Hd = mcfg.n_layers, mcfg.n_kv_heads, mcfg.n_heads, \
            mcfg.head_dim
        Hi, Di, topk = mcfg.index_heads, mcfg.index_head_dim, mcfg.index_topk
        # (whole tiles of 8 pages: the kernels' view of the scales as
        # [..., P, 1, ps] is then a bitcast, as it is in the engine's pool)
        P = -(-(B * maxp + 1) // 8) * 8
        key = jax.random.key(3)

        @jax.jit
        def rows(key):
            a, b, c = jax.random.split(key, 3)
            kv = jax.lax.bitcast_convert_type(
                jax.random.bits(a, (2, L, KH, P, ps, Hd), jnp.uint8),
                jnp.int8)
            s = jax.random.uniform(b, (2, L, KH, P, ps), jnp.float32,
                                   0.005, 0.02)
            idx = jax.random.normal(c, (L, P, Di, ps), jnp.bfloat16)
            return kv, s, idx

        kv, s, idx = jax.block_until_ready(rows(key))
        rng = np.random.default_rng(5)
        table = jnp.asarray(1 + rng.permutation(P - 1)[:B * maxp].reshape(
            B, maxp), jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, H, Hd)), jnp.bfloat16)
        qi = jnp.asarray(rng.standard_normal((B, Hi, Di)), jnp.bfloat16)
        wt = jnp.asarray(rng.standard_normal((B, Hi)) * 0.03, jnp.float32)
        live = every_row(B)

        pools = (kv, s, idx)  # arguments of every program, never constants

        def twelve(one):
            """`one(pools, l, *a)` for every layer, as a step program calls
            it: one program of L calls."""
            def run(pools, *a):
                def layer(l, acc):
                    return acc + one(pools, l, *a).astype(jnp.float32)
                first = jax.eval_shape(one, pools, 0, *a)
                return jax.lax.fori_loop(
                    0, L, layer, jnp.zeros(first.shape, jnp.float32))
            return jax.jit(run)

        def scores_of(pools, l, ln):
            return sparse_index_scores(qi, wt, pools[2], l, table, ln,
                                       use_pallas=use_pallas, live=live)

        scores_fn = twelve(scores_of)
        which = set(args.kernels.split(","))

        parents = {}  # tree -> the kernel modules it holds, by file name
        for tree in filter(None, (args.parent or "").split(",")):
            for name in ("sparse_index_scores", "paged_attention_sparse"):
                path = os.path.join(tree, "generativeaiexamples_tpu",
                                    "serving", name + ".py")
                if os.path.exists(path):
                    spec = importlib.util.spec_from_file_location(
                        f"parent{len(parents)}_{name}", path)
                    module = importlib.util.module_from_spec(spec)
                    spec.loader.exec_module(module)
                    parents.setdefault(tree, {})[name] = module

        def index_program(module):
            """Twelve calls of `module`'s index kernel (off the chip it is
            interpreted, at the rehearsal's size)."""
            return twelve(
                lambda pools, l, ln: module.sparse_index_scores_pallas(
                    qi, wt, pools[2], l, table, ln, live,
                    interpret=args.rehearse))

        def built(**patch):
            """This tree's index kernel traced ONCE with `patch` over its
            module's names: a form the probe alone builds (the lengths are
            an argument, so one trace serves every context)."""
            kept = {name: getattr(sis, name) for name in patch}
            sis.sparse_index_scores_pallas.clear_cache()
            for name, value in patch.items():
                setattr(sis, name, value)
            try:
                fn = index_program(sis)
                jax.block_until_ready(fn(pools, jnp.full((B,), contexts[0],
                                                         jnp.int32)))
            finally:
                for name, value in kept.items():
                    setattr(sis, name, value)
                sis.sparse_index_scores_pallas.clear_cache()
            return fn

        class NoCopies:
            """`pltpu` for a kernel whose copies neither start nor wait:
            it multiplies whatever its buffers hold."""
            def __init__(self, real):
                self.real = real

            def __getattr__(self, name):
                return getattr(self.real, name)

            def make_async_copy(self, *refs):
                class Nothing:
                    start = wait = staticmethod(lambda: None)
                return Nothing

        index_forms = {}
        if "index" in which:
            index_forms = {
                "copies_alone_us": built(
                    _tile_scores=lambda q, w, pages: jnp.zeros(
                        (pages.shape[0], pages.shape[-1]), jnp.float32)),
                "dot_alone_us": built(pltpu=NoCopies(sis.pltpu))}
        index_parents = [(tree, index_program(held["sparse_index_scores"]),
                          held["sparse_index_scores"])
                         for tree, held in parents.items()
                         if "sparse_index_scores" in held
                         and "index" in which]

        def xla_threshold(sc, ln):
            valid = jnp.arange(sc.shape[1])[None, :] < ln[:, None]
            return sm.select_mask(sc, valid, topk)

        def top_k_threshold(sc, ln):
            vals, _ = jax.lax.top_k(sc, min(topk, sc.shape[1]))
            thr = vals[:, -1:]
            tie = sc == thr
            need = topk - jnp.sum(sc > thr, -1, keepdims=True)
            return ((sc > thr) | (tie & (jnp.cumsum(tie, -1) <= need))) \
                & (jnp.arange(sc.shape[1])[None, :] < ln[:, None])

        def walk_attention(pools, l, sel, ln):
            return paged_attention_sparse(
                q, pools[0], pools[1], table, ln, sel, l,
                use_pallas=use_pallas, live=live)

        def walk_with(walk):
            """The kernel at another (width, tail, ahead) than the module's
            (off the chip it is interpreted, at the rehearsal's size)."""
            def one(pools, l, sel, ln):
                return pas.paged_attention_sparse_pallas(
                    q, pools[0], pools[1], table, ln, sel, l, live,
                    walk=walk, interpret=args.rehearse)
            return one

        walks = []
        for item in filter(None, args.attn_widths.split(",")):
            # width[/tail[/ahead]]; a width past 8 gets a tail of 4 (a
            # switch of more than nine bodies does not compile)
            width, *rest = (int(x) for x in item.split("/"))
            tail = rest[0] if rest else (width if width <= 8 else 4)
            ahead = rest[1] if len(rest) > 1 else pas.BLOCKS_AHEAD
            walk = pas._walk(maxp, (width, tail, ahead))
            walks.append((walk, twelve(walk_with(walk))))

        def walk_of(module):
            return twelve(
                lambda pools, l, sel, ln: module.paged_attention_sparse_pallas(
                    q, pools[0], pools[1], table, ln, sel, l, live,
                    interpret=args.rehearse))

        parent_walks = [(tree, walk_of(held["paged_attention_sparse"]))
                        for tree, held in parents.items()
                        if "paged_attention_sparse" in held]

        def gather_attention(pools, l, sel_idx, ln):
            """The other form: the selected rows gathered, then dense
            attention over them. sel_idx [B, topk] token positions."""
            kv, s, _ = pools
            page = jnp.take_along_axis(table, sel_idx // ps, axis=1)
            off = sel_idx % ps
            codes = kv[:, l][:, :, page, off]          # [2, KH, B, topk, Hd]
            scale = s[:, l][:, :, page, off]           # [2, KH, B, topk]
            k = codes[0].astype(jnp.float32) * scale[0][..., None]
            v = codes[1].astype(jnp.float32) * scale[1][..., None]
            qg = q.astype(jnp.float32).reshape(B, KH, H // KH, Hd) \
                * Hd ** -0.5
            sc = jnp.einsum("bkgd,kbsd->bkgs", qg, k)
            keep = (sel_idx < ln[:, None])[:, None, None, :]
            p = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1)
            return jnp.einsum("bkgs,kbsd->bkgd", p, v).reshape(B, H, Hd)

        def selection(sc, ln):
            return np.asarray(sparse_select(sc, ln, topk, ps,
                                            use_pallas=use_pallas, live=live))

        asked = [c if c == "mix" else int(c)
                 for c in filter(None, args.contexts.split(","))] or contexts
        for context in asked:
            if context == "mix":  # as a cell's slots are: no two alike
                ln = jnp.asarray(np.random.default_rng(7).integers(
                    contexts[0], contexts[-1] + 1, B), jnp.int32)
            else:
                ln = jnp.full((B,), context, jnp.int32)
            sc = jax.jit(scores_of, static_argnums=1)(pools, 0, ln)
            pages = int(np.sum(-(-np.asarray(ln) // ps)))
            line = dict(phase="kernels", slots=B, context=context,
                        calls_a_program=L, pages_a_call=pages)
            if "index" in which:
                us = timed(scores_fn, pools, ln) / L * 1e6
                block, ahead = sis._walk(Di, ps, idx.dtype.itemsize)
                line["index_scores_us"] = us
                line["index_scores"] = dict(
                    block=block, ahead=ahead, us_per_page=us / pages,
                    **{name: timed(fn, pools, ln) / L * 1e6
                       for name, fn in index_forms.items()})
                # the kernel itself on both sides (off the chip `sc` is
                # the XLA form's)
                ours = np.asarray(sis.sparse_index_scores_pallas(
                    qi, wt, pools[2], 0, table, ln, live,
                    interpret=args.rehearse))
                line["index_scores_parents"] = []
                for tree, fn, module in index_parents:
                    us = timed(fn, pools, ln) / L * 1e6
                    theirs = module.sparse_index_scores_pallas(
                        qi, wt, pools[2], 0, table, ln, live,
                        interpret=args.rehearse)
                    line["index_scores_parents"].append(dict(
                        tree=tree, us_per_call=us, us_per_page=us / pages,
                        same_scores=bool(np.array_equal(
                            np.asarray(theirs), ours)),
                        same_selection=bool(np.array_equal(
                            selection(theirs, ln),
                            selection(jnp.asarray(ours), ln)))))
            if "select" in which:
                forms = {
                    "select_kernel_us": lambda _, l, sc, ln: sparse_select(
                        sc + l * 0.0, ln, topk, ps, use_pallas=use_pallas,
                        live=live),
                    "select_xla_bitwise_us": lambda _, l, sc, ln:
                        xla_threshold(sc + l * 0.0, ln),
                    "select_top_k_us": lambda _, l, sc, ln: top_k_threshold(
                        sc + l * 0.0, ln)}
                masks = {}
                for name, fn in forms.items():
                    line[name] = timed(twelve(fn), (), sc, ln) / L * 1e6
                    masks[name] = np.asarray(jax.jit(
                        fn, static_argnums=(0, 1))((), 0, sc, ln))
                line["selections_agree"] = bool(all(
                    np.array_equal(m, masks["select_kernel_us"])
                    for m in masks.values()))
            if "attention" not in which:
                say(**line)
                continue
            sel = jnp.asarray(selection(sc, ln))
            line["attention_walk_us"] = timed(
                twelve(walk_attention), pools, sel, ln) / L * 1e6
            width, tail, ahead = pas._walk(maxp)
            line["attention_walk"] = dict(
                width=width, tail=tail, ahead=ahead, pages=pages,
                blocks=pas.walk_counts(np.asarray(ln), ps, maxp)[1],
                us_per_page=line["attention_walk_us"] / pages)
            line["attention_walks"] = []
            for walk, fn in walks:
                us = timed(fn, pools, sel, ln) / L * 1e6
                line["attention_walks"].append(dict(
                    width=walk[0], tail=walk[1], ahead=walk[2],
                    blocks=pas.walk_counts(np.asarray(ln), ps, maxp,
                                           walk=walk)[1],
                    us_per_call=us, us_per_page=us / pages))
            for tree, fn in parent_walks:
                us = timed(fn, pools, sel, ln) / L * 1e6
                line.setdefault("attention_walk_parents", []).append(dict(
                    tree=tree, us_per_call=us, us_per_page=us / pages))
            line["attention_dense_int8_us"] = timed(twelve(
                lambda pools, l, ln: paged_attention_int8(
                    q, pools[0], pools[1], table, ln, l, live=live,
                    interpret=args.rehearse)), pools, ln) / L * 1e6
            _, sel_idx = jax.lax.top_k(sel.astype(jnp.float32),
                                       min(topk, sel.shape[1]))
            line["attention_gather_xla_us"] = timed(
                twelve(gather_attention), pools, sel_idx, ln) / L * 1e6
            a = np.asarray(jax.jit(walk_attention, static_argnums=1)(
                pools, 0, sel, ln), np.float32)
            g = np.asarray(jax.jit(gather_attention, static_argnums=1)(
                pools, 0, sel_idx, ln), np.float32)
            line["walk_against_gather_max_abs"] = float(np.abs(a - g).max())
            line["bytes_walk"] = int(np.sum(np.asarray(ln))) \
                * entry.kv_bytes_per_token_layer(config)
            line["bytes_gather"] = int(np.sum(np.minimum(
                np.asarray(ln), topk))) * entry.kv_bytes_per_token_layer(
                    config)
            say(**line)
        del kv, s, idx, pools

    if not {"hazard", "compare", "step"} & set(phases):
        return 0

    for seed in range(args.seeds):
        seed = 2**31 + 4200 + seed
        t0 = time.perf_counter()
        params = jax.block_until_ready(
            entry.init_params(config, mcfg, seed, [dev])[0])
        say(phase="weights", seed=seed, seconds=time.perf_counter() - t0,
            bytes=sum(x.nbytes for x in jax.tree.leaves(params)))
        rng = np.random.default_rng(seed)
        key = jax.random.key(seed)

        # -- the known hazard: the largest prefill, twenty times -----------
        if "hazard" in phases:
            pool = PagePool.zeros(mcfg, config["serving"]["n_pages"], ps,
                                  dtype=jnp.int8)
            one = jnp.ones((1,), jnp.float32)
            times, toks = [], []
            for i in range(args.hazard_runs):
                n = int(rng.integers(big - ps * 4, big + 1))
                ids = np.zeros((1, big), np.int32)
                ids[0, :n] = rng.integers(0, vocab, n)
                table_row = np.zeros((1, big // ps), np.int32)
                pages = -(-n // ps)
                table_row[0, :pages] = 1 + np.arange(pages)
                t0 = time.perf_counter()
                tok, pool = em.prefill_batch_step(
                    params, mcfg, pool, jnp.asarray(ids),
                    jnp.asarray([n], jnp.int32), jnp.asarray(table_row),
                    one * 0, one, jnp.zeros((1,), jnp.int32),
                    jax.random.key_data(key), use_pallas,
                    sampling_flags=greedy)
                tok = int(jax.block_until_ready(tok)[0])
                times.append(time.perf_counter() - t0)
                toks.append(tok)
                say(phase="hazard", run=i, rows=big, prompt=n,
                    seconds=times[-1], token=tok)
            say(phase="hazard", done=True, runs=args.hazard_runs,
                tile_rows=sm.PREFILL_TILE_ROWS,
                first_s=times[0], median_warm_s=float(np.median(times[1:])),
                tokens_in_range=bool(all(0 <= t < vocab for t in toks)),
                memory_peak_bytes=(dev.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0))
            del pool

        # -- the three-part comparison -------------------------------------
        if "compare" in phases:
            n_new = 8
            ids = rng.integers(0, vocab, prompt_n + n_new).astype(np.int32)
            pages = prompt_bucket // ps + 1
            pool = PagePool.zeros(mcfg, pages + 2, ps, dtype=jnp.int8)
            table = np.zeros((1, maxp), np.int32)
            table[0, :pages] = 1 + np.arange(pages)
            padded = np.zeros((1, prompt_bucket), np.int32)
            padded[0, :prompt_n] = ids[:prompt_n]
            logits, pool = em.prefill_step(
                params, mcfg, pool, jnp.asarray(padded), jnp.int32(prompt_n),
                jnp.asarray(table[0, :prompt_bucket // ps]), use_pallas)
            served = [np.asarray(logits, np.float32)]
            # layer 0 of the first decode step, part by part
            first = prompt_n + 1
            w0 = sm.take_layer(sm.split_experts(params["layers"])[0], 0)
            for t in range(n_new):
                n = prompt_n + t + 1
                lg, pool = em.decode_step(
                    params, mcfg, pool, jnp.asarray(ids[n - 1:n]),
                    jnp.asarray(table), jnp.asarray([n], jnp.int32),
                    use_pallas)
                served.append(np.asarray(lg[0], np.float32))
                if n == first:
                    x = sm.embed(mcfg, params, jnp.asarray(ids[n - 1:n]))
                    h = rms_norm(x[:, None], w0["ln1"], mcfg.rms_eps
                                 ).astype(mcfg.dtype)
                    qi, _, wt = sm.project_index(
                        mcfg, h, w0, jnp.asarray([[n - 1]]))
                    ln = jnp.asarray([n], jnp.int32)
                    sc = sparse_index_scores(
                        qi[:, 0], wt[:, 0], pool.idx, 0, jnp.asarray(table),
                        ln, use_pallas=use_pallas)
                    picked = sparse_select(sc, ln, mcfg.index_topk, ps,
                                           use_pallas=use_pallas)
                    sc, picked = np.asarray(sc[0, :n]), np.asarray(
                        picked[0, :n])
            served = np.stack(served[:-1])  # rows prompt_n - 1 .. + n_new - 1
            del pool
            t0 = time.perf_counter()
            want, kept, _ = entry.reference_forward(
                config, params, ids[:-1], keep_layers=(0,))
            ref_s = time.perf_counter() - t0
            rows_ = np.arange(prompt_n - 1, prompt_n + n_new - 1)
            ref_scores = np.asarray(kept[0][0][first - 1, :first])
            ref_set = np.asarray(kept[0][1][first - 1, :first])
            top = float(np.abs(ref_scores).max())
            ordered = -np.sort(-ref_scores)
            k = mcfg.index_topk
            margin = float(ordered[k - 1] - ordered[k]) if first > k else None
            near = np.abs(ref_scores - ordered[min(k, first) - 1]) \
                <= 2 * SCORE_TOL * top
            rel = np.abs(served - want[rows_]).max(-1) \
                / np.abs(want[rows_]).max()
            dense = entry.reference_forward(config, params, ids[:-1],
                                            sparse=False)[0]
            miss = np.abs(served - dense[rows_]).max(-1) \
                / np.abs(dense[rows_]).max()
            argmax_agree = float((served.argmax(-1)
                                  == want[rows_].argmax(-1)).mean())
            line = dict(
                phase="compare", seed=seed, prompt=prompt_n,
                bucket=prompt_bucket, decode_steps=n_new - 1,
                reference_s=ref_s,
                score_max_abs_over_top=float(
                    np.abs(sc - ref_scores).max() / top),
                score_tol=SCORE_TOL,
                sets_differ=int((picked != ref_set).sum()),
                sets_differ_outside_margin=int(
                    ((picked != ref_set) & ~near).sum()),
                reference_margin_over_top=(margin / top
                                           if margin is not None else None),
                tokens_inside_margin=int(near.sum()),
                logits_rel=[float(r) for r in rel],
                logits_rel_median=float(np.median(rel)),
                logits_rel_max=float(rel.max()), median_tol=MEDIAN_TOL,
                logit_tol=LOGIT_TOL, argmax_agree=argmax_agree,
                dense_reference_rel=[float(r) for r in miss],
                dense_reference_rel_median=float(np.median(miss)))
            line["ok"] = bool(
                line["score_max_abs_over_top"] <= SCORE_TOL
                and line["sets_differ_outside_margin"] == 0
                and line["logits_rel_median"] <= MEDIAN_TOL
                and line["logits_rel_max"] <= LOGIT_TOL)
            line["negative_control_misses"] = bool(
                line["dense_reference_rel_median"] > MEDIAN_TOL)
            say(**line)
            del want, dense

        # -- a decode block at the cell's shape ----------------------------
        if "step" in phases:
            K = ecfg.decode_steps_per_dispatch
            n_pages = config["serving"]["n_pages"]
            pool = PagePool.zeros(mcfg, n_pages, ps, dtype=jnp.int8)

            # random rows, a (k|v, layer) slice at a time: the whole pool's
            # random bits at once do not fit beside it
            @functools.partial(jax.jit, donate_argnums=0)
            def fill(pool, key, h, l):
                a, b, c = jax.random.split(jax.random.fold_in(key, 2 * l + h),
                                           3)
                kv, sc, idx = pool.pages.kv, pool.pages.s, pool.idx
                kv = kv.at[h, l].set(jax.lax.bitcast_convert_type(
                    jax.random.bits(a, kv.shape[2:], jnp.uint8), jnp.int8))
                sc = sc.at[h, l].set(jax.random.uniform(
                    b, sc.shape[2:], jnp.float32, 0.005, 0.02))
                idx = idx.at[l].set(jax.random.normal(c, idx.shape[1:],
                                                      jnp.bfloat16))
                return type(pool)(type(pool.pages)(kv, sc, ps), idx)

            for l in range(mcfg.n_layers):
                for h in (0, 1):
                    pool = fill(pool, key, h, l)
            jax.block_until_ready(pool)
            for context in contexts[:2] if not args.rehearse else (40,):
                need = -(-(context + 4 * K) // ps)
                table = np.zeros((B, maxp), np.int32)
                for b in range(B):
                    table[b, :need] = 1 + b * need + np.arange(need)
                lengths = np.full((B,), context, np.int32) \
                    + rng.integers(0, ps, B).astype(np.int32)
                last = jnp.asarray(rng.integers(0, vocab, B), jnp.int32)
                active = jnp.ones((B,), bool)
                fl = jnp.zeros((B,), jnp.float32)
                ts = []
                for i in range(4):
                    t0 = time.perf_counter()
                    block, last, pool = em.decode_multi_step(
                        params, mcfg, pool, last, jnp.asarray(table),
                        jnp.asarray(lengths + i * K), active, fl, fl + 1,
                        jnp.zeros((B,), jnp.int32), jax.random.key_data(key),
                        K, use_pallas, sampling_flags=greedy)
                    jax.block_until_ready(block)
                    ts.append(time.perf_counter() - t0)
                say(phase="step", seed=seed, slots=B, context=context,
                    block_steps=K, first_block_s=ts[0],
                    ms_a_step=float(np.median(ts[1:])) / K * 1e3,
                    finite=bool(np.isfinite(np.asarray(block)).all()))
            del pool
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
