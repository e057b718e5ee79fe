#!/usr/bin/env python3
"""Both forms of the decode family's q/k/v projection, on the chip, at a
benchmark configuration's published widths:

    chiprun -- python3 scripts/measure_qkv_forms.py \
        --config mistral-7b-v0.3-int8 --steps 1,2,4,8 --live 11 --context 300

For every block length and both forms (`staged`: the head split fused
into the dot, so XLA stages the weight; `direct`: a plain 2-D matmul,
engine_model.direct_qkv) it compiles `decode_multi_step` at the cell's
shape, times it by the host's clock over `--reps` executions that end
in `block_until_ready`, then traces three executions and sums device
time by operation: the program, the staging (`slice_bitcast_fusion*`
and the weight-shaped `copy`), and each `jax.named_scope` of the block
(`attn.qkv`, `attn.out`, `mlp.gate_up`, `mlp.down`), which is how
PERF.md section 5's per-projection table and engine_model's
DIRECT_QKV_MAX_STEPS were read. The forms are forced by replacing
`engine_model.direct_qkv`; nothing in the served program is switched.

One JSON object per line on stdout and in
chiprun_out/qkv_forms/<config>.jsonl; never a measurement on the CPU
(`--rehearse` is the same control flow there at a tiny size).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCOPES = ("attn.qkv", "attn.out", "mlp.gate_up", "mlp.down", "lm_head",
          "loop.norm", "attn.post_norm", "mlp.post_norm")


def scopes_of(hlo_text: str) -> dict:
    """{instruction name: (the innermost named scope of the block that
    its `op_name` metadata names, else "-"; a signature: the tail of the
    `op_name` and the result's shape)} over a compiled program's text
    (a trace event carries the instruction, not its metadata)."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^ ]*)[^\n]*$",
                         hlo_text, re.M):
        op = re.search(r'op_name="([^"]*)"', m.group(0))
        path = op.group(1) if op else ""
        found = [(path.rfind(s), s) for s in SCOPES if s in path]
        shape = re.sub(r"\{[^}]*\}", "", m.group(2))[:48]
        out[m.group(1)] = (max(found)[1] if found else "-",
                           "/".join(path.split("/")[-2:]) + " " + shape)
    return out


def by_operation(xplane_path: str, program: str, scopes: dict,
                 top: int = 0) -> dict:
    """{"executions", "device_ms", "ops": {kind: ms}, "scopes": {...},
    "top": the `top` signatures with most time} over one trace, self
    times on the first device's "XLA Ops" line."""
    from benchmark.harness import xplane

    planes = xplane.load(xplane_path)
    lines = planes[xplane.device_planes(planes)[0]]
    mods = [m for m in lines.get(xplane.MODULE_LINE, [])
            if xplane.program_of(m[0]) == program]
    kinds, scoped, sigs = {}, {}, {}
    for name, start, self_s in xplane.self_times(lines[xplane.OPS_LINE]):
        if not any(m[1] <= start < m[1] + m[2] for m in mods):
            continue
        instr = name.split(" = ")[0].lstrip("%").strip()
        kind = re.sub(r"(\.(remat|clone)?\d*)+$", "", instr) or instr
        kinds[kind] = kinds.get(kind, 0.0) + self_s * 1e3
        sc, sig = scopes.get(instr, ("?", "?"))
        if kind.startswith("slice_bitcast_fusion") or kind == "copy":
            sc = "staging:" + kind
        scoped[sc] = scoped.get(sc, 0.0) + self_s * 1e3
        n, ms = sigs.get(f"{kind} {sig}", (0, 0.0))
        sigs[f"{kind} {sig}"] = (n + 1, ms + self_s * 1e3)
    return {"executions": len(mods),
            "device_ms": sum(m[2] for m in mods) * 1e3,
            "ops": dict(sorted(kinds.items(), key=lambda kv: -kv[1])[:14]),
            "scopes": scoped,
            "top": sorted(sigs.items(), key=lambda kv: -kv[1][1])[:top]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mistral-7b-v0.3-int8")
    ap.add_argument("--steps", default="1,2,4,8")
    ap.add_argument("--forms", default="staged,direct")
    ap.add_argument("--live", type=int, default=None,
                    help="active slots (default: all)")
    ap.add_argument("--context", type=int, default=300)
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--top", type=int, default=0,
                    help="also list the N operation signatures (kind, "
                         "op_name tail, result shape) with most time")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import architectures
    from benchmark.harness import system, xplane
    from generativeaiexamples_tpu.serving import engine_model as em
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    dev = jax.devices()[0]
    if args.rehearse:
        from benchmark.tests import test_ouro, test_rehearsal
        config = (test_ouro.TINY_OURO if args.config.startswith("ouro")
                  else test_rehearsal.TINY)
        args.context, args.reps = 20, 2
    elif dev.platform != "tpu":
        raise SystemExit("measure_qkv_forms: no TPU; refusing")
    else:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               args.config + ".json")) as fh:
            config = json.load(fh)
    entry = architectures.load(config)
    mcfg = entry.model_config(config)
    ecfg = system.engine_config(config)
    ps, B = ecfg.page_size, ecfg.max_batch_size
    maxp = ecfg.max_seq_len // ps
    n_pages = config["serving"]["n_pages"]
    live = B if args.live is None else min(args.live, B)
    need = -(-(args.context + 16) // ps)
    if need > maxp or 1 + live * need > n_pages:
        raise SystemExit(f"measure_qkv_forms: {live} slots at context "
                         f"{args.context} need {need} pages each; the "
                         f"configuration has {maxp} a slot, {n_pages} in all")
    out_dir = os.path.join(ROOT, "chiprun_out", "qkv_forms")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, args.config + ".jsonl"), "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    say(device=dev.device_kind, config=args.config, slots=B, live=live,
        context=args.context, passes=mcfg.n_passes, layers=mcfg.n_layers)
    params, _ = entry.init_params(config, mcfg, args.seed, [dev])
    pool = PagePool.zeros(mcfg, n_pages, ps, dtype=jnp.dtype(ecfg.kv_dtype))
    tables = np.zeros((B, maxp), np.int32)
    lengths = np.ones((B,), np.int32)
    active = np.zeros((B,), bool)
    for i in range(live):
        tables[i, :need] = 1 + i * need + np.arange(need)
        lengths[i] = args.context
        active[i] = True
    tokens = jnp.asarray(np.arange(B, dtype=np.int32) % 97 + 3)
    fixed = [jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(active),
             jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.float32),
             jnp.zeros((B,), jnp.int32), jax.random.PRNGKey(0)]
    served = em.direct_qkv

    for K in [int(k) for k in args.steps.split(",")]:
        for form in args.forms.split(","):
            em.direct_qkv = lambda cfg, n, _f=(form == "direct"): _f
            jax.clear_caches()

            t0 = time.perf_counter()
            compiled = em.decode_multi_step.lower(
                params, mcfg, pool, tokens, *fixed, K, None,
                sampling_flags=(True, False, False)).compile()
            compile_s = time.perf_counter() - t0

            def run(tok, pool):
                _, tok, pool = compiled(params, pool, tok, *fixed)
                return tok, pool

            tokens, pool = run(tokens, pool)
            jax.block_until_ready(tokens)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                tokens, pool = run(tokens, pool)
            jax.block_until_ready(tokens)
            host_ms = (time.perf_counter() - t0) * 1e3 / (args.reps * K)
            line = dict(form=form, n_steps=K, served_form=(
                "direct" if served(mcfg, K) else "staged"),
                compile_s=round(compile_s, 1), host_ms_per_step=host_ms)
            if dev.platform == "tpu":
                tdir = tempfile.mkdtemp(prefix="qkv_trace_")
                with jax.profiler.trace(tdir):
                    for _ in range(3):
                        tokens, pool = run(tokens, pool)
                    jax.block_until_ready(tokens)
                red = by_operation(xplane.find_xplane(tdir),
                                   "decode_multi_step",
                                   scopes_of(compiled.as_text()), args.top)
                shutil.rmtree(tdir, ignore_errors=True)
                steps = max(red["executions"], 1) * K
                line.update(
                    device_ms_per_step=red["device_ms"] / steps,
                    scope_ms_per_step={k: v / steps for k, v in
                                       sorted(red["scopes"].items())},
                    op_ms_per_step={k: v / steps
                                    for k, v in red["ops"].items()},
                    top_ms_per_step=[(k, n // steps, ms / steps)
                                     for k, (n, ms) in red["top"]])
            say(**line)
    em.direct_qkv = served
    return 0


if __name__ == "__main__":
    sys.exit(main())
