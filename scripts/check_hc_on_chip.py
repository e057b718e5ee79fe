#!/usr/bin/env python3
"""The mixing of four residual streams around ONE branch, alone on the
chip, at the published widths of `xing4.0-29b-a4b-int8-ep4`: the two
kernels of serving/hc_mix.py against the `jax.numpy` form of
models/hyper_connections.py that XLA compiles.

    chiprun -- python3 scripts/check_hc_on_chip.py [--tokens 128,1024]
        [--reps 200] [--blocks 16,32,64,128]

For each count of tokens (a decode step's 128 slots; a prefill group's
4 x 256) and each form, one program runs `--reps` mixes in a row
(`hc_pre`, the branch stood in for by its own input, `hc_post` over the
stream in place; the doubly stochastic H_res keeps the stream's size) so
that the host's dispatch is not in the time; it is timed by the host's
clock around `block_until_ready`, then traced once and the device time
summed by operation (benchmark/harness/xplane.py). Also: how far the
kernels' stream is from the XLA form's after ONE mix (bf16 stream, float32
coefficients: a rounding of the stream, 2^-8 of its size), and with
`--blocks` the kernels at those tokens a grid step.

One JSON object a line on stdout and in chiprun_out/hc_probe/probe.jsonl;
never a measurement on the CPU (`--rehearse` is the same control flow
there at a tiny size, the kernels interpreted).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="128,1024")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from benchmark.architectures import xing4
    from benchmark.harness import xplane
    from generativeaiexamples_tpu.models import hyper_connections as hc
    from generativeaiexamples_tpu.serving import hc_mix

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("check_hc_on_chip: no TPU (use --rehearse for "
                         "the control flow on the CPU)")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b-int8-ep4.json")) as fh:
        config = json.load(fh)
    if args.rehearse:
        from benchmark.tests.test_xing4 import tiny_file
        config = tiny_file()
        args.reps = min(args.reps, 3)
    cfg = xing4.model_config(config)
    n, C, L = cfg.hc_mult, cfg.dim, 3
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    key = jax.random.split(jax.random.key(57), 4)
    K = hc.widths(n)[0]
    phi = (jax.random.normal(key[0], (L, K, n * C), jnp.float32)
           * (n * C) ** -0.5).astype(dtype)
    b, alpha = hc.init_bias(cfg, L), jnp.ones((L, 3), jnp.float32)
    out_dir = os.path.join(ROOT, "chiprun_out", "hc_probe")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "probe.jsonl"), "a")

    def say(**row):
        row["device"] = dev.device_kind
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    def mix_xla(x):
        u, carry = hc.hc_pre(cfg, x, phi, b, alpha, 1)
        return hc.hc_post(cfg, x, u, carry)

    def mix_kernels(x, tb=None):
        u, coef = hc_mix.hc_pre_pallas(cfg, x, phi, b, alpha, 1,
                                       interpret=args.rehearse,
                                       tokens_a_block=tb)
        return hc_mix.hc_post_pallas(cfg, x, u, coef,
                                     interpret=args.rehearse,
                                     tokens_a_block=tb)

    def looped(mix):
        return jax.jit(lambda x: jax.lax.fori_loop(
            0, args.reps, lambda i, x: mix(x), x), donate_argnums=0)

    for T in (int(t) for t in args.tokens.split(",")):
        x0 = jax.random.normal(key[1], (T, n * C), jnp.float32).astype(dtype)
        one = jax.jit(mix_xla)(x0).astype(jnp.float32)
        got = jax.jit(mix_kernels)(x0).astype(jnp.float32)
        say(tokens=T, what="kernels against the XLA form after one mix",
            max_abs=float(jnp.abs(got - one).max()),
            stream_rms=float(jnp.sqrt(jnp.mean(one * one))))
        forms = [("xla", mix_xla), ("kernels", mix_kernels)]
        forms += [(f"kernels_tb{tb}",
                   functools.partial(mix_kernels, tb=int(tb)))
                  for tb in args.blocks.split(",") if tb and int(tb) <= T]
        for name, mix in forms:
            run = looped(mix)
            jax.block_until_ready(run(x0 + 0))  # compile, warm
            times = []
            for _ in range(3):
                x = x0 + 0
                jax.block_until_ready(x)
                t0 = time.perf_counter()
                jax.block_until_ready(run(x))
                times.append((time.perf_counter() - t0) / args.reps)
            trace_dir = os.path.join(out_dir, f"trace_{name}_{T}")
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(run(x0 + 0))
            ops = {}
            path = xplane.find_xplane(trace_dir)
            if path and not args.rehearse:
                reduced = xplane.reduce(xplane.load(path))
                for k, s in reduced["ops"].items():
                    kind = k.split("/", 1)[1]
                    ops[kind] = ops.get(kind, 0.0) + s / args.reps * 1e6
            top = dict(sorted(ops.items(), key=lambda kv: -kv[1])[:8])
            say(tokens=T, form=name, us_a_mix=min(times) * 1e6,
                us_a_mix_runs=[t * 1e6 for t in times],
                device_us_a_mix=sum(ops.values()) or None,
                device_us_by_op=top,
                least_us=xing4.hc_kernel(config, 2, T)["bytes"] / 819e9 * 1e6
                if not args.rehearse else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
