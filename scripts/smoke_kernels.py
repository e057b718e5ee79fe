"""Kernel-parity smoke: the CPU CI gate for the Pallas tree-attention
kernels and the fused first-token sampling tail.

Runs the kernel-parity suite (run_verify below: the int8 linear kernel
vs the dequant oracle, both tree kernels vs the XLA gather references,
the fused first-token sampling tail vs the unfused pair) in
Pallas INTERPRET mode on the CPU backend — the same kernel code that
compiles on TPU, executed by the Pallas interpreter and pinned against
the XLA gather references — then an end-to-end engine check: a
tree-speculative engine served twice, once on the reference attention
route and once with ENGINE_TREE_KERNEL_INTERPRET=1 forcing the Pallas
kernels, must emit byte-identical greedy streams (the commit-semantics
contract: the kernel may only change speed, never content).

CI-grade: exits nonzero on any violation, prints one JSON summary line.

Usage:
    JAX_PLATFORMS=cpu python scripts/smoke_kernels.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_E2E = r'''
import json, os, sys
import jax
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

cfg = llama.LlamaConfig.tiny()
params = llama.init_params(cfg, jax.random.PRNGKey(3))
ecfg = EngineConfig(max_batch_size=2, max_seq_len=256, page_size=8,
                    prefill_buckets=(16,), decode_steps_per_dispatch=2,
                    speculative_k=2, speculative_tree_branches=3,
                    step_plans=True, pace_emission_max_streams=0,
                    kv_dtype=os.environ.get("SMOKE_KV_DTYPE", "bfloat16"))
eng = LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)
eng.start()
toks = [ev["token_id"]
        for ev in eng.generate_stream([7, 8, 9, 7, 8, 9, 7, 8],
                                      max_new_tokens=48)
        if ev["token_id"] >= 0]
# A prompt past the biggest bucket takes the CHUNKED prefill path, so
# its finish exercises the fused first-token tail (rider_sample plan).
long_prompt = [(i * 7) % cfg.vocab_size for i in range(40)]
toks_long = [ev["token_id"]
             for ev in eng.generate_stream(long_prompt, max_new_tokens=8)
             if ev["token_id"] >= 0]
snap = eng.metrics.snapshot()
eng.stop()
print(json.dumps({"tokens": toks, "tokens_long": toks_long,
                  "spec_tps": snap["spec_tokens_per_step"],
                  "fused_sample": snap["fused_sample_dispatches"]}))
'''


def _run_e2e(kv_dtype: str, interpret_kernels: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", SMOKE_KV_DTYPE=kv_dtype)
    if interpret_kernels:
        env["ENGINE_TREE_KERNEL_INTERPRET"] = "1"
    else:
        env.pop("ENGINE_TREE_KERNEL_INTERPRET", None)
    proc = subprocess.run([sys.executable, "-c", _E2E], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print(proc.stderr[-4000:], file=sys.stderr)
        raise SystemExit(f"e2e child failed (kv_dtype={kv_dtype}, "
                         f"interpret={interpret_kernels})")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _geometry(on_tpu: bool):
    """llama3-8b deployment decode shapes on TPU; toy shapes on CPU."""
    if on_tpu:
        return dict(B=128, H=32, KH=8, Hd=128, ps=128, maxp=4,
                    spec_k=3, branches=4)
    return dict(B=4, H=4, KH=2, Hd=64, ps=16, maxp=4, spec_k=2, branches=2)


def _pools(g, key):
    """Random bf16 + fused-int8 (L=1) pools at the parity geometry,
    plus a shared page table / ragged lengths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        fuse_kv, quantize_kv)

    B, KH, Hd, ps, maxp = g["B"], g["KH"], g["Hd"], g["ps"], g["maxp"]
    P = B * maxp + 1
    ks_ = jax.random.split(key, 3)
    k = jax.random.normal(ks_[0], (KH, P, ps, Hd), jnp.float32)
    v = jax.random.normal(ks_[1], (KH, P, ps, Hd), jnp.float32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    kv, s = fuse_kv(kq, ks, vq, vs)
    rng = np.random.default_rng(0)
    table = np.zeros((B, maxp), np.int32)
    perm = rng.permutation(np.arange(1, P))
    for b in range(B):
        table[b] = perm[b * maxp:(b + 1) * maxp]
    # Ragged, with tree-slot headroom at the top end.
    r = 1 + g["branches"] * g["spec_k"]
    lengths = rng.integers(max(1, ps // 2), maxp * ps - r, (B,))
    return {
        "kb": k.astype(jnp.bfloat16), "vb": v.astype(jnp.bfloat16),
        "kv": kv[:, None], "s": s[:, None],  # L=1 fused pool
        "table": jnp.asarray(table),
        "lengths": jnp.asarray(lengths.astype(np.int32)),
        "sum_len": int(lengths.sum()), "r": r,
    }


def _check(name, got, want, tol_rel):
    import jax.numpy as jnp

    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    mag = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    ok = err <= tol_rel * max(1.0, mag)
    print(f"[kernels] {name}: max_abs_err={err:.4e} "
          f"(ref magnitude {mag:.3f}) {'OK' if ok else 'MISMATCH'}")
    assert ok, f"{name}: kernel does not match oracle ({err:.4e})"


def run_verify(B: int = 0, maxp: int = 0) -> None:
    """Kernel-vs-oracle parity: hardware kernels on TPU (through
    scripts/check_int8_kernel.py), interpret mode on CPU (main()'s CI
    gate). Asserts on any mismatch."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.serving.engine_model import _tree_layout
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_tree_attention_int8_reference_fused,
        paged_tree_attention_reference)
    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        paged_attention_int8, paged_attention_int8_reference, quantize_kv)
    from generativeaiexamples_tpu.serving.paged_attention_tree import (
        paged_tree_attention)

    on_tpu = jax.default_backend() == "tpu"
    interp = not on_tpu
    g = _geometry(on_tpu)
    if B:
        g["B"] = B
    if maxp:
        g["maxp"] = maxp
    # int8 tolerances: quantization noise dominates (the old
    # check_int8_kernel bound); bf16 pools compare at bf16 rounding.
    tol8, tolb = 3e-2, (2e-2 if on_tpu else 5e-5)
    pools = _pools(g, jax.random.PRNGKey(0))
    H, KH, Hd, ps = g["H"], g["KH"], g["Hd"], g["ps"]
    Bv = g["B"]
    q = jax.random.normal(jax.random.PRNGKey(1), (Bv, H, Hd),
                          jnp.float32).astype(jnp.bfloat16)
    kv, s = pools["kv"], pools["s"]
    _check("paged_int8_linear",
           paged_attention_int8(q, kv, s, pools["table"],
                                pools["lengths"], 0, interpret=interp),
           paged_attention_int8_reference(
               q.astype(jnp.float32), kv[0, 0], s[0, 0], kv[1, 0],
               s[1, 0], pools["table"], pools["lengths"]),
           tol8)

    for (tk, tm) in {(g["spec_k"], g["branches"]), (2, 2), (2, 8)}:
        r = 1 + tk * tm
        _, anc = _tree_layout(tk, tm)
        qt = jax.random.normal(jax.random.PRNGKey(2), (Bv, H, r, Hd),
                               jnp.float32).astype(jnp.bfloat16)
        lengths = jnp.minimum(pools["lengths"],
                              g["maxp"] * ps - r)
        _check(f"tree_bf16_k{tk}m{tm}",
               paged_tree_attention(qt, pools["kb"], pools["vb"],
                                    pools["table"], lengths, (tk, tm),
                                    interpret=interp),
               paged_tree_attention_reference(
                   qt, pools["kb"], pools["vb"], pools["table"],
                   lengths, anc),
               tolb)
        _check(f"tree_int8_k{tk}m{tm}",
               paged_attention_int8(
                   qt.transpose(0, 2, 1, 3), kv, s, pools["table"],
                   lengths, 0, q_rep=r, tree=(tk, tm),
                   interpret=interp).transpose(0, 2, 1, 3),
               paged_tree_attention_int8_reference_fused(
                   qt, kv[:, 0], s[:, 0], pools["table"], lengths, anc),
               tol8)

    _verify_fused_sampling()
    print("[kernels] verify: all parity checks passed")


def _verify_fused_sampling() -> None:
    """Fused first-token tail == unfused pair: bitwise greedy, and the
    identical categorical draw under the same key for sampled flags."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import engine_model

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(9))
    W = 16
    toks = jnp.asarray(np.arange(2, 2 + W)[None, :], jnp.int32)
    valid = jnp.asarray(W, jnp.int32)
    key = jax.random.PRNGKey(42)
    for temp, flags in ((0.0, (True, False, False)),
                        (0.9, (False, True, True))):
        cache = llama.KVCache.zeros(cfg, 1, max_len=W)
        logits, _ = engine_model.prefill_chunk_step(
            params, cfg, cache, toks, valid, False)
        want = engine_model.sample_token(logits, temp, 0.95, 20, key,
                                         *flags)
        lt = jnp.zeros((4,), jnp.int32)
        cache = llama.KVCache.zeros(cfg, 1, max_len=W)
        got, lt2, _ = engine_model.prefill_chunk_sample_step(
            params, cfg, cache, toks, valid, lt,
            jnp.asarray(1, jnp.int32), temp, 0.95, 20, key, False,
            sampling_flags=flags)
        assert int(got) == int(want), (temp, int(got), int(want))
        assert int(lt2[1]) == int(want)
        # sample_token_into: the merged finish dispatch.
        lt = jnp.zeros((4,), jnp.int32)
        got3, lt3 = engine_model.sample_token_into(
            lt, jnp.asarray(2, jnp.int32), logits, temp, 0.95, 20, key,
            *flags)
        assert int(got3) == int(want) and int(lt3[2]) == int(want)
        print(f"[kernels] fused_sampling temp={temp}: token "
              f"{int(want)} identical across fused/unfused")


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # before jax is imported
    # 1. Kernel parity + fused-sampling equality (interpret mode).
    run_verify()

    # 2. E2E commit semantics: reference route vs forced Pallas
    # kernels, bf16 and int8 pools — byte-identical greedy streams,
    # with speculation actually engaged and the fused sampling tail
    # actually used.
    summary = {"parity": "ok"}
    for kvd in ("bfloat16", "int8"):
        ref = _run_e2e(kvd, False)
        ker = _run_e2e(kvd, True)
        assert ref["tokens"] == ker["tokens"], (
            f"{kvd}: kernel route changed the greedy stream "
            f"(ref {ref['tokens'][:8]}... vs kernel {ker['tokens'][:8]}...)")
        assert ref["tokens_long"] == ker["tokens_long"], (
            f"{kvd}: chunked-prefill stream diverged under the kernel "
            f"route")
        assert len(ref["tokens"]) == 48, len(ref["tokens"])
        assert ref["spec_tps"] > 1.0, ref["spec_tps"]
        # The long prompt's finish must have ridden the fused
        # first-token tail (engine.fused_sampling default-on).
        assert ref["fused_sample"] >= 1, ref["fused_sample"]
        summary[f"{kvd}_tokens"] = len(ref["tokens"])
        summary[f"{kvd}_spec_tokens_per_step"] = round(ker["spec_tps"], 3)
        summary[f"{kvd}_fused_sample_dispatches"] = ker["fused_sample"]
    print(json.dumps({"smoke_kernels": summary}))
    print("smoke_kernels: PASS", file=sys.stderr)


if __name__ == "__main__":
    main()
