"""Per-kernel roofline microbench + THE kernel-parity entry point.

Two jobs, one geometry table:

1. **Roofline bench** (default): time each serving kernel standalone —
   paged linear decode attention (bf16 dispatch + the int8 narrow-scale
   kernel), paged TREE-verify attention (bf16 + int8 twins,
   serving/paged_attention_tree.py), the int8 weight matmul
   (ops/int8_matmul.py) and causal flash prefill (ops/attention.py) —
   and report achieved vs peak bytes/s and FLOP/s per kernel, from a
   first-principles traffic model (the bytes a perfect implementation
   must move, the FLOPs it must execute). Decode attention kernels are
   HBM-bound by construction, so `hbm_util` is their headline; matmuls
   read `mxu_util`. The summary rides `python bench.py`'s artifact
   under "extras" as kern_* keys (BENCH_KERNELS=0 skips), so a kernel
   regression is visible per-PR without decoding the e2e headline.

2. **Parity verify** (--verify): ONE entry point for every kernel-vs-
   oracle check — the int8 linear kernel vs the dequant oracle
   (absorbing the old scripts/check_int8_kernel.py, which now
   forwards here), both tree kernels vs the XLA gather references,
   and the fused first-token sampling tail vs the unfused
   sample_token pair (bitwise greedy, identical draw under a fixed
   key). On TPU the kernels run on hardware; on CPU they run in
   Pallas interpret mode — same code path CI gates via
   scripts/smoke_kernels.py. Nonzero exit on any mismatch.

Usage:
    python scripts/bench_kernels.py [--json]        # roofline bench
    python scripts/bench_kernels.py --verify [B] [maxp]
    BENCH_KERNELS_ITERS=50 python scripts/bench_kernels.py

Peaks come from a device-kind table (v5e/v4/v5p/v6e); a device that is
not in it (the CPU included) is an error for the roofline bench.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# (hbm GB/s, bf16 TFLOP/s, int8 TOP/s) per jax device_kind substring.
# Public spec-sheet numbers; the point is a STABLE denominator so the
# util gauges are comparable PR-over-PR, not a lab-grade calibration.
_PEAKS = {
    "v5 lite": (819.0, 197.0, 394.0),
    "v5e": (819.0, 197.0, 394.0),
    "v4": (1228.0, 275.0, 275.0),
    "v5p": (2765.0, 459.0, 918.0),
    "v6 lite": (1640.0, 918.0, 1836.0),
    "v6e": (1640.0, 918.0, 1836.0),
}


def _peaks():
    """Published peaks of the attached device; a device that is not in
    the table is an error — a peak is looked up, never assumed."""
    kind = jax.devices()[0].device_kind.lower()
    for key, (gbps, tflops, tops) in _PEAKS.items():
        if key in kind:
            return kind, gbps, tflops, tops
    raise SystemExit(
        f"bench_kernels: no published peaks for device_kind {kind!r} "
        f"(known: {sorted(_PEAKS)}); add its spec-sheet row to _PEAKS")


def _timeit(fn, iters: int) -> float:
    """Median wall seconds per call (post-compile, post-warm)."""
    jax.block_until_ready(fn())  # compile
    jax.block_until_ready(fn())  # warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _entry(name, secs, bytes_moved, flops, peak_gbps, peak_flops):
    gb_s = bytes_moved / secs / 1e9
    gf_s = flops / secs / 1e9
    return {
        f"kern_{name}_ms": round(secs * 1e3, 4),
        f"kern_{name}_gb_s": round(gb_s, 2),
        f"kern_{name}_gflop_s": round(gf_s, 1),
        f"kern_{name}_hbm_util": round(gb_s / peak_gbps, 4),
        f"kern_{name}_mxu_util": round(gf_s / 1e3 / peak_flops, 4),
    }


def _geometry(on_tpu: bool):
    """llama3-8b deployment decode shapes on TPU; toy shapes on CPU
    (the CPU run exists to keep the script importable/covered, not to
    read utilizations)."""
    if on_tpu:
        return dict(B=128, H=32, KH=8, Hd=128, ps=128, maxp=4,
                    spec_k=3, branches=4, mm=(128, 4096, 4096),
                    prefill_s=2048, iters=int(
                        os.environ.get("BENCH_KERNELS_ITERS", "30")))
    return dict(B=4, H=4, KH=2, Hd=64, ps=16, maxp=4,
                spec_k=2, branches=2, mm=(8, 256, 256),
                prefill_s=64, iters=int(
                    os.environ.get("BENCH_KERNELS_ITERS", "3")))


def _pools(g, key):
    """Random bf16 + fused-int8 (L=1) pools at the bench geometry,
    plus a shared page table / ragged lengths."""
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


def run_bench() -> dict:
    """Roofline pass; returns the flat kern_* extras dict."""
    from generativeaiexamples_tpu.ops import attention as attn_ops
    from generativeaiexamples_tpu.ops.int8_matmul import int8_matmul
    from generativeaiexamples_tpu.ops.quant import quantize_tensor
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention_dispatch, paged_tree_attention_reference)
    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        paged_attention_int8)
    from generativeaiexamples_tpu.serving.paged_attention_tree import (
        paged_tree_attention, tree_shape_of)

    on_tpu = jax.default_backend() == "tpu"
    g = _geometry(on_tpu)
    kind, peak_gbps, peak_bf16, peak_int8 = _peaks()
    B, H, KH, Hd, ps = g["B"], g["H"], g["KH"], g["Hd"], g["ps"]
    iters = g["iters"]
    key = jax.random.PRNGKey(0)
    pools = _pools(g, key)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, Hd),
                          jnp.float32).astype(jnp.bfloat16)
    out = {"kern_backend": jax.default_backend(),
           "kern_device_kind": kind,
           "kern_peak_gbps": peak_gbps,
           "kern_peak_tflops_bf16": peak_bf16,
           "kern_peak_tops_int8": peak_int8}

    sum_len = pools["sum_len"]
    # Traffic model, paged DECODE attention: a perfect kernel reads
    # each live token's k AND v exactly once (+ q/out, negligible at
    # decode shapes), and runs the qk + pv matmuls = 4 * H * Hd FLOPs
    # per (q position, kv token) pair.
    dec_flops = 4.0 * H * Hd * sum_len
    bf16_bytes = 2.0 * sum_len * KH * Hd * 2
    int8_bytes = 2.0 * sum_len * KH * (Hd + 4)  # codes + f32 scale

    out.update(_entry(
        "paged_bf16",
        _timeit(lambda: paged_attention_dispatch(
            q, pools["kb"], pools["vb"], pools["table"], pools["lengths"]),
            iters),
        bf16_bytes, dec_flops, peak_gbps, peak_bf16))

    if on_tpu:
        out.update(_entry(
            "paged_int8",
            _timeit(lambda: paged_attention_int8(
                q, pools["kv"], pools["s"], pools["table"],
                pools["lengths"], 0), iters),
            int8_bytes, dec_flops, peak_gbps, peak_int8))

    # TREE verify: r packed positions share ONE kv stream; span grows
    # by r-1 tree slots per row.
    r = pools["r"]
    tree = (g["spec_k"], g["branches"])
    span = sum_len + B * (r - 1)
    tree_flops = 4.0 * H * Hd * r * span
    qt = jax.random.normal(jax.random.PRNGKey(2), (B, H, r, Hd),
                           jnp.float32).astype(jnp.bfloat16)
    from generativeaiexamples_tpu.serving.engine_model import _tree_layout
    _, anc = _tree_layout(*tree)
    assert tree_shape_of(anc, *tree) is not None
    if on_tpu:
        out.update(_entry(
            "tree_bf16",
            _timeit(lambda: paged_tree_attention(
                qt, pools["kb"], pools["vb"], pools["table"],
                pools["lengths"], tree), iters),
            2.0 * span * KH * Hd * 2, tree_flops, peak_gbps, peak_bf16))
        out.update(_entry(
            "tree_int8",
            _timeit(lambda: paged_attention_int8(
                qt.transpose(0, 2, 1, 3), pools["kv"], pools["s"],
                pools["table"], pools["lengths"], 0, q_rep=r, tree=tree),
                iters),
            2.0 * span * KH * (Hd + 4), tree_flops, peak_gbps, peak_int8))
        # The XLA gather route the kernels replace, at the same shape —
        # the speedup denominator for the tree-kernel story.
        out.update(_entry(
            "tree_xla_ref",
            _timeit(lambda: paged_tree_attention_reference(
                qt, pools["kb"], pools["vb"], pools["table"],
                pools["lengths"], anc), iters),
            2.0 * span * KH * Hd * 2, tree_flops, peak_gbps, peak_bf16))

    # int8 weight matmul (the decode-step FLOP carrier).
    M, K, N = g["mm"]
    x = jax.random.normal(jax.random.PRNGKey(3), (M, K),
                          jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(4), (K, N), jnp.float32)
    qt8 = quantize_tensor(w)
    if on_tpu:
        out.update(_entry(
            "int8_matmul",
            _timeit(lambda: int8_matmul(x, qt8.q, qt8.s), iters),
            float(M * K * 2 + K * N + M * N * 2), 2.0 * M * K * N,
            peak_gbps, peak_int8))

    # Causal flash prefill at one bucket (compute-bound end of the
    # roofline; ~half the square is masked off).
    S = g["prefill_s"]
    qp = jax.random.normal(jax.random.PRNGKey(5), (1, H, S, Hd),
                           jnp.float32).astype(jnp.bfloat16)
    kp = jax.random.normal(jax.random.PRNGKey(6), (1, KH, S, Hd),
                           jnp.float32).astype(jnp.bfloat16)
    vp = jax.random.normal(jax.random.PRNGKey(7), (1, KH, S, Hd),
                           jnp.float32).astype(jnp.bfloat16)
    out.update(_entry(
        "flash_prefill",
        _timeit(lambda: attn_ops.attention(
            qp, kp, vp, causal=True,
            lengths=jnp.asarray([S], jnp.int32)), iters),
        float((S * H + 2 * S * KH) * Hd * 2 + S * H * Hd * 2),
        2.0 * H * Hd * S * S, peak_gbps, peak_bf16))
    return out


# ---------------------------------------------------------------------------
# --verify: the one kernel-parity entry point
# ---------------------------------------------------------------------------


def _check(name, got, want, tol_rel):
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    mag = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    ok = err <= tol_rel * max(1.0, mag)
    print(f"[kernels] {name}: max_abs_err={err:.4e} "
          f"(ref magnitude {mag:.3f}) {'OK' if ok else 'MISMATCH'}")
    assert ok, f"{name}: kernel does not match oracle ({err:.4e})"


def run_verify(B: int = 0, maxp: int = 0) -> None:
    """Kernel-vs-oracle parity: hardware kernels on TPU, interpret
    mode on CPU (scripts/smoke_kernels.py's CI gate). Asserts on any
    mismatch."""
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


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--help" in argv or "-h" in argv:
        print(__doc__)
        return
    verify = "--verify" in argv
    as_json = "--json" in argv
    pos = [a for a in argv if not a.startswith("-")]
    if verify:
        run_verify(int(pos[0]) if pos else 0,
                   int(pos[1]) if len(pos) > 1 else 0)
        return
    out = run_bench()
    if as_json:
        print(json.dumps(out))
    else:
        for k in sorted(out):
            print(f"{k}: {out[k]}")


if __name__ == "__main__":
    main()
