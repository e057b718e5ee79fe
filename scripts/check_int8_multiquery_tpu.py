"""Numerics check for the multi-query (speculative-verify) form of the
fused int8 kernel on the real chip: paged_attention_int8(q_rep=R) must
match R independent q_rep=1 calls at lengths+j, and both must match the
dequantize-then-attend oracle.

Run: python scripts/check_int8_multiquery_tpu.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.serving.paged_attention_int8 import (
    paged_attention_int8, paged_attention_int8_reference_fused)


def main() -> None:
    assert jax.default_backend() != "cpu", "needs the TPU chip"
    rng = np.random.default_rng(0)
    B, L, KH, G, Hd, P, ps, maxp, R = 4, 2, 8, 4, 128, 24, 128, 4, 3
    H = KH * G
    kv = jnp.asarray(rng.integers(-127, 128, (2, L, KH, P, ps, Hd),
                                  dtype=np.int8))
    scales = jnp.asarray(
        rng.uniform(0.5, 2.0, (2, L, KH, P, ps)).astype(np.float32) / 127)
    table = jnp.asarray(
        rng.choice(np.arange(1, P), (B, maxp), replace=False).astype(
            np.int32))
    lengths = jnp.asarray([ps * 2 + 17, 61, ps * 3, 128], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, R, H, Hd)).astype(np.float32))
    layer = 1

    got = np.asarray(paged_attention_int8(q, kv, scales, table, lengths,
                                          layer, q_rep=R))
    # Oracle 1: R independent single-query kernel calls.
    singles = np.stack([
        np.asarray(paged_attention_int8(q[:, j], kv, scales, table,
                                        lengths + j, layer))
        for j in range(R)], axis=1)
    # Oracle 2: reference dequantize-then-attend.
    refs = np.stack([
        np.asarray(paged_attention_int8_reference_fused(
            q[:, j], kv[:, layer], scales[:, layer], table, lengths + j))
        for j in range(R)], axis=1)

    e_single = np.abs(got - singles).max()
    e_ref = np.abs(got - refs).max()
    print(f"[mq] max|multi - singles| = {e_single:.3e}")
    print(f"[mq] max|multi - reference| = {e_ref:.3e}")
    assert e_single < 1e-4, e_single
    assert e_ref < 2e-2, e_ref  # int8 path vs f32 math re-dequantized
    print("[mq] OK")


if __name__ == "__main__":
    main()
