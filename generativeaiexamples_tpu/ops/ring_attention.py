"""Ring attention: sequence-parallel exact attention over the mesh.

The long-context story the reference cannot tell: its sequence-length
strategy is application-level context budgeting (SURVEY.md §5.7 — chunk
caps, retrieval budgets, recursive summarization) because all attention
lives inside TRT-LLM on one GPU's memory. Here sequences shard across
the mesh "sequence" axis and attention is computed EXACTLY with a ring
schedule (the Ring Attention construction): each device holds one
sequence shard of Q for the whole computation while K/V shards rotate
around the ring via `ppermute`; partial results merge with the online-
softmax rule, so the full S x S score matrix never exists on any chip
and per-chip memory scales with S / ring_size.

ICI mapping: the "sequence" axis is an in-slice mesh axis
(parallel/mesh.py MESH_AXIS_NAMES), so each rotation is a
nearest-neighbour ICI hop that overlaps with the local attention block —
the standard TPU ring pipeline. Causal masking works on absolute
positions derived from each shard's ring index, so rotations need no
re-indexing.

Usage: wrap with shard_map over ("sequence",) — `ring_attention` is the
per-device function; `ring_attention_sharded` does the wrapping.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, kv_pos, scale, causal):
    """Attention scores of a local Q block against one K/V block, with
    running-softmax stats returned for cross-block merging.
    q [B,H,Sq,D], k/v [B,KH,Sk,D]; positions are ABSOLUTE."""
    H = q.shape[1]
    KH = k.shape[1]
    if KH != H:  # GQA
        k = jnp.repeat(k, H // KH, axis=1)
        v = jnp.repeat(v, H // KH, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B,H,Sq,1]
    # A fully-masked block contributes nothing; clamp so exp() is finite.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    if causal:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m_safe, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Online-softmax merge of two partial attention results."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1 + o2 * a2
    l = l1 * a1 + l2 * a2
    return o, m, l


def ring_attention(
    q: jax.Array,  # [B, H, S_local, D] — this device's query shard
    k: jax.Array,  # [B, KH, S_local, D] — this device's key shard
    v: jax.Array,
    *,
    axis_name: str = "sequence",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Per-device body (call under shard_map over `axis_name`). Shards
    are contiguous sequence chunks in ring order: global position of
    local index i on ring rank r is r * S_local + i."""
    B, H, S_local, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    ring = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    q_pos = rank * S_local + jnp.arange(S_local)

    # Rotation r delivers the K/V shard originally on rank (rank - r).
    perm = [(i, (i + 1) % ring) for i in range(ring)]

    # Mark the accumulators as varying over the ring axis: they are
    # per-shard state from step 0's output onward, and shard_map's
    # varying-axis tracking requires the loop carry type to say so up
    # front.
    vary = lambda x: jax.lax.pcast(x, axis_name, to="varying")  # noqa: E731
    o = vary(jnp.zeros((B, H, S_local, D), jnp.float32))
    m = vary(jnp.full((B, H, S_local, 1), NEG_INF / 2, jnp.float32))
    l = vary(jnp.zeros((B, H, S_local, 1), jnp.float32))

    def attend(r, o, m, l, k_cur, v_cur):
        src = (rank - r) % ring
        kv_pos = src * S_local + jnp.arange(S_local)
        o2, m2, l2 = _block_attn(q, k_cur, v_cur, q_pos, kv_pos, scale,
                                 causal)
        return _merge(o, m, l, o2, m2, l2)

    def step(r, carry):
        o, m, l, k_cur, v_cur = carry
        o, m, l = attend(r, o, m, l, k_cur, v_cur)
        # Rotate K/V one hop around the ring (overlappable with the
        # NEXT block's compute by XLA's latency-hiding scheduler).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    # ring-1 attend+rotate steps, then the last block attends WITHOUT
    # a rotation (two discarded ICI hops per call otherwise).
    o, m, l, k_last, v_last = jax.lax.fori_loop(
        0, ring - 1, step, (o, m, l, k, v))
    o, m, l = attend(ring - 1, o, m, l, k_last, v_last)
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (padding)
    return (o / l).astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,  # [B, H, S, D] GLOBAL arrays (sharded or to-shard)
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = "sequence",
) -> jax.Array:
    """shard_map wrapper: S splits over the mesh sequence axis, heads/
    batch follow their usual axes (replicated here; compose with the
    tensor axis by extending the specs)."""
    if q.shape[2] % mesh.shape[axis_name]:
        raise ValueError(
            f"sequence length {q.shape[2]} must be divisible by the "
            f"{mesh.shape[axis_name]}-way {axis_name} axis")
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
