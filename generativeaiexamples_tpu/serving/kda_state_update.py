"""A decode step's state update of ONE delta-rule (KDA) layer, in place.

For every live decode slot the recurrence of models/linear_attn_moe.py,
per head on a state S [key, value] float32:

    S = Diag(a) S        r = S^T k        u = b (v - r)
    S = S + k (x) u      o = S^T q

over the per-slot state pool [layers, slots, H, d, d] float32
(kv_cache.HybridPool.state). Where serving/ssm_state_update.py's decay is
one scalar a head, this one differs by ROW of the state (a key channel
each), and the rank-one correction needs k^T S of the DECAYED state
before anything can be written: two passes over a head's block, which is
in VMEM by then. The work is the state itself, read once and written once
(2 x 2 MiB a slot and layer at H, d = 32, 128); the pool is ALIASED input
to output and each grid step moves one slot's block through VMEM.

Idle slots cost nothing and are never written: the walk over the live
slots (`live_slots`, `walked_slot`) is ssm_state_update's, shared. With
no live slot at all the first step copies its block through unchanged.

What the kernel takes per slot: a, k and q over the KEY channels as
COLUMNS of one [d, 3 * H] block (the key channels on the sublanes; lanes
h, H + h and 2 * H + h are head h's a, k and q), so that a head's column
is one constant lane, broadcast; v and the step b, broadcast over the
value lanes, as rows [H, d]. (Handed over as rows [H, d] and turned into
columns inside, three relayouts a head, a call over 80 slots took 654 us
where this form takes 521, 63 and 79 % of what the state's bytes take at
the HBM's rate: my chip run, PR 48.) Off the chip the same function in
XLA (`kda_state_update_reference`), which tests hold the kernel to.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.serving.ssm_state_update import (
    live_slots, walked_slot)
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

_VMEM_LIMIT_BYTES = 48 << 20


def _update_kernel(layer_ref, order_ref, n_ref, akq_ref, v_ref, b_ref, s_in,
                   s_out, o_ref):
    del layer_ref, order_ref  # read by the index maps
    H = s_in.shape[0]
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _():
        for h in range(H):  # static: a column is picked by a constant lane
            k_col = akq_ref[:, H + h:H + h + 1]            # [d, 1]
            s = akq_ref[:, h:h + 1] * s_in[h]
            r = jnp.sum(k_col * s, axis=0, keepdims=True)  # [1, d]
            u = b_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - r)
            s = s + k_col * u
            s_out[h] = s
            o_ref[h:h + 1, :] = jnp.sum(
                akq_ref[:, 2 * H + h:2 * H + h + 1] * s, axis=0,
                keepdims=True)

    @pl.when((n == 0) & (i == 0))
    def _():  # nobody is live: the block goes back as it came
        s_out[...] = s_in[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_state_update_pallas(state, layer, order, n_live, a, k, q, v, b, *,
                            interpret: bool = False):
    """The kernel form. state [L, slots, H, d, d] float32; order [B] the
    live slots first, n_live [1]; a, k, q [B, H, d] over the key channels,
    v, b [B, H, d] over the value channels, all float32. Returns (state,
    o [B, H, d])."""
    _, B, H, dk, dv = state.shape
    lanes = -(-3 * H // 128) * 128
    akq = jnp.concatenate([a, k, q], axis=1).transpose(0, 2, 1)  # [B, d, 3H]
    akq = jnp.pad(akq, ((0, 0), (0, 0), (0, lanes - 3 * H)))

    def per_slot(*block):
        return pl.BlockSpec((None,) + block,
                            lambda i, l, o, n: (walked_slot(i, o, n),)
                            + (0,) * len(block))

    state_spec = pl.BlockSpec(
        (None, None, H, dk, dv),
        lambda i, l, o, n: (l[0], walked_slot(i, o, n), 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[per_slot(dk, lanes)] + [per_slot(H, dv)] * 2 + [state_spec],
        out_specs=[state_spec, per_slot(H, dv)],
    )
    return pl.pallas_call(
        _update_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), jnp.float32)],
        # operands count the scalar prefetches: the state is the 7th
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kda_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n_live, akq, v, b,
      state)


def kda_state_update_reference(state, layer, active, a, k, q, v, b):
    """The XLA form: every slot's update, an idle slot keeping its state.
    Elementwise in float32 (no dot: a TPU's default matmul precision would
    round the state to bfloat16)."""
    s0 = state[layer]
    s = a[..., None] * s0
    u = b * (v - jnp.sum(k[..., None] * s, axis=-2))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.sum(q[..., None] * s, axis=-2)
    live = active[:, None, None, None]
    return (state.at[layer].set(jnp.where(live, s, s0)),
            jnp.where(active[:, None, None], o, 0.0))


def kernel_update(state, use_pallas: Optional[bool] = None) -> bool:
    """Whether `kda_state_update` over `state` is the Pallas kernel: on a
    TPU (or `use_pallas`), and key and value sizes the kernel's blocks can
    tile (else the log says so, once). The engine counts
    `ssm_steps_kernel` by the same function."""
    if not ((jax.default_backend() == "tpu") if use_pallas is None
            else use_pallas):
        return False
    _, _, _, dk, dv = state.shape
    if dk % 128 or dv % 128:
        log_kernel_declined(
            "kda_state_update", "the update of every slot in XLA",
            f"key {dk} and value {dv} sizes must be multiples of 128")
        return False
    return True


def kda_state_update(state, layer, active, g, beta, q, k, v,
                     use_pallas: Optional[bool] = None, live=None):
    """One token a slot through KDA layer `layer` of the pool.

    state [L, slots, H, d, d] float32 (donate it: updated in place);
    active [B] bool or None (all live); g [B, H, d] the log decay a key
    channel, beta [B, H] the step, q, k, v [B, H, d], float32; `live`:
    `live_slots(active)` where the caller has taken it already (once a
    step, outside the layer walk: every layer walks the same slots).
    Returns (state, o [B, H, d] float32 with o = S_new^T q, zeros for an
    idle slot)."""
    B = q.shape[0]
    f32 = jnp.float32
    if active is None:
        active = jnp.ones((B,), bool)
    a = jnp.exp(g.astype(f32))
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    b = jnp.broadcast_to(beta.astype(f32)[..., None], v.shape)
    if not kernel_update(state, use_pallas):
        return kda_state_update_reference(state, layer, active, a, k, q, v, b)
    order, n_live = live if live is not None else live_slots(active)
    state, o = kda_state_update_pallas(state, layer, order, n_live, a, k, q,
                                       v, b)
    return state, jnp.where(active[:, None, None], o, 0.0)
