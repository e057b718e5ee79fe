"""A decode step's state update of ONE state-space layer, in place.

For every live decode slot the recurrence of models/hybrid_ssm.py,

    S = a * S + (D_t * x) (x) B        y = S . C

over the per-slot state pool [layers, slots, H, P, N] float32
(kv_cache.StatePool.state): a * S is per head, (x) the outer product of
a head's P values with the N-vector B, and `.` the sum over N. The work
is the state itself, read once and written once (2 x 4 MB a slot and
layer at H, P, N = 128, 64, 128); a copy of the pool would not fit and
XLA's scatter would serialise, so the pool is ALIASED input to output and
each grid step moves one slot's block through VMEM.

Idle slots cost nothing and are never written: the caller's `active`
mask is turned into the list of live slots first (`order`), the grid
walks that list, and the steps past its end stay on the last live slot's
block, which Pallas neither fetches nor writes again (the same trick as
ops/moe.py's unused tiles). With no live slot at all the first step
copies its block through unchanged.

What the kernel takes per slot, laid out so that nothing is relaid in
VMEM: the decay `a` broadcast over N lanes [H, N], `D_t * x` [H, P], and
B and C as rows [1, N]. Off the chip the same function in XLA
(`ssm_state_update_reference`), which tests hold the kernel to.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.utils.platform import log_kernel_declined

# Heads a pass of the kernel's inner loop: [4, 64, 128] float32 is half
# the vector registers, so the product and the sum stay out of VMEM.
HEADS_PER_PASS = 4
_VMEM_LIMIT_BYTES = 48 << 20


def _update_kernel(layer_ref, order_ref, n_ref, a_ref, xdt_ref, b_ref, c_ref,
                   s_in, s_out, y_ref, *, heads_per_pass: int):
    del layer_ref, order_ref  # read by the index maps
    H = s_in.shape[0]
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _():
        b_row = b_ref[...][None]  # [1, 1, N]
        c_row = c_ref[...][None]

        def heads(g, carry):
            hs = pl.ds(pl.multiple_of(g * heads_per_pass, heads_per_pass),
                       heads_per_pass)
            s = a_ref[hs, :][:, None, :] * s_in[hs] \
                + xdt_ref[hs, :][:, :, None] * b_row
            s_out[hs] = s
            y_ref[hs, :] = jnp.sum(s * c_row, axis=-1)
            return carry

        lax.fori_loop(0, H // heads_per_pass, heads, 0)

    @pl.when((n == 0) & (i == 0))
    def _():  # nobody is live: the block goes back as it came
        s_out[...] = s_in[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def live_slots(active):
    """A step's `active` mask [B] as the in-place state kernels walk it:
    (the live slots' indices first, their count [1])."""
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    return order, jnp.sum(active, dtype=jnp.int32).reshape(1)


def walked_slot(i, order, n):
    """Grid step i's slot: past the last live one, stay on it (Pallas
    neither fetches nor writes its block again)."""
    return order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]


def ssm_state_update_pallas(state, layer, order, n_live, a, xdt, Bv, Cv, *,
                            interpret: bool = False):
    """The kernel form. state [L, slots, H, P, N] float32; order [B] the
    live slots first, n_live [1]; a [B, H, N], xdt [B, H, P], Bv, Cv
    [B, N], all float32. Returns (state, y [B, H, P])."""
    _, B, H, P, N = state.shape
    hp = HEADS_PER_PASS if H % HEADS_PER_PASS == 0 else 1

    def per_slot(*block):
        return pl.BlockSpec((None,) + block,
                            lambda i, l, o, n: (walked_slot(i, o, n),)
                            + (0,) * len(block))

    state_spec = pl.BlockSpec(
        (None, None, H, P, N),
        lambda i, l, o, n: (l[0], walked_slot(i, o, n), 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[per_slot(H, N), per_slot(H, P), per_slot(1, N),
                  per_slot(1, N), state_spec],
        out_specs=[state_spec, per_slot(H, P)],
    )
    return pl.pallas_call(
        functools.partial(_update_kernel, heads_per_pass=hp),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
        # operands count the scalar prefetches: the state is the 8th
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n_live, a, xdt,
      Bv[:, None, :], Cv[:, None, :], state)


def ssm_state_update_reference(state, layer, active, a, xdt, Bv, Cv):
    """The XLA form: every slot's product, an idle slot keeping its
    state. Elementwise in float32 (no dot: a TPU's default matmul
    precision would round the state to bfloat16)."""
    s0 = state[layer]
    s = a[:, :, None, None] * s0 + xdt[..., None] * Bv[:, None, None, :]
    live = active[:, None, None, None]
    y = jnp.sum(s * Cv[:, None, None, :], axis=-1)
    return (state.at[layer].set(jnp.where(live, s, s0)),
            jnp.where(active[:, None, None], y, 0.0))


def kernel_update(state, use_pallas: Optional[bool] = None) -> bool:
    """Whether `ssm_state_update` over `state` is the Pallas kernel: on
    a TPU (or `use_pallas`), and a head size and state size the kernel's
    blocks can tile (else the log says so, once). The engine counts
    `ssm_steps_kernel` by the same function."""
    if not ((jax.default_backend() == "tpu") if use_pallas is None
            else use_pallas):
        return False
    _, _, _, P, N = state.shape
    if P % 8 or N % 128:
        log_kernel_declined(
            "ssm_state_update", "the update of every slot in XLA",
            f"head_dim {P} must be a multiple of 8 and state {N} of 128")
        return False
    return True


def ssm_state_update(state, layer, active, step, log_a, x, Bv, Cv,
                     use_pallas: Optional[bool] = None):
    """One token a slot through state-space layer `layer` of the pool.

    state [L, slots, H, P, N] float32 (donate it: updated in place);
    active [B] bool or None (all live); step, log_a [B, H] float32 (D_t
    and D_t * A); x [B, H, P]; Bv, Cv [B, N]. Returns (state, y [B, H,
    P] float32 with y = S_new . C, zeros for an idle slot)."""
    B = x.shape[0]
    f32 = jnp.float32
    if active is None:
        active = jnp.ones((B,), bool)
    a = jnp.exp(log_a.astype(f32))
    xdt = x.astype(f32) * step.astype(f32)[..., None]
    Bv, Cv = Bv.astype(f32), Cv.astype(f32)
    if not kernel_update(state, use_pallas):
        return ssm_state_update_reference(state, layer, active, a, xdt, Bv,
                                          Cv)
    N = state.shape[-1]
    order, n_live = live_slots(active)
    state, y = ssm_state_update_pallas(
        state, layer, order, n_live,
        jnp.broadcast_to(a[..., None], a.shape + (N,)), xdt, Bv, Cv)
    return state, jnp.where(active[:, None, None], y, 0.0)
