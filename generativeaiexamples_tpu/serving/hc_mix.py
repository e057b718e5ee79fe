"""The mixing of several residual streams around a branch
(models/hyper_connections.py's `hc_pre` / `hc_post`) as two kernels.

A decode step of a four-stream model mixes 2 x layers times; left to XLA
each mix is a reduction, a matmul 24 wide, 40 dependent normalisations of
16 numbers a token and two weighted sums: a dozen small programs, each
bound by its start and not by its bytes. Here a mix is two calls:

`hc_pre`: a block of tokens' streams x [TB, n * C] comes into VMEM once.
The sum of squares (float32) and the projection phi x^T [K, TB] (K = n +
n + n^2, accumulated in float32) give the raw coefficients with the
TOKENS ON THE LANES: a coefficient is one sublane row, H~_res four [n, TB]
pieces, and a Sinkhorn pass is a sublane sum, a sum of four pieces and
eight divisions over whole lanes, never 16 numbers in a 128-lane row. The
finished coefficients go through one [128, 128] transposition to the
tokens-on-sublanes form the weighted sums want (a coefficient a column,
broadcast along the lanes) and out as `coef [T, 128]` float32 (columns
0..n H_pre, n..2n H_post, 2n.. H_res row-major); u = sum_i H_pre,i x_i is
taken from the block while it is still in VMEM.

`hc_post`: x, y and `coef` in, x' = H_res x + H_post y out, IN PLACE of x.

Off the chip, and for a stream no block divides, the `jax.numpy` form of
models/hyper_connections.py runs, which tests hold the kernels to.
"""

from __future__ import annotations

import functools
import types
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.models import hyper_connections as hc

LANES = 128           # coefficients' columns; the most tokens a block
SUB = 16              # tokens a weighted sum holds coefficients for
_VMEM_LIMIT_BYTES = 64 << 20
_F32 = jnp.float32


def block_tokens(T: int) -> int:
    """Tokens a grid step: the most that still leaves two blocks, so that
    a block's copies run under its neighbour's arithmetic (a decode
    step's 128 tokens in two blocks of 64: 28.4 us a mix against 33.6 in
    four of 32 and 33.8 in one of 128; a prefill group's 1,024 in eight
    of 128: 175 us against 189 in blocks of 64; my chip run, PR 57)."""
    for tb in (128, 64, 32):
        if T % tb == 0 and T // tb >= 2:
            return tb
    return SUB


def _chunk(C: int) -> int:
    """The lanes a weighted sum handles at a time."""
    return LANES if C % LANES == 0 else C


def _lanes(c, width):
    """Chunk `c` (traced) of `width` lanes: whole tiles."""
    return pl.ds(pl.multiple_of(c * width, width), width)


def _square_transposed(a):
    """a [rows <= 128, cols <= 128] float32 -> a^T, through one
    [128, 128] transposition (the form the chip's transposer takes)."""
    rows, cols = a.shape
    a = jnp.pad(a, ((0, LANES - rows), (0, LANES - cols)))
    return a.T[:cols, :rows]


def _pre_kernel(layer_ref, b_ref, alpha_ref, x_ref, phi_ref, u_ref, coef_ref,
                h_ref, *, n, C, eps, iters, hc_eps, clamp):
    TB = x_ref.shape[0]
    K = 2 * n + n * n
    l = layer_ref[0]
    W = _chunk(C)
    # -- hc.coeffs: the norm's scalar and the projection, tokens on lanes
    acc = jax.lax.fori_loop(
        0, n * C // W,
        lambda c, acc: acc + jnp.square(x_ref[:, _lanes(c, W)].astype(_F32)),
        jnp.zeros((TB, W), _F32))
    ms = jnp.sum(acc, axis=-1, keepdims=True) / (n * C)         # [TB, 1]
    r = jax.lax.rsqrt(ms + eps)
    r = _square_transposed(jnp.broadcast_to(r, (TB, LANES)))[0:1]  # [1, TB]
    proj = jax.lax.dot_general(
        phi_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=_F32)                            # [K, TB]
    row = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
    gain = jnp.where(row < n, alpha_ref[l, 0],
                     jnp.where(row < 2 * n, alpha_ref[l, 1],
                               alpha_ref[l, 2]))
    bias = jax.lax.fori_loop(
        0, K, lambda k, bias: jnp.where(row == k, b_ref[l, k], bias),
        jnp.zeros((K, 1), _F32))
    h_ref[0:K, :] = proj * r * gain + bias
    gate = jax.nn.sigmoid(h_ref[0:2 * n, :])                    # pre ; post
    half = jax.lax.broadcasted_iota(jnp.int32, (2 * n, 1), 0) < n
    gate = jnp.where(half, gate, 2.0 * gate)
    # -- hc.sinkhorn: m[i] is row i of H_res over the lanes' tokens [n, TB]
    m = tuple(jnp.exp(jnp.clip(h_ref[2 * n + n * i:2 * n + n * (i + 1), :],
                               clamp[0], clamp[1])) for i in range(n))

    def one_pass(_, m):
        m = [a / (jnp.sum(a, axis=0, keepdims=True) + hc_eps) for a in m]
        col = functools.reduce(jnp.add, m) + hc_eps
        return tuple(a / col for a in m)

    m = jax.lax.fori_loop(0, iters, one_pass, m)
    h_ref[0:2 * n, :] = gate
    for i in range(n):
        h_ref[2 * n + n * i:2 * n + n * (i + 1), :] = m[i]
    h_ref[K:, :] = jnp.zeros((LANES - K, TB), _F32)
    coef_ref[...] = _square_transposed(h_ref[...])              # [TB, 128]
    # -- hc.pre: u = sum_i H_pre,i x_i, SUB tokens' coefficients at a time
    sub = min(SUB, TB)

    def rows_of(t, _):
        rows = pl.ds(pl.multiple_of(t * sub, sub), sub)
        cf = [jnp.broadcast_to(coef_ref[rows, i:i + 1], (sub, W))
              for i in range(n)]

        def chunk(c, _):
            u = cf[0] * x_ref[rows, _lanes(c, W)].astype(_F32)
            for i in range(1, n):
                u = u + cf[i] * x_ref[
                    rows, _lanes(i * (C // W) + c, W)].astype(_F32)
            u_ref[rows, _lanes(c, W)] = u.astype(u_ref.dtype)
            return 0

        return jax.lax.fori_loop(0, C // W, chunk, 0)

    jax.lax.fori_loop(0, TB // sub, rows_of, 0)


def _post_kernel(x_ref, y_ref, coef_ref, o_ref, *, n, C):
    TB = x_ref.shape[0]
    W = _chunk(C)
    sub = min(SUB, TB)

    def rows_of(t, _):
        rows = pl.ds(pl.multiple_of(t * sub, sub), sub)
        cf = coef_ref[rows, :]
        post = [jnp.broadcast_to(cf[:, n + i:n + i + 1], (sub, W))
                for i in range(n)]
        res = [[jnp.broadcast_to(
            cf[:, 2 * n + n * i + j:2 * n + n * i + j + 1], (sub, W))
            for j in range(n)] for i in range(n)]

        def chunk(c, _):
            y = y_ref[rows, _lanes(c, W)].astype(_F32)
            xs = [x_ref[rows, _lanes(j * (C // W) + c, W)].astype(_F32)
                  for j in range(n)]
            for i in range(n):
                out = post[i] * y
                for j in range(n):
                    out = out + res[i][j] * xs[j]
                o_ref[rows, _lanes(i * (C // W) + c, W)] = \
                    out.astype(o_ref.dtype)
            return 0

        return jax.lax.fori_loop(0, C // W, chunk, 0)

    jax.lax.fori_loop(0, TB // sub, rows_of, 0)


def _padded(a, T_pad):
    return a if a.shape[0] == T_pad else jnp.pad(
        a, ((0, T_pad - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


@functools.partial(jax.jit, static_argnames=("cfg", "interpret",
                                             "tokens_a_block"))
def hc_pre_pallas(cfg, x, phi, b, alpha, layer, *, interpret: bool = False,
                  tokens_a_block: Optional[int] = None):
    """x [T, n * C]; phi [L, K, n * C], b [L, K], alpha [L, 3] the stacked
    leaves and `layer` the block's index in them (the kernel reads the
    block's slice where it lies). -> (u [T, C], coef [T, 128] float32)."""
    n, C = hc.streams(cfg), cfg.dim
    T = x.shape[0]
    K = 2 * n + n * n
    tb = tokens_a_block or block_tokens(T)
    T_pad = -(-T // tb) * tb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T_pad // tb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((tb, n * C), lambda t, l: (t, 0)),
                  pl.BlockSpec((None, K, n * C), lambda t, l: (l[0], 0, 0))],
        out_specs=[pl.BlockSpec((tb, C), lambda t, l: (t, 0)),
                   pl.BlockSpec((tb, LANES), lambda t, l: (t, 0))],
        scratch_shapes=[pltpu.VMEM((LANES, tb), _F32)])
    u, coef = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, C=C, eps=cfg.rms_eps,
                          iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
                          clamp=cfg.hc_res_clamp),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T_pad, C), x.dtype),
                   jax.ShapeDtypeStruct((T_pad, LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="hc_pre",
    )(jnp.asarray(layer, jnp.int32).reshape(1), b, alpha, _padded(x, T_pad),
      phi.astype(x.dtype))
    return u[:T], coef[:T]


@functools.partial(jax.jit, static_argnames=("cfg", "interpret",
                                             "tokens_a_block"))
def hc_post_pallas(cfg, x, y, coef, *, interpret: bool = False,
                   tokens_a_block: Optional[int] = None):
    """x [T, n * C], y [T, C], coef [T, 128] (`hc_pre_pallas`'s) -> x'
    [T, n * C], written over x (donated where the caller's x is dead)."""
    n, C = hc.streams(cfg), cfg.dim
    T = x.shape[0]
    tb = tokens_a_block or block_tokens(T)
    T_pad = -(-T // tb) * tb

    def rows(width):
        return pl.BlockSpec((tb, width), lambda t: (t, 0))

    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n, C=C),
        grid=(T_pad // tb,),
        in_specs=[rows(n * C), rows(C), rows(LANES)],
        out_specs=rows(n * C),
        out_shape=jax.ShapeDtypeStruct((T_pad, n * C), x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="hc_post",
    )(_padded(x, T_pad), _padded(y, T_pad), _padded(coef, T_pad))
    return out[:T]


# -- what models/hyper_connections.py's open / close take as `mix` ---------

def hc_pre(cfg, x, phi, b, alpha, layer=None):
    if layer is None:  # the block's own slices: a stack of one
        phi, b, alpha, layer = phi[None], b[None], alpha[None], 0
    return hc_pre_pallas(cfg, x, phi, b, alpha, layer)


def hc_post(cfg, x, y, coef):
    return hc_post_pallas(cfg, x, y, coef)


KERNELS = types.SimpleNamespace(hc_pre=hc_pre, hc_post=hc_post)


def mixer(use_pallas: Optional[bool] = None):
    """Who mixes the streams: the kernels on a TPU (or `use_pallas`),
    else None, models/hyper_connections.py's own form."""
    on = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    return KERNELS if on else None
