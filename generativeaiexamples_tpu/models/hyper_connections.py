"""How the residual stream goes into a branch and comes out of it: the
ONE home of a block's two adds (models/latent_moe.py's walk and
serving/served_latent.py's call `open` before a branch and `close` after
it; a branch takes its input and returns its output).

`hc_mult` 1 (every configuration but one): the stream is `x [.., D]`,
`open` is the identity and `close` is `x + y`.

`hc_mult` n > 1: manifold-constrained hyper-connections (mHC, arXiv:
2512.24880, over Hyper-Connections, arXiv:2409.19606). The stream of a
token is `x in R^{n x C}` (n = hc_mult, C = dim), held as `[.., n, C]`.
Entering: `x_0 = (e, .., e)`, `e` the token's embedding (`enter`).
For each branch `F` (attention with its `ln1`, feed-forward or expert
layer with its `ln2`: the branch keeps its own RMSNorm), with the
branch's own `phi in R^{nC x (n + n + n^2)}`, `b in R^{n + n + n^2}`,
`alpha_pre, alpha_post, alpha_res in R`:

    x~      = RMSNorm(vec(x))                      over all nC values, no learned gain, eps rms_eps
    H~_pre  = alpha_pre  . x~ phi[:, 0:n]      + b[0:n]          in R^n
    H~_post = alpha_post . x~ phi[:, n:2n]     + b[n:2n]         in R^n
    H~_res  = alpha_res  . mat(x~ phi[:, 2n:]) + mat(b[2n:])     in R^{n x n}
    H_pre   = sigmoid(H~_pre)          H_post = 2 sigmoid(H~_post)
    M^0     = exp(clamp(H~_res, hc_res_clamp))
    M^t     = cols(rows(M^{t-1})),  t = 1 .. hc_sinkhorn_iters,
              rows(M)_ij = M_ij / (sum_j M_ij + hc_eps),  cols(M)_ij = M_ij / (sum_i M_ij + hc_eps)
    H_res   = M^iters                              doubly stochastic to the passes' precision
    u       = sum_i H_pre,i . x_i                  in R^C     the branch's input   (`open`)
    y       = F(u)
    x'_i    = sum_j H_res,ij . x_j + H_post,i . y  the stream after the branch     (`close`)

Leaving: `h = sum_i x_i` (`leave`), then `ln_f` and the head. The
coefficients (x~, the projection's accumulation, the sigmoids, the
exponential, every pass) are float32; the stream is the caller's type.

Parameters of a block, stacked like every other leaf, for `branch` in
("attn", "ffn"): `hc_<branch>_phi [L, n + n + n^2, nC]` (phi TRANSPOSED:
a minor dimension of 24 would take 128 lanes of every tile in the
device's memory, and the kernel of serving/hc_mix.py wants the tokens,
not the coefficients, on the lanes), `hc_<branch>_b [L, n + n + n^2]`
and `hc_<branch>_alpha [L, 3]` (pre, post, res), both float32.

`hc_pre` / `hc_post` below are the mixing in `jax.numpy`: the CPU path,
and what the kernels are tested against. `open` / `close` take them, or
whoever the caller names (`mix`: serving/hc_mix.py's `KERNELS`), which
supplies the same two functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BRANCHES = ("attn", "ffn")


def streams(lcfg) -> int:
    """The residual streams of a model configuration of ANY class: a
    class without the field has one."""
    return getattr(lcfg, "hc_mult", 1)


def widths(n: int):
    """(the coefficients a branch projects, the columns of pre, post, res)."""
    return 2 * n + n * n, slice(0, n), slice(n, 2 * n), slice(2 * n, None)


# The seeded biases: b_res = RES_INIT * I, b_pre = b_post = 0, alpha 1,
# phi normal at (nC)^-0.5 so that x~ phi has unit variance (`init_leaves`). The dynamic term then moves every
# coefficient by tens of percent, so that dropping it, two passes where
# twenty are asked, a transposed H_res or a bf16 coefficient shows in the
# logits, while the doubly stochastic H_res keeps the stream's size over
# every branch. (The paper's trained init, alpha 0.01, would hide all of
# those inside a few percent.) A checkpoint's own values replace these.
RES_INIT = 2.0


def init_bias(cfg, L: int):
    n = streams(cfg)
    b = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                         RES_INIT * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
    return jnp.broadcast_to(b, (L, b.shape[0]))


def init_leaves(cfg, L: int, normal):
    """The seeded mixing leaves of `L` stacked blocks; `normal(*shape,
    scale=)` is the caller's draw in cfg.dtype."""
    n = streams(cfg)
    k = widths(n)[0]
    out = {}
    for branch in BRANCHES:
        out[f"hc_{branch}_phi"] = normal(L, k, n * cfg.dim,
                                         scale=(n * cfg.dim) ** -0.5)
        out[f"hc_{branch}_b"] = init_bias(cfg, L)
        out[f"hc_{branch}_alpha"] = jnp.ones((L, 3), jnp.float32)
    return out


# -- the mixing in jax.numpy ----------------------------------------------

def coefficients(cfg, x, phi, b, alpha):
    """x [T, n * C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]),
    float32."""
    n = streams(cfg)
    T = x.shape[0]
    _, pre, post, res = widths(n)
    with jax.named_scope("hc.coeffs"):
        x32 = x.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                          + cfg.rms_eps)
        # the norm is one scalar a token: x~ phi = r . (x phi)
        proj = jax.lax.dot_general(
            x, phi.astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * r
        h_pre = jax.nn.sigmoid(alpha[0] * proj[:, pre] + b[pre])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, post] + b[post])
        h_res = (alpha[2] * proj[:, res] + b[res]).reshape(T, n, n)
    with jax.named_scope("hc.sinkhorn"):
        lo, hi = cfg.hc_res_clamp
        m = jnp.exp(jnp.clip(h_res, lo, hi))
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (jnp.sum(m, axis=2, keepdims=True) + cfg.hc_eps)
            m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps)
    return h_pre, h_post, m


def hc_pre(cfg, x, phi, b, alpha, layer=None):
    """The stream x [T, n * C] before a branch -> (u [T, C] in x's type,
    carry: what `hc_post` needs of the coefficients). `layer`: the
    block's index where phi, b and alpha are the stacked leaves."""
    n = streams(cfg)
    if layer is not None:
        phi, b, alpha = phi[layer], b[layer], alpha[layer]
    T = x.shape[0]
    h_pre, h_post, h_res = coefficients(cfg, x, phi, b, alpha)
    with jax.named_scope("hc.pre"):
        u = jnp.einsum("ti,tic->tc", h_pre,
                       x.reshape(T, n, -1).astype(jnp.float32))
    return u.astype(x.dtype), (h_post, h_res)


def hc_post(cfg, x, y, carry):
    """x [T, n * C], the branch's output y [T, C] -> x' [T, n * C]."""
    n = streams(cfg)
    T = x.shape[0]
    h_post, h_res = carry
    with jax.named_scope("hc.post"):
        out = jnp.einsum("tij,tjc->tic", h_res,
                         x.reshape(T, n, -1).astype(jnp.float32)) \
            + h_post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.reshape(T, -1).astype(x.dtype)


# -- the seam ---------------------------------------------------------------

def enter(cfg, e):
    """The embedding e [.., C] as the stream: itself, or n copies."""
    n = streams(cfg)
    if n == 1:
        return e
    return jnp.broadcast_to(e[..., None, :], e.shape[:-1] + (n, e.shape[-1]))


def leave(cfg, x):
    """The stream as ln_f and the head take it: itself, or the plain sum."""
    if streams(cfg) == 1:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def leaves(cfg):
    """The names of a block's mixing leaves (none for one stream)."""
    if streams(cfg) == 1:
        return ()
    return tuple(f"hc_{branch}_{k}" for branch in BRANCHES
                 for k in ("phi", "b", "alpha"))


def open(cfg, x, w, branch: str, mix=None,  # noqa: A001 (the seam's name)
         layer=None):
    """The stream before a branch -> (the branch's input u [.., C], carry
    for `close`). `w`: the block's leaves; `branch`: "attn" or "ffn";
    `mix`: who supplies `hc_pre` / `hc_post` (None: this module);
    `layer`: the block's index where `w` holds the mixing leaves whole
    (`leaves`: a kernel reads its slice where it lies)."""
    n = streams(cfg)
    if n == 1:
        return x, None
    lead = x.shape[:-2]
    u, carry = (mix.hc_pre if mix else hc_pre)(
        cfg, x.reshape(-1, n * x.shape[-1]), w[f"hc_{branch}_phi"],
        w[f"hc_{branch}_b"], w[f"hc_{branch}_alpha"], layer)
    return u.reshape(lead + (x.shape[-1],)), (mix, carry)


def close(cfg, x, y, carry):
    """The stream after a branch whose output is y [.., C]."""
    if carry is None:
        return x + y
    mix, carry = carry
    n = streams(cfg)
    out = (mix.hc_post if mix else hc_post)(
        cfg, x.reshape(-1, n * x.shape[-1]), y.reshape(-1, y.shape[-1]),
        carry)
    return out.reshape(x.shape)
