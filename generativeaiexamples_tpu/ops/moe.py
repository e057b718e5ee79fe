"""Sparse experts held on this chip: the dispatch plan and the grouped
int8 matmul.

An expert layer routes every token over ALL the model's experts and
computes the part of the result that the experts HELD HERE give (expert
parallelism's share; models/latent_moe.py says which are held). The
token-expert pairs that fall on held experts are laid out expert by
expert in one buffer, each expert's group starting on a row tile, and
`grouped_matmul_int8` multiplies every tile by ITS expert's weight:

- groups are ragged: a group takes the tiles it needs, an empty group
  none, and no group is padded to the longest;
- the buffer has room for the worst case (every pair of every token on a
  held expert), so no token is ever dropped; the tiles past the last
  used one are skipped without a DMA or a matmul;
- the int8 weights are read where they lie in the layer stack
  [L, E, K, N] (the layer is a scalar the block index reads), once a
  (column block, group); their float32 per-column scales are the
  epilogue, as in ops/quant.py::mm.

Off the chip the same function is a loop over the held experts in XLA.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.quant import QuantizedTensor
from generativeaiexamples_tpu.utils.platform import log_kernel_declined

# Rows a tile: a decode step's few pairs an expert (5 of 128 slots)
# want small tiles, a prefill's many (64 an expert) the MXU's height.
TILE_ROWS_DECODE = 32
TILE_ROWS_PREFILL = 128
# A weight block [K, tn] int8 stays under this many bytes (two are in
# flight beside the row tiles and the converted chunk).
_WEIGHT_BLOCK_BYTES = 8 << 20
_VMEM_LIMIT_BYTES = 64 << 20


def tile_rows(n_pairs: int) -> int:
    """Rows of a tile for a buffer of `n_pairs` pairs at most."""
    return TILE_ROWS_DECODE if n_pairs <= 2048 else TILE_ROWS_PREFILL


class DispatchPlan(NamedTuple):
    """Where the pairs of T tokens x k choices go.

    rows        [M] int32: the token a buffer row holds (0 where unused)
    pos         [T, k] int32: the buffer row of a pair (M where the
                pair's expert is not held here)
    tile_group  [M // tm] int32: the held expert a tile belongs to (past
                the last used tile: the last used tile's)
    n_tiles     [1] int32: tiles in use
    counts      [E] int32: pairs each held expert took
    """

    rows: jax.Array
    pos: jax.Array
    tile_group: jax.Array
    n_tiles: jax.Array
    counts: jax.Array
    tm: int


def dispatch_plan(local: jax.Array, n_held: int,
                  tm: Optional[int] = None) -> DispatchPlan:
    """`local` [T, k]: for each token's k-th choice the held expert's
    index in [0, n_held), or `n_held` for an expert that lives
    elsewhere. A counting sort by expert (no sort: a cumulative sum over
    a one-hot [T*k, E]), groups aligned to `tm` rows."""
    T, k = local.shape
    E = n_held
    tm = tm or tile_rows(T * k)
    M = -(-T * k // tm) * tm + E * tm
    flat = local.reshape(-1)
    onehot = (flat[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot        # pairs before, same e
    counts = onehot.sum(axis=0)                        # [E]
    tiles = -(-counts // tm)                           # tiles a group takes
    tile_end = jnp.cumsum(tiles)
    start = (tile_end - tiles) * tm                    # [E] first row
    held = flat < E
    e = jnp.minimum(flat, E - 1)
    pos = jnp.where(held, start[e] + jnp.take_along_axis(
        rank, e[:, None], axis=1)[:, 0], M)
    token = jnp.arange(T * k, dtype=jnp.int32) // k
    rows = jnp.zeros((M,), jnp.int32).at[pos].set(token, mode="drop")
    n_tiles = tile_end[-1:]
    t = jnp.minimum(jnp.arange(M // tm), jnp.maximum(n_tiles - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right"), E - 1).astype(jnp.int32)
    return DispatchPlan(rows, pos.reshape(T, k).astype(jnp.int32), tile_group,
                        n_tiles.astype(jnp.int32), counts, tm)


def _block_cols(K: int, N: int) -> int:
    """The widest column block that divides N in multiples of 128 and
    keeps a [K, tn] int8 block inside the budget."""
    best = 128
    for tn in range(128, N + 1, 128):
        if N % tn == 0 and K * tn <= _WEIGHT_BLOCK_BYTES:
            best = tn
    return best


def _gmm_kernel(layer_ref, group_ref, n_ref, x_ref, w_ref, s_ref, o_ref, *,
                tk: int):
    del layer_ref, group_ref  # read by the index maps
    K = x_ref.shape[1]

    @pl.when(pl.program_id(1) < n_ref[0])
    def _():
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for k0 in range(0, K, tk):  # a chunk at a time: one bf16 copy live
            w = w_ref[k0:k0 + tk, :].astype(jnp.float32).astype(x_ref.dtype)
            acc += jnp.dot(x_ref[:, k0:k0 + tk], w,
                           preferred_element_type=jnp.float32)
        o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def grouped_matmul_pallas(x, w: QuantizedTensor, layer, plan: DispatchPlan,
                          *, interpret: bool = False):
    """The kernel form: x [M, K], w.q [L, E, K, N] int8, w.s [L, E, N]."""
    M, K = x.shape
    N = w.q.shape[-1]
    tm = plan.tm
    tn = _block_cols(K, N)
    tk = 512 if K % 512 == 0 else K
    n_row_tiles = M // tm

    def tile(i, n):  # past the last used tile: stay on it (no new DMA)
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // tn, n_row_tiles),
        in_specs=[
            pl.BlockSpec((tm, K), lambda j, i, l, g, n: (tile(i, n), 0)),
            pl.BlockSpec((None, None, K, tn),
                         lambda j, i, l, g, n: (l[0], g[tile(i, n)], 0, j)),
            pl.BlockSpec((None, None, 1, tn),
                         lambda j, i, l, g, n: (l[0], g[tile(i, n)], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, l, g, n: (tile(i, n), j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tk=tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_grouped_matmul_int8",
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan.tile_group,
      plan.n_tiles, x, w.q, w.s[:, :, None, :])


def grouped_matmul_reference(x, w: QuantizedTensor, layer, plan: DispatchPlan):
    """The XLA form: every held expert's matmul over the whole buffer,
    a row keeping its own expert's. Rows of unused tiles give zeros."""
    M, _ = x.shape
    quantized = isinstance(w, QuantizedTensor)
    q = w.q[layer] if quantized else w[layer]
    E, tm = q.shape[0], plan.tm
    used = jnp.arange(M // tm) < plan.n_tiles[0]
    group = jnp.repeat(jnp.where(used, plan.tile_group, E), tm)  # [M]
    y = jnp.zeros((M, q.shape[-1]), jnp.float32)
    for e in range(E):
        ye = jnp.dot(x, q[e].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        if quantized:
            ye = ye * w.s[layer][e]
        y = jnp.where((group == e)[:, None], ye, y)
    return y.astype(x.dtype)


def grouped_matmul_int8(x, w: QuantizedTensor, layer, plan: DispatchPlan,
                        use_pallas: Optional[bool] = None):
    """x [M, K] (rows laid out by `plan`) times the held experts' int8
    weights of layer `layer` (a Python int or a traced scalar): row r
    by expert plan.tile_group[r // tm]. Returns [M, N] in x's type;
    rows of unused tiles are undefined (the combine never reads them)."""
    use_pallas = (jax.default_backend() == "tpu") if use_pallas is None \
        else use_pallas
    if not isinstance(w, QuantizedTensor):  # float weights: tests
        return grouped_matmul_reference(x, w, layer, plan)
    K, N = w.q.shape[-2:]
    if use_pallas and (K % 128 or N % 128):
        log_kernel_declined(
            "moe_grouped_matmul_int8", "a loop over the held experts in XLA",
            f"K {K} and N {N} must both be multiples of 128")
        use_pallas = False
    if use_pallas:
        return grouped_matmul_pallas(x, w, layer, plan)
    return grouped_matmul_reference(x, w, layer, plan)
