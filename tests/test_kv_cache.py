"""The page pools' writes and reads (serving/kv_cache.py) against plain
numpy that fills the same layout by loops: float pool k, v
[rows, KH, pages, page_size, Hd]; int8 pool codes [2, rows, KH, pages,
page_size, Hd] beside scales [2, rows, KH, pages, page_size]. Every
axis has another size, page ids and offsets are not symmetric and the
pools start from random contents, so a transposed scatter index, a
write to another row or a touched neighbour fails the comparison.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.serving.kv_cache import (
    PagePool, QuantPagePool, token_slots)

R, KH, P, PS, HD, B = 3, 2, 6, 4, 8, 3


def _pool(dtype, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == "int8":
        kv = rng.integers(-127, 128, (2, R, KH, P, PS, HD)).astype(np.int8)
        s = rng.random((2, R, KH, P, PS)).astype(np.float32)
        return QuantPagePool(jnp.asarray(kv), jnp.asarray(s), PS)
    k, v = rng.standard_normal((2, R, KH, P, PS, HD)).astype(np.float32)
    return PagePool(jnp.asarray(k), jnp.asarray(v), PS)


def _planes(pool):
    """The pool as numpy: (k, v) of the float pool, or the int8 pool's
    (k codes, v codes, k scales, v scales), each [rows, KH, pages, ...]."""
    if pool.quantized:
        kv, s = np.array(pool.kv), np.array(pool.s)
        return [kv[0], kv[1], s[0], s[1]]
    return [np.array(pool.k), np.array(pool.v)]


def _encode(pool, k, v):
    """What the pool stores for float K and V [..., Hd], plane by plane
    in _planes' order: the values, or codes and one scale a row."""
    if not pool.quantized:
        return [k, v]

    def quantize(x):
        s = np.maximum(np.abs(x).max(-1, keepdims=True) / np.float32(127.0),
                       np.float32(1e-8))
        return np.clip(np.round(x / s), -127, 127).astype(np.int8), s[..., 0]

    (kq, ks), (vq, vs) = quantize(k), quantize(v)
    return [kq, vq, ks, vs]


def _same(pool, want):
    for got, ref in zip(_planes(pool), want):
        np.testing.assert_array_equal(got, ref)


def _append(pool, variant):
    rng = np.random.default_rng(1)
    if variant == "r4":  # four rows a slot, from offset 2: a page boundary
        r = 4
        tables = np.array([[1, 4], [5, 2], [3, 1]])
        start = np.array([2, 3, 1])
        pos = start[:, None] + np.arange(r)[None, :]
        page_idx = np.take_along_axis(tables, pos // PS, axis=1)  # [B, r]
        offset = pos % PS
        assert (page_idx[:, 0] != page_idx[:, -1]).all()
    else:  # one row a slot
        page_idx, offset = np.array([4, 1, 3]), np.array([2, 0, 3])
    k_new, v_new = rng.standard_normal(
        (2, KH) + page_idx.shape + (HD,)).astype(np.float32)
    slots = token_slots(KH, jnp.asarray(page_idx), jnp.asarray(offset))
    row = 2

    def run(pool, row):
        return pool.append(row, slots, jnp.asarray(k_new), jnp.asarray(v_new))

    got = (jax.jit(run)(pool, jnp.int32(row)) if variant == "traced_row"
           else run(pool, row))
    want = _planes(pool)
    for plane, new in zip(want, _encode(pool, k_new, v_new)):
        for kh in range(KH):
            for at in np.ndindex(page_idx.shape):
                plane[row, kh, page_idx[at], offset[at]] = new[(kh,) + at]
    return got, want


def _pages(rng, m):
    return rng.standard_normal((2, R, KH, m, PS, HD)).astype(np.float32)


TABLE = np.array([3, 1, 4])


def _write_pages(pool, _):
    kw, vw = _pages(np.random.default_rng(2), len(TABLE))
    got = pool.write_pages(
        pool.encode_pages(jnp.asarray(kw), jnp.asarray(vw)),
        jnp.asarray(TABLE))
    want = _planes(pool)
    for plane, new in zip(want, _encode(pool, kw, vw)):
        for l in range(R):
            for kh in range(KH):
                for m, page in enumerate(TABLE):
                    plane[l, kh, page] = new[l, kh, m]
    return got, want


def _move_tokens(pool, _):
    src = (np.array([[1, 1, 2], [4, 4, 4], [3, 5, 5]]),
           np.array([[3, 1, 0], [0, 2, 3], [2, 0, 1]]))
    dst = (np.array([[1, 2, 2], [4, 4, 0], [3, 3, 5]]),
           np.array([[2, 0, 1], [1, 3, 0], [3, 0, 2]]))
    got = pool.move_tokens(tuple(map(jnp.asarray, src)),
                           tuple(map(jnp.asarray, dst)))
    before, want = _planes(pool), _planes(pool)
    for old, plane in zip(before, want):  # every read precedes every write
        for l in range(R):
            for kh in range(KH):
                for at in np.ndindex(src[0].shape):
                    plane[l, kh, dst[0][at], dst[1][at]] = \
                        old[l, kh, src[0][at], src[1][at]]
    return got, want


def _page_major(planes):
    """pool_to_pages' layout by loops: codes [n, 2, rows, KH, ps, Hd]
    and, for the int8 pool, scales [n, 2, rows, KH, ps]."""
    out = []
    for pair in (planes[:2], planes[2:]):
        if pair:
            arr = np.zeros((len(TABLE), 2) + pair[0][:, :, 0].shape,
                           pair[0].dtype)
            for n, page in enumerate(TABLE):
                for i, plane in enumerate(pair):
                    for l in range(R):
                        for kh in range(KH):
                            arr[n, i, l, kh] = plane[l, kh, page]
            out.append(arr)
    return out


def _export_pages(pool, _):
    codes, scales = pool.export_pages(jnp.asarray(TABLE))
    got = [np.array(codes)] + ([] if scales is None else [np.array(scales)])
    assert (scales is None) == (not pool.quantized)
    for g, w in zip(got, _page_major(_planes(pool)), strict=True):
        np.testing.assert_array_equal(g, w)
    return pool, _planes(pool)  # and the pool is as it was


def _import_pages(pool, _):
    other = _pool("int8" if pool.quantized else "float32", seed=7)
    arrays = _page_major(_planes(other)) + [None]
    got = pool.import_pages(jnp.asarray(arrays[0]),
                            arrays[1] if arrays[1] is None
                            else jnp.asarray(arrays[1]), jnp.asarray(TABLE))
    want = _planes(pool)
    for plane, new in zip(want, _planes(other)):
        for page in TABLE:
            plane[:, :, page] = new[:, :, page]
    return got, want


def _read_pages(pool, _):
    k, v = pool.read_pages(jnp.asarray(TABLE), jnp.float32)
    planes = _planes(pool)
    for got, i in ((k, 0), (v, 1)):
        want = np.zeros((R, KH, len(TABLE), PS, HD), np.float32)
        for l in range(R):
            for kh in range(KH):
                for n, page in enumerate(TABLE):
                    want[l, kh, n] = planes[i][l, kh, page]
                    if pool.quantized:
                        want[l, kh, n] *= planes[2 + i][l, kh, page][:, None]
        np.testing.assert_array_equal(np.array(got), want)
    return pool, planes


CASES = [(_append, v) for v in ("one_row", "r4", "traced_row")] + [
    (op, "") for op in (_write_pages, _move_tokens, _export_pages,
                        _import_pages, _read_pages)]


@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize(
    "op,variant", CASES,
    ids=[(op.__name__ + "." + v).strip("_.") for op, v in CASES])
def test_a_pool_operation_fills_the_layout_as_a_loop_does(op, variant, dtype):
    pool = _pool(dtype)
    got, want = op(pool, variant)
    assert type(got) is type(pool) and got.page_size == PS
    _same(got, want)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_geometry_names_the_axes_whatever_the_layout(dtype):
    g = _pool(dtype).geometry
    assert g == (R, KH, PS, HD, jnp.dtype(dtype))
