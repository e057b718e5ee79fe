"""The delta-rule mixer's two forms (models/linear_attn_moe.py: chunks
over a prompt, a step a decoded token) against the recurrence a token at a
time, and the in-place state kernel (serving/kda_state_update.py),
interpreted, against its XLA form, at a tiny size on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import linear_attn_moe as lam
from generativeaiexamples_tpu.serving import kda_state_update as upd

CFG = lam.LinearAttnMoeConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return lam.init_params_on_device(CFG, 7, quantize=True)


def _sequential(q, k, v, g, beta, delta=True):
    """The recurrence a token at a time over [B, S, H, d] (with `delta`
    False: gated linear attention, no correction)."""
    B, S, H, d = q.shape
    s = jnp.zeros((B, H, d, d))
    out = []
    for t in range(S):
        s = jnp.exp(g[:, t])[..., None] * s
        r = jnp.sum(k[:, t][..., None] * s, axis=-2) if delta else 0.0
        u = beta[:, t][..., None] * (v[:, t] - r)
        s = s + k[:, t][..., None] * u[..., None, :]
        out.append(jnp.sum(q[:, t][..., None] * s, axis=-2))
    return jnp.stack(out, 1), s


# -- the mixer's two forms ----------------------------------------------------

def _mixer_inputs(B, S, H=4, d=16, seed=0, strong=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, d)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.normal(ks[3], (B, S, H, d))
                 * (1.5 if strong else 0.5) + (1.0 if strong else -3.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("S,lengths", [(24, (24, 13)), (32, (1, 19)),
                                       (5, (5, 2)), (27, (27, 8))])
def test_chunked_form_is_the_step_form(S, lengths):
    """... whatever the bucket's relation to the chunk, and the padding
    past `lengths` leaves the state after the row's LAST REAL token."""
    q, k, v, g, beta = _mixer_inputs(2, S, seed=S)
    lengths = jnp.asarray(lengths, jnp.int32)
    o, state = lam.kda_chunks(CFG, q, k, v, g, beta, lengths)
    for b, n in enumerate(np.asarray(lengths)):
        want, s = _sequential(*(t[b:b + 1, :n] for t in (q, k, v, g, beta)))
        np.testing.assert_allclose(o[b, :n], want[0], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(state[b], s[0], rtol=1e-4, atol=1e-6)
    # one step of the served form continues it
    s1, o1 = upd.kda_state_update(state[None], 0, None, g[:, 0], beta[:, 0],
                                  q[:, 0], k[:, 0], v[:, 0], use_pallas=False)
    s1 = s1[0]
    want, s = _sequential(*(jnp.concatenate(
        [t[:1, :lengths[0]], t[:1, :1]], 1) for t in (q, k, v, g, beta)))
    np.testing.assert_allclose(o1[0], want[0, -1], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(s1[0], s[0], rtol=1e-4, atol=1e-6)


def test_a_strong_decay_neither_overflows_nor_loses_the_sum():
    """exp(G_t - G_j) is formed, never exp(-G_j) alone: with decays down
    to e^-30 a token the chunked form is still the loop."""
    q, k, v, g, beta = _mixer_inputs(1, 32, seed=3, strong=True)
    assert float(g.min()) < -30 and float(jnp.cumsum(g, 1).min()) < -200
    lengths = jnp.asarray([32], jnp.int32)
    cfg = dataclasses.replace(CFG, kda_chunk=16, kda_sub=4)
    o, state = lam.kda_chunks(cfg, q, k, v, g, beta, lengths)
    want, s = _sequential(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state, s, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk,sub", [(4, 4), (8, 2), (16, 4), (32, 16)])
def test_any_chunk_and_sub_block_give_the_same_sums(chunk, sub):
    q, k, v, g, beta = _mixer_inputs(1, 32, seed=9)
    lengths = jnp.asarray([29], jnp.int32)
    want, s = lam.kda_chunks(CFG, q, k, v, g, beta, lengths)
    cfg = dataclasses.replace(CFG, kda_chunk=chunk, kda_sub=sub)
    o, state = lam.kda_chunks(cfg, q, k, v, g, beta, lengths)
    np.testing.assert_allclose(o[:, :29], want[:, :29], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(state, s, rtol=1e-4, atol=1e-6)


def test_the_convolutions_tail_is_the_last_three_real_inputs(params):
    w = lam.take_layer(params["kda"], 0)
    qkv = jax.random.normal(jax.random.key(2), (2, 16, 3 * CFG.d_inner))
    lengths = jnp.asarray([16, 2], jnp.int32)
    out, tail = lam.conv_prompt(CFG, qkv, w, lengths)
    np.testing.assert_array_equal(tail[0], qkv[0, 13:16])
    np.testing.assert_array_equal(tail[1, 1:], qkv[1, :2])
    assert not np.asarray(tail[1, 0]).any()  # before the sequence: zeros
    # and a step from that tail is the prompt form's next position
    nxt = jax.random.normal(jax.random.key(3), (2, 3 * CFG.d_inner))
    step, new_tail = lam.conv_step(CFG, nxt, tail.transpose(1, 0, 2), w)
    longer = jnp.concatenate([qkv[:1], nxt[None, :1]], 1)
    want, _ = lam.conv_prompt(CFG, longer, w, jnp.asarray([17], jnp.int32))
    np.testing.assert_allclose(step[0], want[0, 16], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(new_tail[:, 0], longer[0, 14:17])


# -- the kernel ---------------------------------------------------------------

def interpreted(monkeypatch):
    """Run the Pallas call in the plain interpreter (never
    force_tpu_interpret_mode: tests/test_kv_append_kernel.py says why)."""
    from jax.experimental import pallas as pl
    real = pl.pallas_call
    monkeypatch.setattr(upd.pl, "pallas_call",
                        lambda *a, **kw: real(*a, **{**kw,
                                                     "interpret": True}))


@pytest.mark.parametrize("live", [(1, 1, 1, 1, 1), (0, 1, 0, 1, 1),
                                  (0, 0, 0, 1, 0), (0, 0, 0, 0, 0)])
def test_state_update_kernel_is_its_xla_form_in_place(monkeypatch, live):
    """The interpreted kernel against `kda_state_update_reference`, with
    idle slots among the live ones and with none live: a live slot's
    block is the recurrence's, an idle slot's is bit for bit what it
    was."""
    L, B, H, d = 2, 5, 2, 128
    ks = jax.random.split(jax.random.key(1), 6)
    state = jax.random.normal(ks[0], (L, B, H, d, d))
    g = -jnp.exp(jax.random.normal(ks[1], (B, H, d)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (B, H)))
    q, k, v = (jax.random.normal(ks[i], (B, H, d)) for i in (3, 4, 5))
    active = jnp.asarray(live, bool)
    want_s, want_o = upd.kda_state_update(state, 1, active, g, beta, q, k, v,
                                          use_pallas=False)
    interpreted(monkeypatch)
    got_s, got_o = upd.kda_state_update(state, 1, active, g, beta, q, k, v,
                                        use_pallas=True)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-4)
    idle = ~np.asarray(active)
    np.testing.assert_array_equal(np.asarray(got_s)[1][idle],
                                  np.asarray(state)[1][idle])
    np.testing.assert_array_equal(got_s[0], state[0])  # another layer's
    assert not np.asarray(got_o)[idle].any()


def test_kernel_update_reads_the_backend_and_the_shape():
    wide = jnp.zeros((1, 2, 2, 128, 128))
    assert not upd.kernel_update(wide)            # the CPU: the XLA form
    assert upd.kernel_update(wide, True)
    assert not upd.kernel_update(wide, False)
    assert not upd.kernel_update(jnp.zeros((1, 2, 2, 16, 16)), True)
