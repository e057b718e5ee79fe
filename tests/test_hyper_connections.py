"""Several residual streams mixed around every branch
(models/hyper_connections.py, serving/hc_mix.py, the seam in
models/latent_moe.py and serving/served_latent.py) at tiny sizes on the
CPU: the equations against a NumPy transcription in float64, the passes'
count, the clamp, the one-stream programs against the parent's, a
four-stream model through `LLMEngine`, the interpreted kernels, the
loader's refusal, the counter and the flight event."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import axk1, xing4
from benchmark.tests.test_axk1 import tiny_file as axk1_file
from benchmark.tests.test_xing4 import tiny_file
from generativeaiexamples_tpu.models import hyper_connections as hc
from generativeaiexamples_tpu.models import latent_moe as lm
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model as em
from generativeaiexamples_tpu.serving import flight, hc_mix, memory_plan
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import PagePool

PS = 8


def config_file(**over):
    c = tiny_file()
    c["serving"].update(kv_dtype="float32", n_pages=48)
    c["serving"]["engine"].update(max_seq_len=64, page_size=PS,
                                  prefill_buckets=[16, 32])
    c.update(over)
    return c


FILE = config_file()
CFG = xing4.model_config(FILE)


@pytest.fixture(scope="module")
def params():
    return lm.init_params_on_device(CFG, 57, quantize=True, depth_gain=True)


def _cfg(n, **kw):
    return dataclasses.replace(CFG, hc_mult=n, **kw)


def _leaves(cfg, seed, gain=1.0):
    n, k = cfg.hc_mult, hc.widths(cfg.hc_mult)[0]
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    phi = jax.random.normal(key[0], (k, n * cfg.dim)) * (n * cfg.dim) ** -0.5
    b = hc.init_bias(cfg, 1)[0] + 0.3 * jax.random.normal(key[1], (k,))
    alpha = jnp.asarray([1.0, 0.7, 1.3]) * gain
    return phi, b, alpha


# -- the equations, in float64 NumPy ----------------------------------------

def numpy_mix(cfg, x, y, phi, b, alpha, iters=None):
    """The docstring's equations, transcribed: x [T, n, C], y [T, C] ->
    (u [T, C], x' [T, n, C], H_res [T, n, n])."""
    n = cfg.hc_mult
    iters = cfg.hc_sinkhorn_iters if iters is None else iters
    x, y, phi = (np.asarray(a, np.float64) for a in (x, y, phi))
    b, alpha = np.asarray(b, np.float64), np.asarray(alpha, np.float64)
    T = x.shape[0]
    flat = x.reshape(T, -1)
    xt = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + cfg.rms_eps)
    raw = xt @ phi.T
    h_pre = 1 / (1 + np.exp(-(alpha[0] * raw[:, :n] + b[:n])))
    h_post = 2 / (1 + np.exp(-(alpha[1] * raw[:, n:2 * n] + b[n:2 * n])))
    m = np.exp(np.clip((alpha[2] * raw[:, 2 * n:] + b[2 * n:])
                       .reshape(T, n, n), *cfg.hc_res_clamp))
    for _ in range(iters):
        m = m / (m.sum(2, keepdims=True) + cfg.hc_eps)
        m = m / (m.sum(1, keepdims=True) + cfg.hc_eps)
    u = np.einsum("ti,tic->tc", h_pre, x)
    out = np.einsum("tij,tjc->tic", m, x) + h_post[:, :, None] * y[:, None]
    return u, out, m


@pytest.mark.parametrize("n", [2, 4])
def test_the_mixing_is_the_equations_in_float64(n):
    cfg = _cfg(n)
    phi, b, alpha = _leaves(cfg, n)
    x = jax.random.normal(jax.random.PRNGKey(1), (9, n, cfg.dim))
    y = jax.random.normal(jax.random.PRNGKey(2), (9, cfg.dim))
    w = {"hc_attn_phi": phi, "hc_attn_b": b, "hc_attn_alpha": alpha}
    u, carry = hc.open(cfg, x, w, "attn")
    out = hc.close(cfg, x, y, carry)
    assert u.shape == (9, cfg.dim) and out.shape == x.shape
    u64, out64, _ = numpy_mix(cfg, x, y, phi, b, alpha)
    np.testing.assert_allclose(u, u64, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, out64, rtol=2e-5, atol=2e-5)
    # in as copies, out as the plain sum
    e = jax.random.normal(jax.random.PRNGKey(3), (2, 5, cfg.dim))
    streams = hc.enter(cfg, e)
    assert streams.shape == (2, 5, n, cfg.dim)
    np.testing.assert_allclose(hc.leave(cfg, streams), n * e, rtol=1e-6)


def test_twenty_passes_are_doubly_stochastic_and_two_are_not():
    """Under the seeded leaves (gains of one, b_res = 2 I): the columns
    sum to one exactly (the last normalisation is theirs), the rows to
    1e-4 for the median token after 20 passes and nowhere near after 2,
    so the passes' count is held. (The slowest token in a hundred is
    still a percent off after 20: the passes' precision, not the
    manifold's.)"""
    cfg = _cfg(4)
    phi, _, _ = _leaves(cfg, 5)
    b, alpha = hc.init_bias(cfg, 1)[0], jnp.ones((3,))
    x = jax.random.normal(jax.random.PRNGKey(4), (256, 4 * cfg.dim))

    def rows_off(iters):
        m = hc.coefficients(dataclasses.replace(
            cfg, hc_sinkhorn_iters=iters), x, phi, b, alpha)[2]
        assert float(jnp.abs(m.sum(1) - 1).max()) < 1e-5
        return np.abs(np.asarray(m.sum(2)) - 1).max(-1)

    twenty, two = rows_off(20), rows_off(2)
    assert np.median(twenty) < 1e-4 and twenty.max() < 5e-2
    assert np.median(two) > 1e-2
    _, _, m = hc.coefficients(cfg, x, phi, b, alpha)
    _, _, want = numpy_mix(cfg, x.reshape(256, 4, -1),
                           jnp.zeros((256, cfg.dim)), phi, b, alpha)
    np.testing.assert_allclose(m, want, rtol=1e-4, atol=1e-6)


def test_the_clamp_keeps_a_wild_h_res_finite():
    cfg = _cfg(4)
    phi, b, alpha = _leaves(cfg, 6)
    wild = b.at[8:].set(jnp.asarray([1e4, -1e4] * 8))
    x = jax.random.normal(jax.random.PRNGKey(5), (7, 4 * cfg.dim))
    h_pre, h_post, m = hc.coefficients(cfg, x, phi, wild, alpha)
    assert bool(jnp.isfinite(m).all()) and float(m.max()) <= 1.0 + 1e-6
    free = dataclasses.replace(cfg, hc_res_clamp=(-1e9, 1e9))
    assert not bool(jnp.isfinite(
        hc.coefficients(free, x, phi, wild, alpha)[2]).all())


# -- one stream: the parent's programs ---------------------------------------
# sha256 (first 16 hex digits) of the lowered StableHLO of a tiny
# A.X-K1-shaped model's decode block (2 steps) and prefill group, taken
# on PR 57's PARENT (ecb6f1b) with these arguments: `open` is the identity
# and `close` the parent's add.
PARENT_TINY_LATENT = {"decode_multi_step": "e653190f69e9ff5b",
                      "prefill_batch_step": "eaf9ce5114a77c01"}


def test_one_stream_lowers_to_the_parents_text_and_is_its_forward():
    cfg = axk1.model_config(axk1_file())
    assert cfg.hc_mult == 1 and hc.leaves(cfg) == ()
    p = lm.init_params_on_device(cfg, 7, quantize=True)
    assert not any(k.startswith("hc_") or k == "router_bias"
                   for k in p["layers"])
    B, maxp = 4, 4
    pool = PagePool.zeros(cfg, 9, 8, dtype=jnp.float32)

    def i32(*s):
        return jnp.zeros(s, jnp.int32)

    def f32(*s):
        return jnp.zeros(s, jnp.float32)

    key, greedy = jax.random.PRNGKey(1), (True, False, False)
    lowered = {
        "decode_multi_step": em.decode_multi_step.lower(
            p, cfg, pool, i32(B), i32(B, maxp), i32(B) + 1,
            jnp.ones((B,), bool), f32(B), f32(B), i32(B), key, 2, False,
            sampling_flags=greedy),
        "prefill_batch_step": em.prefill_batch_step.lower(
            p, cfg, pool, i32(2, 16), i32(2) + 1, i32(2, 2), f32(2), f32(2),
            i32(2), key, False, sampling_flags=greedy)}
    got = {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
           for k, v in lowered.items()}
    assert got == PARENT_TINY_LATENT, json.dumps(got)

    # ... and bit for bit the parent's walk, written out with its adds (one
    # operation at a time on both sides, so that the order of sums is one)
    with jax.disable_jit():
        tokens = jnp.arange(2 * 16).reshape(2, 16) % 256
        S = tokens.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S)[None], tokens.shape)
        lengths = jnp.full((2,), S, jnp.int32)
        x = p["tok_emb"][tokens].astype(cfg.residual_dtype)
        _, experts = lm.split_experts(p["layers"])
        for l in range(cfg.n_layers):
            d = l < cfg.n_dense_layers
            w = lm.take_layer(p["dense" if d else "layers"],
                              l if d else l - cfg.n_dense_layers,
                              skip=() if d else lm.EXPERT_WEIGHTS)
            h = llama.rms_norm(x, w["ln1"], cfg.rms_eps).astype(cfg.dtype)
            q_nope, q_rope, row = lm.project_latent(cfg, h, w, positions)
            out = lm.attend_prompt(cfg, q_nope, q_rope, row, w, lengths, False)
            x = llama.attn_out(cfg, x, out, w)
            y, _, _ = lm.feed_forward(cfg, x, w, None if d else experts,
                                      None if d else l - cfg.n_dense_layers,
                                      False)
            x = x + y
        want = lm.logits_of(cfg, p, x)
        got, _ = lm.forward(p, cfg, tokens, use_pallas=False)
    assert bool((got == want).all())


# -- four streams through the engine ------------------------------------------

def _engine(params, **over):
    from benchmark.harness import system
    from benchmark.harness.bench_tokenizer import WordTokenizer

    ecfg = dataclasses.replace(system.engine_config(FILE), **over)
    return LLMEngine(params, CFG, WordTokenizer(512), ecfg, n_pages=48)


def test_prefill_then_paged_decode_is_the_forward_over_the_sequence(params):
    """A four-stream model: a prefill group, then every later token
    through `decode_step` and the latent pool, against
    `latent_moe.forward` over the whole sequence; float32, logits to 1e-4
    of the largest."""
    n_prompt, n_new = 11, 7
    ids = np.random.default_rng(3).integers(1, 512, n_prompt + n_new) \
        .astype(np.int32)
    want, _ = lm.forward(params, CFG, jnp.asarray(ids)[None],
                         use_pallas=False)
    want = np.asarray(want[0])
    pool = PagePool.zeros(CFG, 24, PS, dtype=jnp.float32)
    toks = np.zeros((2, 16), np.int32)
    toks[0, :n_prompt] = ids[:n_prompt]
    rows = np.zeros((2, 2), np.int32)
    rows[0] = [1, 2]
    table = np.zeros((4, 8), np.int32)
    table[0] = 1 + np.arange(8)
    logits, pool = em.prefill_step(
        params, CFG, pool, jnp.asarray(toks[:1]), jnp.int32(n_prompt),
        jnp.asarray(rows[0]), False)
    got = [np.asarray(logits)]
    for i in range(n_prompt, len(ids)):
        cur, ln = np.zeros((4,), np.int32), np.ones((4,), np.int32)
        cur[0], ln[0] = ids[i], i + 1
        logits, pool = em.decode_step(params, CFG, pool, jnp.asarray(cur),
                                      jnp.asarray(table), jnp.asarray(ln),
                                      False)
        got.append(np.asarray(logits[0]))
    top = float(np.abs(want).max())
    assert np.abs(np.stack(got) - want[n_prompt - 1:]).max() / top < 1e-4
    # and the plain reference of the benchmark agrees with both
    ref = np.asarray(xing4.reference_logits(FILE, params, ids))
    assert np.abs(ref - want).max() / top < 2e-3


def test_the_engine_serves_the_forwards_tokens_and_counts_the_mixes(params):
    eng = _engine(params)
    assert eng.metrics.snapshot()["hc_streams"] == 4
    eng.start()
    try:
        ids = [int(t) for t in
               np.random.default_rng(9).integers(1, 512, 13)]
        served = [ev["token_id"] for ev in eng.generate_stream(
            ids, max_new_tokens=5, temperature=0.0)]
    finally:
        eng.stop()
    seq = list(ids)
    for _ in range(5):
        logits, _ = lm.forward(params, CFG, jnp.asarray([seq], jnp.int32),
                               use_pallas=False)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert served == seq[len(ids):]
    snap = eng.metrics.snapshot()
    # one prefill of 13 tokens, then decode blocks of 2 steps with one
    # live slot: 2 branches x 4 layers a token
    blocks = [e for e in eng.flight.snapshot_events()
              if e["kind"] == flight.EV_RESIDUAL_MIX]
    assert len(blocks) >= 2
    assert snap["hc_mixes"] == 13 * 8 + snap["decode_steps"] * 8
    assert all(e["a"] == 8.0 and e["b"] == 4 * 64 * 4 for e in blocks)
    assert flight.EVENT_NAMES[flight.EV_RESIDUAL_MIX] == "residual_mix"
    assert flight.EV_RESIDUAL_MIX == 26
    from generativeaiexamples_tpu.serving import fleet
    assert "hc_mixes" in fleet.counter_keys()


def test_a_one_stream_engine_reports_the_streams_as_zero():
    cfg = axk1.model_config(axk1_file())
    from benchmark.harness import system
    from benchmark.harness.bench_tokenizer import WordTokenizer
    eng = LLMEngine(lm.init_params_on_device(cfg, 7, quantize=True), cfg,
                    WordTokenizer(512), system.engine_config(axk1_file()),
                    n_pages=64)
    snap = eng.metrics.snapshot()
    assert (snap["hc_streams"], snap["hc_mixes"]) == (0, 0)


def test_the_memory_plan_counts_the_streams_and_the_mixing_leaves(params):
    from generativeaiexamples_tpu.config.schema import EngineConfig
    ecfg = dataclasses.replace(EngineConfig(), page_size=PS,
                               kv_dtype="float32", max_seq_len=64,
                               max_batch_size=4, prefill_buckets=(16,))
    weights = memory_plan.weight_bytes_per_device(CFG, {}, quantize=True)
    assert weights == sum(x.nbytes for x in jax.tree.leaves(
        lm.init_params_on_device(CFG, 0, quantize=True)))
    four, one = ({l.name: l for l in memory_plan._scratch_lines(c, ecfg, {})}
                 for c in (CFG, dataclasses.replace(CFG, hc_mult=1)))
    line = four["activation_transients"]
    assert line.bytes_per_device > one["activation_transients"] \
        .bytes_per_device
    assert "4 residual streams wide" in line.note
    assert "residual" not in one["activation_transients"].note


# -- the kernels, interpreted -------------------------------------------------

@pytest.mark.parametrize("tokens", [1, 7, 128, 1024])
def test_the_interpreted_kernels_are_the_jnp_form(tokens):
    """`hc_pre` / `hc_post` against models/hyper_connections.py's form,
    with idle slots among the tokens (rows of zeros, as an idle slot's
    embedding of token 0 may be: they must stay finite and leave their
    neighbours alone)."""
    cfg = _cfg(4)
    L = 3
    key = jax.random.split(jax.random.PRNGKey(tokens), 4)
    phi = jax.random.normal(key[0], (L, 24, 4 * cfg.dim)) \
        * (4 * cfg.dim) ** -0.5
    b = hc.init_bias(cfg, L) + 0.3 * jax.random.normal(key[1], (L, 24))
    alpha = jnp.ones((L, 3)) * jnp.asarray([[1.0], [0.5], [2.0]])
    x = jax.random.normal(key[2], (tokens, 4 * cfg.dim))
    idle = np.arange(tokens) % 3 == 1
    x = jnp.where(idle[:, None], 0.0, x)
    y = jax.random.normal(key[3], (tokens, cfg.dim))
    u0, carry = hc.hc_pre(cfg, x, phi, b, alpha, 2)
    want = hc.hc_post(cfg, x, y, carry)
    u1, coef = hc_mix.hc_pre_pallas(cfg, x, phi, b, alpha, 2, interpret=True)
    got = hc_mix.hc_post_pallas(cfg, x, y, coef, interpret=True)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(u1, u0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(coef[:, 4:8], carry[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(coef[:, 8:24].reshape(tokens, 4, 4), carry[1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # layer 1's leaves give another answer: the index is read
    u2, _ = hc_mix.hc_pre_pallas(cfg, x, phi, b, alpha, 1, interpret=True)
    assert float(jnp.abs(u2 - u1).max()) > 1e-3


def test_the_seam_takes_the_kernels_as_its_mixer(params, monkeypatch):
    """`open` / `close` with serving/hc_mix.py as `mix` (interpreted) are
    the jax.numpy seam: one block's leaves and the whole stack by index."""
    monkeypatch.setattr(hc_mix, "hc_pre_pallas", lambda *a, **kw:
                        _PRE(*a, interpret=True, **kw))
    monkeypatch.setattr(hc_mix, "hc_post_pallas", lambda *a, **kw:
                        _POST(*a, interpret=True, **kw))
    assert hc_mix.mixer(False) is None \
        and hc_mix.mixer(True) is hc_mix.KERNELS
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, 4, CFG.dim))
    y = jax.random.normal(jax.random.PRNGKey(9), (3, 5, CFG.dim))
    stack = params["layers"]
    w = lm.take_layer(stack, 1, skip=lm.EXPERT_WEIGHTS)
    u0, c0 = hc.open(CFG, x, w, "ffn")
    want = hc.close(CFG, x, y, c0)
    whole = lm.take_layer(stack, 1, skip=lm.EXPERT_WEIGHTS + hc.leaves(CFG))
    for leaves, layer in ((w, None), (whole, 1)):
        u1, c1 = hc.open(CFG, x, leaves, "ffn", hc_mix.KERNELS, layer)
        np.testing.assert_allclose(u1, u0, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(hc.close(CFG, x, y, c1), want,
                                   rtol=1e-5, atol=1e-5)


_PRE, _POST = hc_mix.hc_pre_pallas, hc_mix.hc_post_pallas


# -- the loader ----------------------------------------------------------------

def test_hf_loader_refuses_a_snapshot_with_several_streams(tmp_path):
    from generativeaiexamples_tpu.models import hf_loader
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in FILE.items() if k not in ("serving", "published")}))
    with pytest.raises(ValueError, match="'xing4_0' has.*no tensor-name map"):
        hf_loader.load_llama(str(tmp_path))
    with pytest.raises(ValueError,
                       match="4 residual streams.*hyper_connections"):
        hf_loader.llama_config_from_hf(str(tmp_path))
