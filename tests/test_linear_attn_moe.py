"""Delta-rule linear attention beside latent attention
(models/linear_attn_moe.py, serving/kda_state_update.py) at a tiny size on
the CPU, seeded weights, against the benchmark's plain reference
(benchmark/architectures/kimilinear.py, which shares no code with the
program and runs the recurrence token by token): logits, not tokens. The
mixer's two forms and the kernel are tests/test_kda_state_update.py, the
served side (both pools, the engine) tests/test_linear_attn_serving.py."""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import kimilinear as ref
from benchmark.tests.test_kimilinear import tiny_file
from generativeaiexamples_tpu.models import latent_moe
from generativeaiexamples_tpu.models import linear_attn_moe as lam
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops.quant import QuantizedTensor
from test_kda_state_update import _sequential

PS = 8


def config_file(**over):
    """The benchmark tests' tiny configuration file in the source's keys
    (KDA, KDA, MLA, KDA, MLA; a dense layer, then 4 of 16 experts held),
    with pages of 8 and a prompt form in chunks of 8, sub-blocks of 4."""
    c = tiny_file()
    c["serving"].update(n_pages=48)
    c["serving"]["engine"].update(max_seq_len=64, page_size=PS,
                                  prefill_buckets=[16, 32])
    c.update(over)
    return c


FILE = config_file()
CFG = ref.model_config(FILE)
CHUNK = CFG.kda_chunk


@pytest.fixture(scope="module")
def params():
    return lam.init_params_on_device(CFG, 7, quantize=True)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n).astype(np.int32)


def miss(got, want):
    """The largest |difference| of logits over the largest |reference|."""
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


# -- the program's forward against the plain reference ----------------------

# under a chunk, at one, over one, over several and no multiple of it
@pytest.mark.parametrize("n", [5, CHUNK, CHUNK + 3, 3 * CHUNK + 3])
def test_forward_is_the_references_sequential_pass(params, n):
    """float32 activations over int8 weights: what is left is the order of
    float32 sums (a chunked triangular system against a loop over tokens),
    so the logits agree to 1e-4 of the largest and every top-4 set
    agrees; and the states a decode step would continue from are the
    loop's."""
    ids = prompt(n, seed=n)
    want, states, choice = ref.reference_forward(FILE, params, ids)
    x, _, mine_states, _, mine = lam.walk_prompt(
        params, CFG, jnp.asarray(ids)[None], use_pallas=False)
    got = lam.logits_of(CFG, params, x)
    assert miss(got[0], want) < 1e-4
    assert np.array_equal(np.sort(np.asarray(mine)[:, 0], -1),
                          np.sort(np.asarray(choice), -1))
    np.testing.assert_allclose(mine_states[:, 0], states, rtol=1e-3,
                               atol=1e-5)
    # the state REMEMBERS: a decay of 0.9-0.999 leaves the first tokens in it
    a = np.exp(-np.exp(np.asarray(params["kda"]["A_log"]))[:, :, None]
               * np.asarray(jax.nn.softplus(params["kda"]["dt_bias"])
                            ).reshape(3, 4, 16))
    assert 0.88 < a.min() and a.max() < 0.9995


def _forward_under(monkeypatch, params, ids, **patches):
    for name, fn in patches.items():
        monkeypatch.setattr(lam, name, fn)
    got, _ = lam.forward(params, CFG, jnp.asarray(ids)[None],
                         use_pallas=False)
    return got[0]


def test_negative_controls_of_the_forward_all_miss(params, monkeypatch):
    """The comparison can tell. Each wrong program must MISS the reference
    by far more than the right one's 1e-4: a decay that is one scalar a
    head; no delta correction; a rotated latent row; the carried state
    zeroed at a chunk boundary."""
    n = 3 * CHUNK + 3
    ids = prompt(n, seed=n)
    want = ref.reference_logits(FILE, params, ids)
    real_project, real_chunks = lam.kda_project, lam.kda_chunks

    def scalar_decay(cfg, h, w):
        qkv, g, beta, gate = real_project(cfg, h, w)
        return qkv, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), \
            beta, gate

    def no_delta(cfg, q, k, v, g, beta, lengths, state=None):
        return _sequential(q, k, v, g, beta, delta=False)

    def zeroed_at_a_boundary(cfg, q, k, v, g, beta, lengths, state=None):
        cut = 2 * CHUNK
        a, _ = real_chunks(cfg, *(t[:, :cut] for t in (q, k, v, g, beta)),
                           lengths)
        b, s = real_chunks(cfg, *(t[:, cut:] for t in (q, k, v, g, beta)),
                           lengths - cut)
        return jnp.concatenate([a, b], 1), s

    real_latent = latent_moe.project_latent

    def rotated(cfg, h, w, positions):
        turned = copy.copy(cfg)
        for name, value in (("rotary", True), ("rope_theta", 1e4),
                            ("rope_scaling", None)):
            object.__setattr__(turned, name, value)
        S = h.shape[1]
        return real_latent(turned, h, w, jnp.arange(S)[None, :])

    assert miss(_forward_under(monkeypatch, params, ids), want) < 1e-4
    for name, patch in (("scalar_decay", dict(kda_project=scalar_decay)),
                        ("no_delta", dict(kda_chunks=no_delta)),
                        ("zeroed", dict(kda_chunks=zeroed_at_a_boundary))):
        with monkeypatch.context() as m:
            assert miss(_forward_under(m, params, ids, **patch),
                        want) > 0.02, name
    with monkeypatch.context() as m:
        m.setattr(latent_moe, "project_latent", rotated)
        assert miss(_forward_under(m, params, ids), want) > 0.02
    # and the sequential loop WITH the correction is the chunked form
    with monkeypatch.context() as m:
        assert miss(_forward_under(
            m, params, ids, kda_chunks=lambda cfg, q, k, v, g, beta, lengths,
            state=None: _sequential(q, k, v, g, beta)), want) < 1e-4


# -- the router and the share -------------------------------------------------

def test_route_with_a_bias_chooses_by_the_sum_and_weighs_by_the_score():
    h = jax.random.normal(jax.random.key(5), (23, CFG.dim), jnp.float32)
    router = jax.random.normal(jax.random.key(6), (CFG.dim, 16)) * 0.125
    bias = jnp.zeros((16,)).at[3].set(5.0).at[11].set(-5.0)
    idx, wts = latent_moe.route(CFG, h, router, bias)
    s = np.asarray(jax.nn.sigmoid(h @ router))
    chosen = np.argsort(-(s + np.asarray(bias)), -1)[:, :4]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(chosen, -1))
    assert (np.asarray(idx) == 3).any(-1).all()        # always chosen
    assert not (np.asarray(idx) == 11).any()           # never
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        wts, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # the bias moves the CHOICE only: without it, other sets, and the
    # weights of a set are the scores' whatever chose it
    plain, plain_w = latent_moe.route(CFG, h, router)
    assert not np.array_equal(np.sort(plain, -1), np.sort(idx, -1))
    by_score = np.argsort(-s, -1)[:, :4]
    np.testing.assert_array_equal(np.sort(plain, -1), np.sort(by_score, -1))
    np.testing.assert_allclose(np.asarray(plain_w).sum(-1), 2.446, rtol=1e-5)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Eight chips hold 2 of the 16 experts each. The parts of an expert
    layer's feed-forward that the eight shares compute, with what every
    chip computes alike (the shared expert) counted once, add up to the
    layer computed whole (all 16 experts held: the uncut reference)."""
    whole_cfg = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    whole = lam.init_params_on_device(whole_cfg, 11, quantize=True)
    sliced, experts = lam.split_experts(whole["layers"])
    w = lam.take_layer(sliced, 0)
    assert float(jnp.abs(w["router_bias"]).max()) > 0
    h = jax.random.normal(jax.random.key(4), (19, CFG.dim), jnp.float32)
    y_whole, counts, _ = latent_moe.moe_branch(whole_cfg, h, w, experts, 0,
                                               False)
    assert int(counts.sum()) == 19 * 4
    shared = llama.swiglu(h, w)
    total = shared
    for share in range(8):
        cfg = dataclasses.replace(CFG, experts_held=2,
                                  expert_offset=2 * share)
        mine = {k: QuantizedTensor(v.q[:, 2 * share:2 * share + 2],
                                   v.s[:, 2 * share:2 * share + 2])
                for k, v in experts.items()}
        y, n, _ = latent_moe.moe_branch(cfg, h, w, mine, 0, False)
        assert int(n.sum()) <= 19 * 4
        total = total + (y - shared)
    np.testing.assert_allclose(total, y_whole, rtol=1e-4, atol=1e-5)
    # and the whole layer is the plain reference's layer
    wl = jax.tree.map(lambda a: a[0], whole["layers"])
    y_ref, idx, wts = ref._route_and_share(h, wl, top_k=4, scaling=2.446,
                                           norm=True)
    for e in range(16):
        y_ref = y_ref + ref._held_expert(h, idx, wts, wl["we_gate_up"],
                                         wl["we_down"], e, e)
    np.testing.assert_allclose(y_whole, y_ref, rtol=2e-3, atol=2e-4)


def test_a_reference_of_another_share_disagrees(params):
    ids = prompt(20, seed=5)
    want = np.asarray(ref.reference_logits(FILE, params, ids))
    other = np.asarray(ref.reference_logits(
        dict(FILE, expert_offset=8), params, ids))
    assert miss(other, want) > 0.02


def test_the_latent_options_leave_a_rotated_bottlenecked_model_alone():
    """`q_lora_rank` None, `rotary` False and a router bias are this
    model's; a LatentMoeConfig without them builds the leaves it built."""
    cfg = latent_moe.LatentMoeConfig.tiny()
    assert cfg.rotary and cfg.q_lora_rank == 32
    shapes = jax.eval_shape(lambda: latent_moe.init_params_on_device(cfg))
    assert {"w_qa", "w_qb", "q_norm"} <= set(shapes["layers"])
    assert "w_q" not in shapes["layers"]
    assert "router_bias" not in shapes["layers"]
    direct = dataclasses.replace(cfg, q_lora_rank=None, rotary=False)
    shapes = jax.eval_shape(lambda: latent_moe.init_params_on_device(direct))
    assert "w_q" in shapes["layers"] and "w_qa" not in shapes["layers"]
    assert shapes["layers"]["w_q"].shape == (2, 64, 4 * 24)


def test_a_config_refuses_what_is_not_written():
    with pytest.raises(ValueError, match="mixer"):
        dataclasses.replace(CFG, layer_types=("kda", "attention"))
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(CFG, experts_held=13)
    with pytest.raises(ValueError, match="kda_sub"):
        dataclasses.replace(CFG, kda_chunk=12, kda_sub=8)
