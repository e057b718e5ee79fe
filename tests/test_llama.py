"""Llama decoder: golden logits vs HF transformers, cache consistency,
sharded-equals-single-device (the SURVEY.md §4 test strategy — the
reference ships no tests to port)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.hf_loader import llama_params_from_state_dict

TINY = llama.LlamaConfig.tiny()


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def test_forward_shapes(tiny_params):
    toks = jnp.zeros((2, 8), jnp.int32)
    logits, cache = llama.forward(tiny_params, TINY, toks)
    assert logits.shape == (2, 8, TINY.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


def test_prefill_then_decode_matches_full_forward(tiny_params):
    """Incremental decoding with the KV cache must reproduce the
    no-cache forward logits position by position."""
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, TINY.vocab_size)
    full, _ = llama.forward(tiny_params, TINY, toks)

    split = 7
    cache = llama.KVCache.zeros(TINY, B, max_len=32)
    pre, cache = llama.forward(tiny_params, TINY, toks[:, :split], kv_cache=cache)
    np.testing.assert_allclose(pre, full[:, :split], atol=1e-4)
    for t in range(split, S):
        step, cache = llama.forward(tiny_params, TINY, toks[:, t:t + 1],
                                    kv_cache=cache)
        np.testing.assert_allclose(step[:, 0], full[:, t], atol=1e-4,
                                   err_msg=f"position {t}")
    assert int(cache.lengths[0]) == S


def test_golden_logits_vs_hf_transformers(tiny_params):
    """Build an HF LlamaForCausalLM with the same tiny geometry, port our
    weights into it, and require logit agreement."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM

    hf_cfg = HFConfig(
        vocab_size=TINY.vocab_size, hidden_size=TINY.dim,
        num_hidden_layers=TINY.n_layers, num_attention_heads=TINY.n_heads,
        num_key_value_heads=TINY.n_kv_heads, head_dim=TINY.head_dim,
        intermediate_size=TINY.mlp_dim, rope_theta=TINY.rope_theta,
        rms_norm_eps=TINY.rms_eps, max_position_embeddings=TINY.max_seq_len,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    with torch.no_grad():
        model = LlamaForCausalLM(hf_cfg).eval()
        sd = {k: v.numpy() for k, v in model.state_dict().items()}

    ours = llama_params_from_state_dict(sd, TINY, dtype=jnp.float32)
    toks = np.random.default_rng(2).integers(0, TINY.vocab_size, (2, 10))
    with torch.no_grad():
        hf_logits = model(torch.tensor(toks)).logits.numpy()
    logits, _ = llama.forward(ours, TINY, jnp.asarray(toks))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, atol=2e-4)


def test_greedy_generate_deterministic(tiny_params):
    prompt = jnp.array([[5, 6, 7], [9, 10, 11]], jnp.int32)
    out = llama.greedy_generate(tiny_params, TINY, prompt, max_new_tokens=5)
    assert out.shape == (2, 8)
    out2 = llama.greedy_generate(tiny_params, TINY, prompt, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_tp_sharded_forward_matches_single_device(tiny_params, eight_devices):
    """Megatron-TP over the 8-device mesh must be numerically identical
    (fp32) to the unsharded forward."""
    from generativeaiexamples_tpu.config.schema import MeshConfig
    from generativeaiexamples_tpu.parallel.mesh import (
        build_mesh, logical_to_spec, shard_pytree)

    mesh = build_mesh(MeshConfig())  # tensor=8
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, TINY.vocab_size)
    want, _ = llama.forward(tiny_params, TINY, toks)

    specs = llama.param_specs(TINY)
    sharded = shard_pytree(tiny_params, specs, mesh)
    from jax.sharding import NamedSharding

    with jax.set_mesh(mesh):
        fn = jax.jit(lambda p, t: llama.forward(p, TINY, t)[0])
        got = fn(sharded, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_rope_llama3_scaling_matches_hf():
    """rope_freqs with llama3 scaling == transformers' reference impl."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    scaling = llama.RopeScaling(
        factor=32.0, low_freq_factor=1.0, high_freq_factor=4.0,
        original_max_position_embeddings=8192)
    hf_cfg = HFLlamaConfig(
        hidden_size=2048, num_attention_heads=32, head_dim=64,
        rope_theta=500000.0,
        rope_scaling={
            "rope_type": "llama3", "factor": 32.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192,
        })
    inv_freq, _ = ROPE_INIT_FUNCTIONS["llama3"](hf_cfg, torch.device("cpu"))
    ours = llama.rope_freqs(64, 500000.0, scaling)
    np.testing.assert_allclose(np.asarray(ours), inv_freq.numpy(), rtol=1e-6)
    # and without scaling the frequencies are plainly theta^(-2i/d)
    base = llama.rope_freqs(64, 500000.0, None)
    np.testing.assert_allclose(
        np.asarray(base),
        500000.0 ** (-np.arange(0, 64, 2, dtype=np.float32) / 64), rtol=1e-6)


def test_hf_loader_parses_rope_scaling(tmp_path):
    import json as _json

    cfg_json = {
        "vocab_size": 128256, "hidden_size": 2048, "num_hidden_layers": 16,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "intermediate_size": 8192, "rope_theta": 500000.0,
        "max_position_embeddings": 131072, "tie_word_embeddings": True,
        "rope_scaling": {
            "rope_type": "llama3", "factor": 32.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192},
    }
    (tmp_path / "config.json").write_text(_json.dumps(cfg_json))
    from generativeaiexamples_tpu.models.hf_loader import llama_config_from_hf

    cfg = llama_config_from_hf(str(tmp_path))
    assert cfg.rope_scaling == llama.RopeScaling(
        factor=32.0, low_freq_factor=1.0, high_freq_factor=4.0,
        original_max_position_embeddings=8192)

    # unsupported scaling types fail loudly instead of silently degrading
    cfg_json["rope_scaling"] = {"rope_type": "yarn", "factor": 2.0}
    (tmp_path / "config.json").write_text(_json.dumps(cfg_json))
    with pytest.raises(ValueError, match="rope_scaling"):
        llama_config_from_hf(str(tmp_path))


def _same_tree(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)


@pytest.mark.parametrize("quantize", [False, True])
def test_init_params_on_device_tree_equals_init_params(quantize):
    """The seeded on-device generator builds the tree init_params (then
    quantize_llama_params) builds, at full llama3-8b width — abstractly:
    nothing 8b-sized is materialised."""
    import functools

    from generativeaiexamples_tpu.ops.quant import quantize_llama_params

    cfg = llama.LlamaConfig.llama3_8b()

    def reference():
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return quantize_llama_params(params) if quantize else params

    _same_tree(jax.eval_shape(functools.partial(
        llama.init_params_on_device, cfg, quantize=quantize)),
        jax.eval_shape(reference))


@pytest.mark.parametrize("quantize", [False, True])
def test_init_params_on_device_is_keyed_by_its_seed(quantize):
    a = llama.init_params_on_device(TINY, 3, quantize=quantize)
    b = llama.init_params_on_device(TINY, 3, quantize=quantize)
    c = llama.init_params_on_device(TINY, 4, quantize=quantize)
    same = [bool((x == y).all())
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    assert all(same)
    assert any(bool((x != y).any())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c)))
    # distinct leaves of one seed are distinct draws
    wk, wv = a["layers"]["wk"], a["layers"]["wv"]
    assert bool((getattr(wk, "q", wk) != getattr(wv, "q", wv)).any())
    logits, _ = llama.forward(a, TINY, jnp.zeros((1, 8), jnp.int32))
    assert bool(jnp.isfinite(logits).all())


def test_init_sharded_params_born_sharded_same_values(eight_devices):
    """Under a mesh every leaf is created in its TP shards (never whole
    on one device and moved) and holds the unsharded call's values."""
    from generativeaiexamples_tpu.config.schema import MeshConfig
    from generativeaiexamples_tpu.parallel.mesh import build_mesh
    from generativeaiexamples_tpu.serving import sharding as shd

    mesh = build_mesh(MeshConfig(ici_tensor=2, ici_data=-1))
    got = shd.init_sharded_params(TINY, mesh, 5, quantize=True)
    want = llama.init_params_on_device(TINY, 5, quantize=True)
    _same_tree(got, want)
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool((x == y).all())
    w_gate = got["layers"]["w_gate"].q
    assert w_gate.sharding.spec[-1] == "tensor"
    assert w_gate.addressable_shards[0].data.shape[-1] == TINY.mlp_dim // 2
