"""Latent attention and sparse experts (models/latent_moe.py, ops/moe.py,
serving/paged_attention_mla.py, kv_cache.LatentPagePool) at a tiny size
on the CPU, seeded weights, against the benchmark's plain reference
(benchmark/architectures/axk1.py, which shares no code with the
program): logits, not tokens, and the router's choices counted."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import axk1
from benchmark.tests.test_axk1 import tiny_file
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import latent_moe as lm
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.quant import QuantizedTensor, quantize_tensor
from generativeaiexamples_tpu.serving import engine_model as em
from generativeaiexamples_tpu.serving import memory_plan
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import LatentPagePool, PagePool
from generativeaiexamples_tpu.serving.paged_attention_mla import (
    paged_attention_mla, paged_attention_mla_reference)

PS = 8


def config_file(**over):
    """The benchmark tests' tiny configuration file in A.X-K1's keys (3
    layers, one dense; 16 experts of which 4 are held from expert 4 on,
    4 a token), with pages of 8."""
    c = tiny_file()
    c["serving"].update(kv_dtype="float32", n_pages=48)
    c["serving"]["engine"].update(max_seq_len=64, page_size=PS,
                                  prefill_buckets=[16, 32])
    c.update(over)
    return c


FILE = config_file()
CFG = axk1.model_config(FILE)


@pytest.fixture(scope="module")
def params():
    return lm.init_params_on_device(CFG, 7, quantize=True)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n).astype(np.int32)


# -- YaRN ------------------------------------------------------------------

def test_yarn_frequencies_are_the_closed_form():
    """A.X-K1's keys: 64 rope dimensions, theta 10000, factor 32 over
    4096. The correction dimensions are 10 and 23 (worked by hand:
    64 ln(4096 / (2 pi b)) / (2 ln 10000) for b = 32 and 1, floor and
    ceiling); below 10 the original frequency, above 23 a 32nd of it,
    a straight blend between."""
    s = llama.YarnScaling()
    f = np.asarray(llama.rope_freqs(64, 10000.0, s))
    orig = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(f[:11], orig[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], orig[23:] / 32, rtol=1e-6)
    ramp = (np.arange(32) - 10) / 13
    mid = slice(11, 23)
    np.testing.assert_allclose(
        f[mid], orig[mid] / 32 * ramp[mid] + orig[mid] * (1 - ramp[mid]),
        rtol=1e-5)
    assert s.softmax_mscale == pytest.approx(0.1 * math.log(32) + 1)
    assert s.softmax_mscale == pytest.approx(1.3466, abs=1e-4)
    assert s.cos_sin_scale == 1.0
    # the program's and the reference's agree
    np.testing.assert_allclose(
        f, np.asarray(axk1.yarn_inv_freq(64, 10000.0, {
            "factor": 32, "beta_fast": 32, "beta_slow": 1,
            "original_max_position_embeddings": 4096})), rtol=1e-6)
    full = lm.LatentMoeConfig(rope_scaling=s)
    assert full.softmax_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2,
                                               rel=1e-4)


def test_a_llama_rope_is_untouched_by_the_yarn_branch():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, 16))
    pos = jnp.arange(5)[None]
    plain = llama.rope(x, pos, 1e4)
    scaled = llama.rope(x, pos, 1e4, llama.YarnScaling(
        factor=4.0, original_max_position_embeddings=8))
    assert not np.allclose(plain, scaled)
    np.testing.assert_allclose(
        llama.rope(x, pos, 1e4, llama.RopeScaling()),
        llama.rope(x, pos, 1e4, llama.RopeScaling()))


# -- the grouped matmul ----------------------------------------------------

def _pairs(sizes, E, T, k, seed):
    """local [T, k]: `sizes[e]` pairs on held expert e, the rest
    elsewhere (E), shuffled."""
    flat = np.full(T * k, E, np.int32)
    at = 0
    for e, n in enumerate(sizes):
        flat[at:at + n] = e
        at += n
    np.random.default_rng(seed).shuffle(flat)
    return jnp.asarray(flat.reshape(T, k))


@pytest.mark.parametrize("sizes", [
    (5, 0, 17, 1), (0, 0, 0, 40), (0, 0, 0, 0), (16, 16, 16, 16),
    (33, 1, 0, 2)], ids=["uneven", "one-group", "empty", "whole-tiles",
                         "over-a-tile"])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_grouped_matmul_is_the_loop_over_experts(sizes, form):
    """Ragged and empty groups: every pair's row times ITS expert's
    weight, scales in the epilogue; nothing dropped, nothing padded to
    the longest group."""
    E, T, k, K, N, tm = 4, 16, 4, 128, 256, 16
    local = _pairs(sizes, E, T, k, seed=sum(sizes))
    plan = moe.dispatch_plan(local, E, tm=tm)
    assert plan.rows.shape[0] == T * k + E * tm
    np.testing.assert_array_equal(plan.counts, sizes)
    assert int(plan.n_tiles[0]) == sum(-(-n // tm) for n in sizes)
    key = jax.random.PRNGKey(1)
    h = jax.random.normal(key, (T, K), jnp.float32)
    w = quantize_tensor(jax.random.normal(key, (2, E, K, N)) * K ** -0.5)
    x = h[plan.rows]
    if form == "kernel":
        y = moe.grouped_matmul_pallas(x, w, 1, plan, interpret=True)
    else:
        y = moe.grouped_matmul_int8(x, w, 1, plan, use_pallas=False)
    got = np.asarray(y)[np.minimum(np.asarray(plan.pos), y.shape[0] - 1)]
    full = np.asarray(w.q[1], np.float32) * np.asarray(w.s[1])[:, None, :]
    loc = np.asarray(local)
    for t in range(T):
        for j in range(k):
            if loc[t, j] < E:
                np.testing.assert_allclose(
                    got[t, j], np.asarray(h[t]) @ full[loc[t, j]],
                    rtol=2e-4, atol=2e-4)
            else:
                assert int(plan.pos[t, j]) == y.shape[0]  # elsewhere


def test_dispatch_plan_tiles_name_their_group():
    plan = moe.dispatch_plan(_pairs((3, 0, 20, 0), 4, 8, 4, 3), 4, tm=8)
    # expert 0: one tile; expert 2: three; the rest repeat the last
    assert list(np.asarray(plan.tile_group)[:4]) == [0, 2, 2, 2]
    assert set(np.asarray(plan.tile_group)[4:]) == {2}
    assert moe.tile_rows(128 * 8) == 32 and moe.tile_rows(1536 * 8) == 128


# -- the absorbed decode against the un-absorbed form -----------------------

def test_paged_mla_kernel_is_its_xla_form():
    # 4 slots of up to 6 pages of 16: one page, a whole turn of four, two
    # turns with a short second one, every page
    B, H, C, R, maxp = 4, 4, 128, 8, 6
    W = 256
    key = jax.random.PRNGKey(2)
    pool = jax.random.normal(key, (2, 1 + B * maxp, 16, W), jnp.float32)
    pool = pool.at[..., C + R:].set(0.0)
    q = jax.random.normal(key, (B, H, W), jnp.float32).at[..., C + R:].set(0)
    table = jnp.asarray(1 + np.arange(B * maxp).reshape(B, maxp), jnp.int32)
    lengths = jnp.asarray([1, 64, 70, 96], jnp.int32)
    want = paged_attention_mla_reference(q, pool, 1, table, lengths,
                                         latent=C, scale=0.3)
    got = paged_attention_mla(q, pool, 1, table, lengths, latent=C,
                              scale=0.3, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_absorbed_decode_is_the_unabsorbed_attention(params):
    """One block's attention for the last token of a sequence: keys and
    values built from every token's latent (attend_prompt) against the
    query absorbed into the latent and the paged kernel's XLA form over
    a latent pool (attend_cached)."""
    S = 21
    w = lm.take_layer(params["layers"], 1, skip=lm.EXPERT_WEIGHTS)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, S, CFG.dim))
    pos = jnp.arange(S)[None]
    q_nope, q_rope, row = lm.project_latent(CFG, h, w, pos)
    want = lm.attend_prompt(CFG, q_nope, q_rope, row, w,
                            jnp.asarray([S]), use_pallas=False)[0, :, -1]
    pool = PagePool.zeros(CFG, 8, PS, dtype=jnp.float32)
    assert isinstance(pool, LatentPagePool)
    pages = pool.encode_pages(jnp.pad(row, ((0, 0), (0, 3), (0, 0))))
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    pool = pool.write_pages(
        jnp.broadcast_to(pages.reshape(1, 3, PS, -1),
                         (CFG.cache_rows, 3, PS, pages.shape[-1])), table[0])

    def attend(q):
        c, r = pool.attention_operands(2)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, c.shape[-1] - q.shape[-1])))
        return paged_attention_mla_reference(
            q, c, r, table, jnp.asarray([S]), latent=CFG.kv_lora_rank,
            scale=CFG.softmax_scale)

    got = lm.attend_cached(CFG, q_nope[:, -1], q_rope[:, -1], w, attend)[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# -- the program against the plain reference --------------------------------

def _paged(params, ids, n_prompt):
    """Prefill ids[:n_prompt] (a group of 2, one real row), then every
    later token through decode_step and the latent pool: logits at
    positions n_prompt - 1 .. len(ids) - 1."""
    pool = PagePool.zeros(CFG, 24, PS, dtype=jnp.float32)
    bucket = 16 if n_prompt <= 16 else 32
    toks = np.zeros((2, bucket), np.int32)
    toks[0, :n_prompt] = ids[:n_prompt]
    rows = np.zeros((2, bucket // PS), np.int32)
    rows[0] = 1 + np.arange(bucket // PS)
    table = np.zeros((4, 8), np.int32)
    table[0] = 1 + np.arange(8)
    logits, pool = em.prefill_step(
        params, CFG, pool, jnp.asarray(toks[:1]), jnp.int32(n_prompt),
        jnp.asarray(rows[0]), False)
    out = [np.asarray(logits)]
    first, pool2 = em.prefill_batch_step(
        params, CFG, PagePool.zeros(CFG, 24, PS, dtype=jnp.float32),
        jnp.asarray(toks), jnp.asarray([n_prompt, 1], jnp.int32),
        jnp.asarray(rows), jnp.zeros(2), jnp.ones(2),
        jnp.zeros(2, jnp.int32), jax.random.PRNGKey(0), False)
    assert int(first[0]) == int(np.argmax(out[0]))
    np.testing.assert_allclose(pool2.c[:, 1:3], pool.c[:, 1:3], atol=1e-4)
    for i in range(n_prompt, len(ids)):
        cur = np.zeros((4,), np.int32)
        cur[0] = ids[i]
        ln = np.ones((4,), np.int32)
        ln[0] = i + 1
        logits, pool = em.decode_step(params, CFG, pool, jnp.asarray(cur),
                                      jnp.asarray(table), jnp.asarray(ln),
                                      False)
        out.append(np.asarray(logits[0]))
    return np.stack(out)


@pytest.mark.parametrize("n_prompt,n_new", [(11, 9), (16, 3), (23, 12)])
def test_prefill_then_paged_decode_is_the_references_one_pass(
        params, n_prompt, n_new):
    """float32 activations over int8 weights: what is left between the
    program and the reference is the order of float32 sums, so the
    logits agree to 2e-3 of the largest and the router's choices (4 of
    16 for every token and expert layer) agree entirely."""
    ids = prompt(n_prompt + n_new, seed=n_prompt)
    ref, ref_choice = axk1.reference_forward(FILE, params, ids)
    got = _paged(params, ids, n_prompt)
    top = float(np.abs(ref).max())
    assert np.abs(got - np.asarray(ref)[n_prompt - 1:]).max() / top < 2e-3
    _, choice = lm.forward(params, CFG, jnp.asarray(ids)[None],
                           use_pallas=False)
    same = np.mean(np.sort(np.asarray(choice)[:, 0], -1)
                   == np.sort(np.asarray(ref_choice), -1))
    assert same == 1.0


def test_a_reference_of_another_share_disagrees(params):
    """The comparison can tell: a reference told the held experts are
    the NEXT four gives other logits."""
    ids = prompt(20, seed=5)
    ref = np.asarray(axk1.reference_logits(FILE, params, ids))
    other = np.asarray(axk1.reference_logits(
        dict(FILE, expert_offset=8), params, ids))
    assert np.abs(ref - other).max() / np.abs(ref).max() > 0.02


# -- the share test ----------------------------------------------------------

@pytest.mark.parametrize("streams", [1, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(streams):
    """Four chips hold 4 of the 16 experts each. The parts of an expert
    layer's feed-forward that the four shares compute, with what every
    chip computes alike (the shared expert) counted once, add up to the
    layer computed whole (all 16 experts held: the uncut reference). With
    four residual streams (`hc_mult` 4: every chip mixes its own
    sequences' streams alike) the same holds of the STREAM after the
    branch, which is linear in the branch's output: the four chips'
    routed parts of it plus the shared expert's, counted once, are the
    uncut layer's."""
    from generativeaiexamples_tpu.models import hyper_connections as residual

    base = dataclasses.replace(CFG, hc_mult=streams)
    whole_cfg = dataclasses.replace(base, experts_held=16, expert_offset=0)
    whole = lm.init_params_on_device(whole_cfg, 11, quantize=True)
    w = lm.take_layer(whole["layers"], 0, skip=lm.EXPERT_WEIGHTS)
    h = jax.random.normal(jax.random.PRNGKey(4), (19, CFG.dim), jnp.float32)
    _, experts = lm.split_experts(whole["layers"])
    y_whole, counts, _ = lm.moe_branch(whole_cfg, h, w, experts, 0, False)
    assert int(counts.sum()) == 19 * 4
    shared = llama.swiglu(h, w)
    total = shared
    parts = []
    for share in range(4):
        cfg = dataclasses.replace(base, experts_held=4,
                                  expert_offset=4 * share)
        mine = {k: QuantizedTensor(v.q[:, 4 * share:4 * share + 4],
                                   v.s[:, 4 * share:4 * share + 4])
                for k, v in experts.items()}
        y, n, _ = lm.moe_branch(cfg, h, w, mine, 0, False)
        assert int(n.sum()) <= 19 * 4
        parts.append(y - shared)
        total = total + (y - shared)
    np.testing.assert_allclose(total, y_whole, rtol=1e-4, atol=1e-5)
    # the stream after the branch: what each chip hands its next layer
    shape = (19,) + ((streams, CFG.dim) if streams > 1 else (CFG.dim,))
    x = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32)
    _, carry = residual.open(whole_cfg, x, w, "ffn")
    assert (carry is None) == (streams == 1)

    def after(y):
        return residual.close(whole_cfg, x, y, carry)

    alike = after(shared)  # counted once
    stream = alike + sum(after(shared + part) - alike for part in parts)
    np.testing.assert_allclose(stream, after(y_whole), rtol=1e-4, atol=1e-4)
    if streams > 1:  # and the mixing is not the plain add
        assert float(jnp.abs(after(y_whole)[:, 0] - (x[:, 0] + y_whole))
                     .max()) > 1e-2
    # and the whole layer is the plain reference's layer
    file16 = config_file(n_routed_experts=16, expert_offset=0)
    wl = jax.tree.map(lambda a: a[0], whole["layers"])
    y_ref, idx, wts = axk1._route_and_share(
        h, wl, top_k=4, scaling=2.5, norm=True)
    for e in range(axk1.held(file16)):
        y_ref = y_ref + axk1._held_expert(
            h, idx, wts, wl["we_gate_up"], wl["we_down"], e=e, expert=e)
    np.testing.assert_allclose(y_whole, y_ref, rtol=2e-3, atol=2e-4)


# -- the engine ---------------------------------------------------------------

def _engine(params, **over):
    from benchmark.harness import system
    from benchmark.harness.bench_tokenizer import WordTokenizer

    ecfg = dataclasses.replace(system.engine_config(FILE), **over)
    return LLMEngine(params, CFG, WordTokenizer(512), ecfg, n_pages=48)


def test_the_engine_serves_the_forwards_tokens_and_counts_the_pairs(params):
    eng = _engine(params)
    assert isinstance(eng.pool, LatentPagePool)
    eng.start()
    try:
        ids = [int(t) for t in prompt(13, seed=9)]
        served = [ev["token_id"] for ev in eng.generate_stream(
            ids, max_new_tokens=10, temperature=0.0)]
    finally:
        eng.stop()
    seq = list(ids)
    for _ in range(10):
        logits, _ = lm.forward(params, CFG, jnp.asarray([seq], jnp.int32),
                               use_pallas=False)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert served == seq[len(ids):]
    snap = eng.metrics.snapshot()
    assert snap["experts_held"] == 4 and snap["kv_cache_rows"] == 3
    # a latent pool runs neither int8 pool kernel: no row is skipped
    assert snap["decode_attn_rows_skipped"] == 0
    # 40 values a token and layer in 128 lanes, float32, 3 rows
    assert snap["kv_bytes_per_token"] == 3 * 128 * 4
    steps = snap["decode_steps"]
    # one live slot: 4 choices in each of the 2 expert layers a step
    assert snap["moe_pairs_routed"] == steps * 2 * 4
    assert 0 < snap["moe_pairs_local"] < snap["moe_pairs_routed"]
    loads = [e for e in eng.flight.snapshot_events() if e["kind"] == 19]
    assert loads and all(e["b"] >= 1.0 or e["a"] == 0 for e in loads)
    # a: pairs a step and expert layer; every block's, times its steps and
    # the 2 expert layers, is the counter
    assert sum(e["a"] for e in loads) > 0
    assert em.expert_load_rows(CFG) == 2 * 4
    assert em.expert_load_rows(llama.LlamaConfig.tiny()) == 0


def test_a_llamas_engine_reports_the_expert_counters_as_zero():
    cfg = llama.LlamaConfig.tiny()
    from benchmark.harness.bench_tokenizer import WordTokenizer
    eng = LLMEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                    WordTokenizer(256), EngineConfig(
                        max_batch_size=2, max_seq_len=32, page_size=8,
                        prefill_buckets=(16,)))
    snap = eng.metrics.snapshot()
    assert (snap["moe_pairs_routed"], snap["moe_pairs_local"],
            snap["experts_held"]) == (0, 0, 0)
    from generativeaiexamples_tpu.serving import fleet
    assert {"moe_pairs_routed", "moe_pairs_local"} <= set(fleet.counter_keys())


@pytest.mark.parametrize("lane,over", [
    ("speculative_k", dict(speculative_k=2)),
    ("step_plans", dict(step_plans=True)),
    ("fused_prefill", dict(fused_prefill=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_pager", dict(prefix_cache=True, kv_pager=True)),
    ("kv_dtype int8", dict(kv_dtype="int8")),
])
def test_lanes_without_a_latent_form_are_refused_by_name(params, lane, over):
    with pytest.raises(ValueError, match=f"engine.{lane}"):
        _engine(params, **over)


def test_a_prompt_past_the_largest_bucket_is_refused(params):
    from generativeaiexamples_tpu.serving.engine import (
        GenRequest, PromptTooLongError)
    eng = _engine(params)
    with pytest.raises(PromptTooLongError):
        eng.submit(GenRequest(prompt_ids=list(range(1, 40))))


def test_memory_plan_counts_a_latent_row(params):
    ecfg = dataclasses.replace(EngineConfig(), page_size=PS,
                               kv_dtype="float32", max_seq_len=64,
                               max_batch_size=4, prefill_buckets=(16,))
    page = memory_plan.pool_page_bytes_per_device(CFG, ecfg, {})
    pool = PagePool.zeros(CFG, 5, PS, dtype=jnp.float32)
    assert page == pool.c.nbytes // 5 == 3 * PS * 128 * 4
    weights = memory_plan.weight_bytes_per_device(CFG, {}, quantize=True)
    assert weights == sum(x.nbytes for x in jax.tree.leaves(params))
    with pytest.raises(memory_plan.MemoryPlanError, match="tensor"):
        memory_plan.weight_bytes_per_device(CFG, {"tensor": 2}, quantize=True)


def test_hf_loader_refuses_an_axk1_snapshot(tmp_path):
    import json

    from generativeaiexamples_tpu.models import hf_loader
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in FILE.items() if k not in ("serving", "published")}))
    with pytest.raises(ValueError, match="'axk1' has.*no tensor-name map"):
        hf_loader.load_llama(str(tmp_path))
    with pytest.raises(ValueError, match="latent"):
        hf_loader.llama_config_from_hf(str(tmp_path))
