"""A looped decoder (the "ouro" family: the same blocks run n_passes times
a token, a cache row per (pass, block), a norm on both sides of each
branch, ln_f closing every pass) through the contiguous and the paged
paths, against the plain reference of benchmark/architectures/ouro.py.
Logits are compared, not tokens. Tiny sizes on the CPU: 3 blocks, 2
passes, 4 heads of 16, vocabulary 256.
"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import ouro
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model as em
from generativeaiexamples_tpu.serving import memory_plan
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import PagePool
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

L, T, PS = 3, 2, 8
CFG = dataclasses.replace(llama.LlamaConfig.tiny(), n_layers=L, n_kv_heads=4,
                          n_passes=T, post_norms=True)
# what the reference reads of a published config.json
PUBLISHED = {"total_ut_steps": T, "num_hidden_layers": L,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
             "vocab_size": 256, "rope_theta": CFG.rope_theta,
             "rms_norm_eps": CFG.rms_eps, "tie_word_embeddings": False}

# Tolerances, as the largest |difference| over the largest |reference
# logit| of the sequence:
# - float32 weights and cache: program and reference are both float32 and
#   differ in the order of their sums only (measured 1e-6).
# - an int8 cache rounds each K and V row to 1/254 of its largest entry,
#   coarse at 16-wide heads: measured 1.2-3.5 % over four seeds, float32
#   or int8 weights (the reference multiplies the same codes and scales,
#   so int8 weights add nothing of their own). 8 % is twice the largest
#   reading; each of the four wrong models below reads over 50 %.
TOL = {"float32": 1e-4, "int8": 0.08}
WRONG = 0.5
IDS = (np.arange(1, 41) * 7 + 3) % 250  # 40 seeded token ids


def _params(quantize=False, seed=0):
    """Seeded weights with norm weights that are NOT ones (a skipped or
    misplaced norm must change the logits)."""
    if quantize:
        p = llama.init_params_on_device(CFG, seed, quantize=True)
    else:
        p = llama.init_params(CFG, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(100 + seed)
    for i, name in enumerate(("ln1", "ln2", "ln1_post", "ln2_post")):
        w = p["layers"][name]
        p["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), w.shape, w.dtype)
    p["ln_f"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 9), p["ln_f"].shape, p["ln_f"].dtype)
    return p


def _worst(got, ref):
    return float(jnp.abs(jnp.asarray(got) - ref).max() / jnp.abs(ref).max())


def _paged_logits(params, cfg, kv_dtype, ids=IDS, prompts=(13, 21),
                  decode=em.decode_step, n_pages=12):
    """Two sequences of unequal prompt lengths: prefill_batch_step (the
    batch scatter), then decode_step token by token over page
    boundaries, teacher-forced with `ids`. Returns the first sampled
    tokens and {(sequence, position): logits}."""
    pool = PagePool.zeros(cfg, n_pages, PS, dtype=jnp.dtype(kv_dtype))
    S = 32
    toks = np.zeros((2, S), np.int32)
    tables = np.array([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], np.int32)
    for b, n in enumerate(prompts):
        toks[b, :n] = ids[:n]
    zeros = jnp.zeros((2,), jnp.float32)
    first, pool = em.prefill_batch_step(
        params, cfg, pool, jnp.asarray(toks),
        jnp.asarray(prompts, jnp.int32), jnp.asarray(tables[:, :S // PS]),
        zeros, zeros, jnp.zeros((2,), jnp.int32), jax.random.PRNGKey(0),
        use_pallas=False)
    out = {}
    lengths = np.asarray(prompts)
    for _ in range(len(ids) - max(prompts)):
        cur = np.array([ids[n] for n in lengths], np.int32)
        lengths = lengths + 1
        logits, pool = decode(params, cfg, pool, jnp.asarray(cur),
                              jnp.asarray(tables), jnp.asarray(lengths),
                              use_pallas=False)
        for b in range(2):
            out[(b, int(lengths[b]) - 1)] = logits[b]
    return np.asarray(first), out


def _paged_worst(params, cfg, kv_dtype, **kw):
    ref = ouro.reference_logits(PUBLISHED, params, IDS)
    _, got = _paged_logits(params, cfg, kv_dtype, **kw)
    return max(_worst(v, ref[pos]) for (_, pos), v in got.items())


def test_contiguous_forward_agrees_with_the_reference():
    params = _params()
    ref = ouro.reference_logits(PUBLISHED, params, IDS)
    logits, _ = llama.forward(params, CFG, jnp.asarray(IDS)[None])
    assert _worst(logits[0], ref) < TOL["float32"]
    # prefill into the contiguous cache, then decode through it
    cache = llama.KVCache.zeros(CFG, 1, max_len=48)
    assert cache.k.shape[0] == L * T
    pre, cache = llama.forward(params, CFG, jnp.asarray(IDS[:17])[None],
                               kv_cache=cache)
    assert _worst(pre[0], ref[:17]) < TOL["float32"]
    for t in range(17, 40):
        step, cache = llama.forward(params, CFG, jnp.asarray(IDS[t:t + 1])[None],
                                    kv_cache=cache)
        assert _worst(step[0, 0], ref[t]) < TOL["float32"], t


@pytest.mark.parametrize("weights,kv_dtype", [
    ("float32", "float32"), ("float32", "int8"), ("int8", "int8")])
def test_paged_prefill_then_decode_agrees_with_the_reference(weights,
                                                             kv_dtype):
    params = _params(quantize=weights == "int8")
    ref = ouro.reference_logits(PUBLISHED, params, IDS)
    first, got = _paged_logits(params, CFG, kv_dtype)
    assert len(got) == 2 * 19 and (0, 13) in got and (1, 39) in got
    worst = max(_worst(v, ref[pos]) for (_, pos), v in got.items())
    assert worst < TOL[kv_dtype], worst
    # the batch prefill's own first tokens: what the reference ranks first
    for b, n in enumerate((13, 21)):
        assert ref[n - 1, first[b]] >= ref[n - 1].max() - 1e-4
    # and the single-sequence prefill's logits
    pool = PagePool.zeros(CFG, 6, PS, dtype=jnp.dtype(kv_dtype))
    toks = np.zeros((1, 16), np.int32)
    toks[0, :11] = IDS[:11]
    logits, _ = em.prefill_step(params, CFG, pool, jnp.asarray(toks),
                                jnp.int32(11), jnp.asarray([1, 2], jnp.int32),
                                use_pallas=False)
    assert _worst(logits, ref[10]) < TOL["float32"]  # prefill reads no cache


def test_chunked_prefill_lane_agrees_with_the_reference():
    """Prompts beyond the largest bucket: prefill_chunk_step into the
    contiguous scratch cache, cache_to_pool, then paged decode; and
    pool_to_cache gives the cache back."""
    params = _params()
    ref = ouro.reference_logits(PUBLISHED, params, IDS)
    cache = llama.KVCache.zeros(CFG, 1, max_len=32)
    for lo in (0, 8, 16):
        logits, cache = em.prefill_chunk_step(
            params, CFG, cache, jnp.asarray(IDS[lo:lo + 8])[None],
            jnp.int32(8), use_pallas=False)
    assert _worst(logits, ref[23]) < TOL["float32"]
    table = jnp.asarray([2, 3, 4, 5], jnp.int32)
    back = em.pool_to_cache(
        em.cache_to_pool(PagePool.zeros(CFG, 8, PS), cache, CFG, table),
        CFG, table, jnp.int32(24))
    np.testing.assert_array_equal(np.asarray(back.k[:, :, :, :24]),
                                  np.asarray(cache.k[:, :, :, :24]))
    pool = em.cache_to_pool(PagePool.zeros(CFG, 8, PS), cache, CFG, table)
    logits, _ = em.decode_step(
        params, CFG, pool, jnp.asarray(IDS[24:25]), table[None],
        jnp.asarray([25], jnp.int32), use_pallas=False)
    assert _worst(logits[0], ref[24]) < TOL["float32"]


# -- four wrong models: each must read NOT correct ------------------------

def _walk_without_closing_norm(cfg, params, x, run_pass, state=None,
                               rolled=False):
    for u in range(cfg.n_passes):
        x, state, _ = run_pass(x, state, u * cfg.n_layers)
    return x, state, None


def _walk_with_shared_rows(cfg, params, x, run_pass, state=None,
                           rolled=False):
    for _ in range(cfg.n_passes):
        x, state, _ = run_pass(x, state, 0)  # every pass on rows 0..L-1
        x = llama.rms_norm(x, params["ln_f"], cfg.rms_eps)
    return x, state, None


def _unjitted_decode(params, cfg, pool, tokens, tables, lengths, use_pallas):
    # not the jitted decode_step: a patched walk must be traced afresh
    return em._decode_once(params, cfg, pool, tokens, tables, lengths,
                           use_pallas)


@pytest.mark.parametrize("wrong", ["one_pass_fewer", "no_loop_norm",
                                   "output_norms_skipped", "rows_shared"])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_a_wrong_model_reads_not_correct(wrong, kv_dtype, monkeypatch):
    params = _params()
    cfg, kw = CFG, {}
    if wrong == "one_pass_fewer":
        cfg = dataclasses.replace(CFG, n_passes=T - 1)
    elif wrong == "output_norms_skipped":
        cfg = dataclasses.replace(CFG, post_norms=False)
    else:  # the prefill is right; the decode steps take the wrong walk
        monkeypatch.setattr(em, "walk_passes", {
            "no_loop_norm": _walk_without_closing_norm,
            "rows_shared": _walk_with_shared_rows}[wrong])
        kw["decode"] = _unjitted_decode
        with monkeypatch.context() as m:  # ... from a right prefill
            m.setattr(em, "walk_passes", llama.walk_passes)
            assert _paged_worst(params, cfg, kv_dtype, **kw) < TOL[kv_dtype]
    worst = _paged_worst(params, cfg, kv_dtype, **kw)
    assert worst > WRONG, (wrong, worst)


# -- the pool and the planner count rows ----------------------------------

@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_pool_has_a_row_per_pass_and_block_and_the_planner_budgets_them(
        kv_dtype):
    assert CFG.cache_rows == L * T
    pool = PagePool.zeros(CFG, 5, PS, dtype=jnp.dtype(kv_dtype))
    codes = pool.kv if pool.quantized else pool.k
    assert codes.shape[-5] == L * T  # [.., rows, KH, P, ps, Hd]
    page_bytes = sum(a.nbytes for a in jax.tree.leaves(pool)) // 5
    ecfg = EngineConfig(page_size=PS, kv_dtype=kv_dtype)
    assert memory_plan.pool_page_bytes_per_device(CFG, ecfg, {}) == page_bytes
    one_pass = dataclasses.replace(CFG, n_passes=1)
    assert memory_plan.pool_page_bytes_per_device(one_pass, ecfg, {}) \
        == page_bytes // T
    fitted = PagePool.for_budget(CFG, 7 * page_bytes + 1, PS,
                                 dtype=jnp.dtype(kv_dtype))
    assert fitted.n_pages == 7


# -- the engine: serves it, counts it, refuses what it cannot walk --------

ECFG = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=PS,
                    prefill_buckets=(16,), decode_steps_per_dispatch=2,
                    pace_emission_max_streams=0)


@pytest.mark.parametrize("option,value", [
    ("speculative_k", 2), ("step_plans", True), ("fused_prefill", True),
    ("prefix_cache", True), ("kv_pager", True)])
def test_engine_refuses_a_lane_it_cannot_walk_by_its_options_name(option,
                                                                  value):
    ecfg = dataclasses.replace(ECFG, **{option: value})
    with pytest.raises(ValueError) as e:
        LLMEngine(_params(), CFG, ByteTokenizer(), ecfg)
    assert f"engine.{option}" in str(e.value)
    assert f"n_passes={T}" in str(e.value)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_engine_serves_it_and_counts_block_executions(kv_dtype):
    """Through LLMEngine as the servers build it: a bucketed prompt and
    one beyond the largest bucket (the chunked lane), greedy; every
    served token is one the reference ranks within 5 % of its best
    (the benchmark's own check), and the counters say 6 blocks a step."""
    params = _params()
    eng = LLMEngine(params, CFG, ByteTokenizer(),
                    dataclasses.replace(ECFG, kv_dtype=kv_dtype)).start()
    try:
        for n in (11, 29):
            prompt = [int(t) for t in IDS[:n]]
            served = [ev["token_id"] for ev in eng.generate_stream(
                prompt, max_new_tokens=6) if ev["token_id"] >= 0]
            assert len(served) == 6
            ref = np.asarray(ouro.reference_logits(
                PUBLISHED, params, prompt + served))
            for i, tok in enumerate(served):
                row = ref[n - 1 + i]
                assert row.max() - row[tok] <= 0.05 * abs(row.max()), (n, i)
        snap = eng.metrics.snapshot()
    finally:
        eng.stop()
    assert snap["decode_steps"] > 0
    assert snap["layer_passes"] == snap["decode_steps"] * L * T
    assert snap["kv_cache_rows"] == L * T
    per_row = 2 * 4 * 16 * (4 if kv_dtype == "float32" else 1) \
        + (2 * 4 * 4 if kv_dtype == "int8" else 0)
    assert snap["kv_bytes_per_token"] == L * T * per_row


def test_tensor_parallel_looped_engine_matches_single_device():
    from generativeaiexamples_tpu.config.schema import MeshConfig
    from generativeaiexamples_tpu.parallel.mesh import build_mesh
    from generativeaiexamples_tpu.serving import sharding as shd

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    mesh = build_mesh(MeshConfig(ici_tensor=-1), devices=jax.devices()[:4])
    params = _params()
    specs = llama.param_specs(CFG)["layers"]
    assert specs["ln1_post"] == specs["ln1"] == specs["ln2_post"]

    def run(p, mesh):
        eng = LLMEngine(p, CFG, ByteTokenizer(), ECFG, mesh=mesh).start()
        try:
            return [[ev["token_id"] for ev in eng.generate_stream(
                [int(t) for t in IDS[:n]], max_new_tokens=8)
                if ev["token_id"] >= 0] for n in (9, 14)]
        finally:
            eng.stop()

    assert run(shd.shard_llama_params(params, CFG, mesh), mesh) \
        == run(params, None)


def test_weights_on_device_know_the_two_new_norm_leaves():
    p = llama.init_params_on_device(CFG, 3, quantize=True)
    assert p["layers"]["ln1_post"].shape == (L, CFG.dim)
    assert p["layers"]["ln2_post"].shape == (L, CFG.dim)
    # depth-scaled: 2 x 3 x 2 = 12 branch outputs sum to the stream's size
    assert float(p["layers"]["ln1_post"][0, 0]) == pytest.approx(12 ** -0.5)
    assert float(llama.init_params(CFG, jax.random.PRNGKey(0))["layers"][
        "ln2_post"][1, 1]) == pytest.approx(12 ** -0.5)
    plain = llama.init_params_on_device(
        dataclasses.replace(CFG, post_norms=False, n_passes=1), 3,
        quantize=True)
    assert "ln1_post" not in plain["layers"]
    # the leaves both models have are drawn from the same keys
    np.testing.assert_array_equal(np.asarray(p["layers"]["w_down"].q),
                                  np.asarray(plain["layers"]["w_down"].q))


def test_the_walks_scopes_are_in_both_copies_of_the_block():
    params = _params()
    pool = PagePool.zeros(CFG, 4, PS)
    paged = em.decode_step.lower(
        params, CFG, pool, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
        False).as_text(debug_info=True)
    contiguous = jax.jit(lambda p, t: llama.forward(p, CFG, t)[0]).lower(
        params, jnp.zeros((1, 4), jnp.int32)).as_text(debug_info=True)
    for scope in ("loop.pass", "loop.norm", "attn.post_norm",
                  "mlp.post_norm"):
        assert scope in paged, scope
        assert scope in contiguous, scope


# -- the direct q/k/v projections (engine_model.direct_qkv) ----------------

@pytest.mark.parametrize("looped", [False, True])
def test_direct_qkv_serves_the_contiguous_forwards_tokens_and_is_counted(
        looped):
    """A tiny int8 model through the engine at blocks of up to 8: the
    greedy tokens are `llama.forward`'s, whichever form a block's
    projections took, and `decode_steps_direct_qkv` counts exactly the
    steps of the blocks that took the direct form: all of a looped
    model's, and a one-pass model's blocks of up to
    DIRECT_QKV_MAX_STEPS steps."""
    cfg = CFG if looped else llama.LlamaConfig.tiny()
    params = llama.init_params_on_device(cfg, 5, quantize=True)
    prompt, new = [int(t) for t in IDS[:11]], 14
    want = list(prompt)
    for _ in range(new):
        logits, _ = llama.forward(params, cfg, jnp.asarray([want]))
        want.append(int(jnp.argmax(logits[0, -1])))
    eng = LLMEngine(params, cfg, ByteTokenizer(), dataclasses.replace(
        ECFG, decode_steps_per_dispatch=8, kv_dtype="float32"))
    blocks, exec_plan = [], eng._exec_plan

    def recording(rec):
        blocks.append(int(rec["plan_decode_k"]))
        return exec_plan(rec)

    eng._exec_plan = recording
    eng.start()
    try:
        served = [ev["token_id"] for ev in eng.generate_stream(
            prompt, max_new_tokens=new) if ev["token_id"] >= 0]
        snap = eng.metrics.snapshot()
    finally:
        eng.stop()
    assert served == want[len(prompt):]
    assert max(blocks) == 8 and min(blocks) <= 2, blocks
    assert snap["decode_steps"] == sum(blocks)
    assert snap["decode_steps_direct_qkv"] == sum(
        k for k in blocks if looped or k <= em.DIRECT_QKV_MAX_STEPS)
    assert em.direct_qkv(cfg, 8) == looped


# -- a one-pass model is the program it always was ------------------------
# sha256 (first 16 hex digits) of the lowered StableHLO of a tiny Llama's
# step programs, taken on the parent of the PR that added the walk (PR
# 29) and unchanged by it. A PR that means to change a step program of
# the Llama block regenerates these (the loop below prints them on a
# mismatch); a PR that adds a family must not have to. PR 30 moved four
# on purpose: `decode_step.*` and `decode_multi_step.*` (lowered at 2
# steps) now hold the optimization barrier of the direct q/k/v form
# (engine_model.direct_qkv); `decode_multi_step_k8.*`, the long block,
# were taken on PR 30's PARENT and must not move with it. PR 31 wrote the
# decode family's body once (engine_model._decode_rows) and left the
# eleven as they were; it added the speculative, tree and fused programs,
# every other site of the pool's append, taken on its PARENT: the tree
# and fused four are the parent's, the linear verify's two are not
# (parent f561f077d4665964 / dce4c8fc3d923554): the same operations,
# with the queries' reshape after the append instead of before it.
LLAMA_PROGRAMS = {
    "decode_multi_step.float32": "4715aca7b36c9762",
    "decode_multi_step.int8": "a5a299d10d5be839",
    "decode_multi_step_k8.float32": "3c7bd6c362e3c342",
    "decode_multi_step_k8.int8": "2cb68fb5cdd29be4",
    "decode_step.float32": "5d5d387a4ddc2a80",
    "decode_step.int8": "9ee0516f7403b667",
    "prefill_batch_step.float32": "6ccb880ce282653a",
    "prefill_batch_step.int8": "dd7d84802127b149",
    "prefill_step.float32": "3bd7b8f722eb93dd",
    "prefill_step.int8": "914f0780433eb1eb",
    "prefill_chunk_step": "57bcbf373ce97da4",
    "decode_spec_multi_step.float32": "8959164beef5d022",
    "decode_spec_multi_step.int8": "184775e26b722ef1",
    "decode_spec_multi_step_tree.float32": "25b9e7244cb6053d",
    "decode_spec_multi_step_tree.int8": "d8ceb5a339d9845b",
    "fused_decode_prefill_step.float32": "2fa8a6c474d096f0",
    "fused_decode_prefill_step.int8": "f44397680782bca8",
}


def test_a_tiny_llamas_step_programs_lower_to_the_text_they_did():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, maxp = 4, 4
    greedy = (True, False, False)
    key = jax.random.PRNGKey(1)

    def i32(*s):
        return jnp.zeros(s, jnp.int32)

    def f32(*s):
        return jnp.zeros(s, jnp.float32)

    lowered = {}
    for dt in ("float32", "int8"):
        pool = PagePool.zeros(cfg, 9, PS, dtype=jnp.dtype(dt))
        for name, n_steps in (("decode_multi_step", 2),
                              ("decode_multi_step_k8", 8)):
            lowered[f"{name}.{dt}"] = em.decode_multi_step.lower(
                params, cfg, pool, i32(B), i32(B, maxp), i32(B) + 1,
                jnp.ones((B,), bool), f32(B), f32(B), i32(B), key, n_steps,
                False, sampling_flags=greedy)
        lowered[f"decode_step.{dt}"] = em.decode_step.lower(
            params, cfg, pool, i32(B), i32(B, maxp), i32(B) + 1, False)
        lowered[f"prefill_batch_step.{dt}"] = em.prefill_batch_step.lower(
            params, cfg, pool, i32(2, 16), i32(2) + 1, i32(2, 2), f32(2),
            f32(2), i32(2), key, False, sampling_flags=greedy)
        lowered[f"prefill_step.{dt}"] = em.prefill_step.lower(
            params, cfg, pool, i32(1, 16), jnp.int32(3), i32(2), False)
        for name, n_branches in (("decode_spec_multi_step", 0),
                                 ("decode_spec_multi_step_tree", 2)):
            lowered[f"{name}.{dt}"] = em.decode_spec_multi_step.lower(
                params, cfg, pool, i32(B, 32), i32(B), i32(B) + 1,
                i32(B, maxp), jnp.ones((B,), bool), n_steps=2, k=3,
                n_branches=n_branches, use_pallas=False)
        lowered[f"fused_decode_prefill_step.{dt}"] = (
            em.fused_decode_prefill_step.lower(
                params, cfg, pool, i32(B), i32(B, maxp), i32(B) + 1,
                jnp.ones((B,), bool), f32(B), f32(B), i32(B), key,
                llama.KVCache.zeros(cfg, 1, max_len=32), i32(1, 8),
                jnp.int32(5), 2, False, sampling_flags=greedy))
    lowered["prefill_chunk_step"] = em.prefill_chunk_step.lower(
        params, cfg, llama.KVCache.zeros(cfg, 1, max_len=32), i32(1, 8),
        jnp.int32(5), False)
    got = {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
           for k, v in lowered.items()}
    assert got == LLAMA_PROGRAMS, json.dumps(got, indent=1)


# -- models/hf_loader.py reads model_type ---------------------------------

def _snapshot(tmp_path, **keys):
    c = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": L,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
         "intermediate_size": 128}
    (tmp_path / "config.json").write_text(json.dumps(dict(c, **keys)))
    return str(tmp_path)


def test_hf_config_of_an_ouro_snapshot_sets_the_passes_and_the_norms(tmp_path):
    from generativeaiexamples_tpu.models import hf_loader

    cfg = hf_loader.llama_config_from_hf(_snapshot(
        tmp_path, model_type="ouro", total_ut_steps=4))
    assert (cfg.n_passes, cfg.post_norms, cfg.cache_rows) == (4, True, 4 * L)
    plain = hf_loader.llama_config_from_hf(_snapshot(
        tmp_path, model_type="mistral"))
    assert (plain.n_passes, plain.post_norms) == (1, False)


def test_load_llama_refuses_an_ouro_snapshot(tmp_path):
    from generativeaiexamples_tpu.models import hf_loader

    with pytest.raises(ValueError, match="'ouro' has no tensor-name map"):
        hf_loader.load_llama(_snapshot(tmp_path, model_type="ouro",
                                       total_ut_steps=4))


def test_stream_load_llama_refuses_a_model_type_it_has_no_names_for(tmp_path):
    from generativeaiexamples_tpu.models import hf_loader

    path = _snapshot(tmp_path, model_type="some_other_decoder")
    with pytest.raises(ValueError, match="refusing to load it as a Llama"):
        hf_loader.stream_load_llama(path, llama.LlamaConfig.tiny())
    # a looped configuration handed in beside a Llama-named snapshot
    with pytest.raises(ValueError, match="no tensor-name map for a looped"):
        hf_loader.stream_load_llama(_snapshot(tmp_path, model_type="llama"),
                                    CFG)
