"""Delta-rule linear attention beside latent attention AS SERVED
(serving/served_linear.py over a kv_cache.HybridPool whose pages are a
LatentPagePool) at a tiny size on the CPU, seeded weights: prefill then
decode through both pools against the benchmark's plain reference's ONE
forward pass, the engine end to end, the refusals and the memory plan."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import kimilinear as ref
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import linear_attn_moe as lam
from generativeaiexamples_tpu.serving import engine_model as em
from generativeaiexamples_tpu.serving import kda_state_update as upd
from generativeaiexamples_tpu.serving import memory_plan, served_linear
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import (
    HybridPool, LatentPagePool, PagePool)
from generativeaiexamples_tpu.serving.served_models import (
    _ENTRY_MODULES, served)
from test_linear_attn_moe import CFG, CHUNK, FILE, PS, miss, prompt


@pytest.fixture(scope="module")
def params():
    return lam.init_params_on_device(CFG, 7, quantize=True)


def fresh_pool():
    return PagePool.zeros(CFG, 24, PS, dtype=jnp.float32, slots=4)


# -- prefill then decode through both pools ---------------------------------

def _paged(params, ids, n_prompt, slot=2, decode=None, after_prefill=None):
    """Prefill ids[:n_prompt] (its state to decode slot `slot`), then every
    later token through `decode` (decode_step) and both pools: logits at
    positions n_prompt - 1 .. len(ids) - 1."""
    bucket = 16 if n_prompt <= 16 else 32
    toks = np.zeros((2, bucket), np.int32)
    toks[0, :n_prompt] = ids[:n_prompt]
    rows = np.zeros((2, bucket // PS), np.int32)
    rows[0] = 1 + np.arange(bucket // PS)
    table = np.zeros((4, 8), np.int32)
    table[slot] = 1 + np.arange(8)
    logits, pool = em.prefill_step(
        params, CFG, fresh_pool(), jnp.asarray(toks[:1]),
        jnp.int32(n_prompt), jnp.asarray(rows[0]), False,
        state_slot=jnp.int32(slot))
    out = [np.asarray(logits)]
    if decode is None:  # the served programs, and the batched prefill
        first, pool2 = em.prefill_batch_step(
            params, CFG, fresh_pool(), jnp.asarray(toks),
            jnp.asarray([n_prompt, 1], jnp.int32), jnp.asarray(rows),
            jnp.zeros(2), jnp.ones(2), jnp.zeros(2, jnp.int32),
            jax.random.PRNGKey(0), False,
            state_slots=jnp.asarray([slot, 4], jnp.int32))  # 4: dropped
        assert int(first[0]) == int(np.argmax(out[0]))
        np.testing.assert_allclose(pool2.state, pool.state, atol=1e-5)
        np.testing.assert_allclose(pool2.tail, pool.tail, atol=1e-5)
        others = [s for s in range(4) if s != slot]
        assert not np.asarray(pool.state)[:, others].any()
        decode = lambda p, pool, cur, tb, ln: em.decode_step(  # noqa: E731
            p, CFG, pool, cur, tb, ln, False)
    if after_prefill is not None:
        pool = after_prefill(pool)
    for i in range(n_prompt, len(ids)):
        cur = np.zeros((4,), np.int32)
        cur[slot] = ids[i]
        ln = np.ones((4,), np.int32)
        ln[slot] = i + 1
        logits, pool = decode(params, pool, jnp.asarray(cur),
                              jnp.asarray(table), jnp.asarray(ln))
        out.append(np.asarray(logits[slot]))
    return np.stack(out)


# a prompt under a chunk, at two, and over several and no multiple of it
@pytest.mark.parametrize("n_prompt,n_new", [
    (5, 6), (2 * CHUNK, 3), (2 * CHUNK + 7, 9)])
def test_prefill_then_decode_through_both_pools_is_the_references_one_pass(
        params, n_prompt, n_new):
    """A prompt padded to its bucket, then token by token through the
    latent page pool (the MLA layers' rows) and the per-slot state pool
    (the KDA layers'), against the reference's ONE forward pass of the
    whole sequence: float32 everywhere, so what is left is the order of
    float32 sums."""
    ids = prompt(n_prompt + n_new, seed=n_prompt)
    want = np.asarray(ref.reference_logits(FILE, params, ids))
    got = _paged(params, ids, n_prompt)
    assert miss(got, want[n_prompt - 1:]) < 1e-4


def _retraced(update):
    """`served_linear.decode_once` under a fresh jit, traced while
    `kda_state_update` is `update` (decode_step's own trace is cached)."""
    def step(p, pool, cur, table, ln):
        logits, pool, _, _ = served_linear.decode_once(
            p, CFG, pool, cur, table, ln, False)
        return logits, pool
    jitted = jax.jit(step)

    def run(*a):
        real, upd.kda_state_update = upd.kda_state_update, update
        try:
            return jitted(*a)
        finally:
            upd.kda_state_update = real
    return run


def test_a_bf16_state_a_scalar_decay_and_a_dropped_state_all_miss(params):
    """The comparison can tell: the decode path with a state rounded to
    bfloat16 at every step, with a decay that is one scalar a head, and
    with the prompt's state dropped (a slot that kept zeros) must each
    MISS the reference; the same path unpatched does not."""
    n_prompt, n_new = 2 * CHUNK + 7, 9
    ids = prompt(n_prompt + n_new, seed=n_prompt)
    want = np.asarray(ref.reference_logits(FILE, params, ids))[n_prompt - 1:]
    real = upd.kda_state_update

    def to_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def bf16_state(state, layer, *a, **kw):
        state, o = real(state, layer, *a, **kw)
        return state.at[layer].set(to_bf16(state[layer])), o

    def scalar_decay(state, layer, active, g, *a, **kw):
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        return real(state, layer, active, g, *a, **kw)

    def rounded(pool):
        return dataclasses.replace(pool, state=to_bf16(pool.state))

    def dropped(pool):
        return dataclasses.replace(pool, state=jnp.zeros_like(pool.state))

    assert miss(_paged(params, ids, n_prompt, decode=_retraced(real)),
                want) < 1e-4
    bf16 = miss(_paged(params, ids, n_prompt, decode=_retraced(bf16_state),
                       after_prefill=rounded), want)
    assert bf16 > 1e-3, bf16  # ten times the right program's, and more
    assert miss(_paged(params, ids, n_prompt,
                       decode=_retraced(scalar_decay)), want) > 0.02
    assert miss(_paged(params, ids, n_prompt, decode=_retraced(real),
                       after_prefill=dropped), want) > 0.02


def test_an_idle_slot_is_left_alone_by_a_decode_block(params):
    """decode_multi_step with one live slot of four: the idle slots'
    states and tails are bit for bit what they were."""
    pool = fresh_pool()
    assert isinstance(pool, HybridPool)
    assert isinstance(pool.pages, LatentPagePool)
    key = jax.random.PRNGKey(3)
    pool = dataclasses.replace(
        pool, state=jax.random.normal(key, pool.state.shape),
        tail=jax.random.normal(key, pool.tail.shape))
    before_s, before_t = np.asarray(pool.state), np.asarray(pool.tail)
    table = np.zeros((4, 8), np.int32)
    table[1] = 1 + np.arange(8)
    active = np.asarray([False, True, False, False])
    block, _, pool = em.decode_multi_step(
        params, CFG, pool, jnp.asarray([0, 5, 0, 0], jnp.int32),
        jnp.asarray(table), jnp.asarray([1, 4, 1, 1], jnp.int32),
        jnp.asarray(active), jnp.zeros(4), jnp.ones(4),
        jnp.zeros(4, jnp.int32), key, 2, False,
        sampling_flags=(True, False, False))
    idle = ~active
    np.testing.assert_array_equal(np.asarray(pool.state)[:, idle],
                                  before_s[:, idle])
    np.testing.assert_array_equal(np.asarray(pool.tail)[:, :, idle],
                                  before_t[:, :, idle])
    assert not np.array_equal(np.asarray(pool.state)[:, 1], before_s[:, 1])
    # the block carries the held experts' pair counts below the token rows:
    # one live slot, at most 4 choices in each of the 4 expert layers a step
    load = np.asarray(block)[4:, 1:]
    assert load.shape == (4 * 4, 2) and (load.sum(axis=0) <= 16).all()


# -- the engine ---------------------------------------------------------------

def _engine(params, **over):
    from benchmark.harness import system
    from benchmark.harness.bench_tokenizer import WordTokenizer

    ecfg = dataclasses.replace(system.engine_config(FILE), **over)
    return LLMEngine(params, CFG, WordTokenizer(512), ecfg, n_pages=48)


@jax.jit
def _padded_forward(params, tokens, lengths):
    return lam.forward(params, CFG, tokens, lengths=lengths,
                       use_pallas=False)[0]


def _greedy(params, ids, n):
    """The forward's greedy continuation (one program: the sequence padded
    to 32, which a causal model's earlier positions do not see)."""
    seq = list(ids)
    for _ in range(n):
        toks = np.zeros((1, 32), np.int32)
        toks[0, :len(seq)] = seq
        logits = _padded_forward(params, jnp.asarray(toks),
                                 jnp.asarray([len(seq)], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(ids):]


def test_the_entry_is_one_line_and_the_engine_names_no_architecture():
    assert sum(m.endswith(".served_linear") for m in _ENTRY_MODULES) == 1
    entry = served(CFG)
    assert entry.prefill is served_linear.prefill and entry.state_slots
    assert not entry.long_prompts and not entry.direct_qkv
    import inspect

    from generativeaiexamples_tpu.serving import engine
    for module in (engine, em):
        text = inspect.getsource(module)
        assert "served_linear" not in text and "linear_attn" not in text
        assert "kda" not in text.lower()


def test_the_engine_serves_the_forwards_tokens_and_counts(params):
    eng = _engine(params)
    assert isinstance(eng.pool, HybridPool)
    assert isinstance(eng.pool.pages, LatentPagePool)
    eng.start()
    try:
        ids = [int(t) for t in prompt(13, seed=9)]
        served_ids = [ev["token_id"] for ev in eng.generate_stream(
            ids, max_new_tokens=10, temperature=0.0)]
    finally:
        eng.stop()
    assert served_ids == _greedy(params, ids, 10)
    # every page is back (page 0 is the sink)
    assert eng.allocator.n_free == eng.allocator.n_pages - 1
    snap = eng.metrics.snapshot()
    assert snap["experts_held"] == 4 and snap["kv_cache_rows"] == 2
    assert snap["kv_bytes_per_token"] == 2 * 128 * 4  # 40 values, 128 lanes
    assert snap["ssm_layers"] == 3
    assert snap["ssm_state_bytes_per_slot"] == 3 * (
        4 * 16 * 16 * 4 + 3 * 192 * 4) == CFG.recurrent_state.bytes_per_slot
    assert snap["ssm_slot_writes"] == 1
    assert snap["ssm_steps_kernel"] == 0  # off the chip: the XLA form
    steps = snap["decode_steps"]
    # one live slot: 4 choices in each of the 4 expert layers
    assert snap["moe_pairs_routed"] == steps * 4 * 4
    assert 0 <= snap["moe_pairs_local"] <= snap["moe_pairs_routed"]
    events = eng.flight.snapshot_events()
    assert [e for e in events if e["kind"] == 19]  # moe_load
    cache = [e for e in events if e["kind"] == 24]  # state_cache
    assert cache and all(13 <= e["a"] <= 24 for e in cache)
    rows = cache[-1]["a"] * 2 * 128 * 4
    assert cache[-1]["b"] == pytest.approx(
        rows / (rows + CFG.recurrent_state.bytes_per_slot))
    assert em.expert_load_rows(CFG) == 4 * 4


def test_a_reused_slot_gives_what_a_fresh_engine_gives(params):
    """One slot, two requests one after the other: the second finds its
    predecessor's state and tail in the slot's rows and must not see them
    (a prefill writes them whole)."""
    a = [int(t) for t in prompt(21, seed=1)]
    b = [int(t) for t in prompt(9, seed=2)]
    eng = _engine(params, max_batch_size=1)
    eng.start()
    try:
        first = [ev["token_id"] for ev in eng.generate_stream(
            a, max_new_tokens=6, temperature=0.0)]
        assert np.asarray(eng.pool.state).any()
        second = [ev["token_id"] for ev in eng.generate_stream(
            b, max_new_tokens=6, temperature=0.0)]
    finally:
        eng.stop()
    assert eng.metrics.snapshot()["ssm_slot_writes"] == 2
    assert eng.allocator.n_free == eng.allocator.n_pages - 1
    assert second == _greedy(params, b, 6)
    assert first == _greedy(params, a, 6)


def test_another_models_engine_reports_the_state_cache_as_absent():
    from benchmark.harness.bench_tokenizer import WordTokenizer
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving import fleet, flight

    cfg = llama.LlamaConfig.tiny()
    eng = LLMEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                    WordTokenizer(256), EngineConfig(
                        max_batch_size=2, max_seq_len=32, page_size=8,
                        prefill_buckets=(16,)))
    snap = eng.metrics.snapshot()
    assert (snap["ssm_state_bytes_per_slot"], snap["ssm_layers"],
            snap["ssm_slot_writes"], snap["ssm_steps_kernel"]) == (0, 0, 0, 0)
    assert flight.EVENT_NAMES[flight.EV_STATE_CACHE] == "state_cache"
    assert flight.EV_STATE_CACHE == 24
    # the state pool's keys stand once, whichever entries name them
    keys = fleet.counter_keys()
    assert list(keys).count("ssm_slot_writes") == 1
    assert list(keys).count("ssm_steps_kernel") == 1


# -- the refusals and the plan -------------------------------------------------

@pytest.mark.parametrize("lane,over", [
    ("speculative_k", dict(speculative_k=2)),
    ("step_plans", dict(step_plans=True)),
    ("fused_prefill", dict(fused_prefill=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_pager", dict(prefix_cache=True, kv_pager=True)),
    ("qos_preempt_prefill", dict(qos=True)),
    ("kv_dtype int8", dict(kv_dtype="int8")),
])
def test_lanes_of_the_latent_row_and_of_the_state_are_refused_by_name(
        params, lane, over):
    with pytest.raises(ValueError, match=f"engine.{lane}") as e:
        _engine(params, **over)
    assert "recurrent state (3 linear-attention layers" in str(e.value)
    assert "latent row of 40 values a token in 2 layers" in str(e.value)


def test_a_mesh_and_the_multi_host_replay_are_refused_by_name():
    from generativeaiexamples_tpu.serving.engine import (
        _refuse_unwalked_lanes)
    with pytest.raises(ValueError, match="engine.mesh.*latent row"):
        _refuse_unwalked_lanes(CFG, EngineConfig(kv_dtype="bfloat16"),
                               mesh=object())
    with pytest.raises(ValueError, match="engine.multihost"):
        _refuse_unwalked_lanes(CFG, EngineConfig(kv_dtype="bfloat16",
                                                 multihost=True))
    _refuse_unwalked_lanes(CFG, EngineConfig(
        kv_dtype="bfloat16", qos=True, qos_preempt_prefill=False))


def test_a_prompt_past_the_largest_bucket_is_refused(params):
    from generativeaiexamples_tpu.serving.engine import (
        GenRequest, PromptTooLongError)
    eng = _engine(params)
    with pytest.raises(PromptTooLongError):
        eng.submit(GenRequest(prompt_ids=list(range(1, 40))))


def test_a_pool_needs_its_slots_and_has_no_int8_form():
    with pytest.raises(ValueError, match="slots"):
        PagePool.zeros(CFG, 5, PS, dtype=jnp.float32)
    with pytest.raises(ValueError, match="no int8 form"):
        PagePool.zeros(CFG, 5, PS, dtype=jnp.int8, slots=2)


def test_memory_plan_counts_state_tail_and_latent_rows(params):
    ecfg = dataclasses.replace(EngineConfig(), page_size=PS,
                               kv_dtype="float32", max_seq_len=64,
                               max_batch_size=4, prefill_buckets=(16,))
    pool = PagePool.zeros(CFG, 5, PS, dtype=jnp.float32, slots=4)
    page = memory_plan.pool_page_bytes_per_device(CFG, ecfg, {})
    assert page == pool.pages.c.nbytes // 5 == 2 * PS * 128 * 4
    entry = served(CFG)
    assert entry.token_bytes(CFG, ecfg, {}) == {"latent rows": 2 * 128 * 4}
    (state, tail) = entry.fixed_pools(CFG, ecfg)
    assert (state[0], tail[0]) == ("state_pool", "tail_pool")
    assert state[1] == pool.state.nbytes and tail[1] == pool.tail.nbytes
    assert state[1] + tail[1] == 4 * CFG.recurrent_state.bytes_per_slot
    weights = memory_plan.weight_bytes_per_device(CFG, {}, quantize=True)
    assert weights == sum(x.nbytes for x in jax.tree.leaves(params))
    with pytest.raises(memory_plan.MemoryPlanError, match="tensor"):
        memory_plan.weight_bytes_per_device(CFG, {"tensor": 2}, quantize=True)
    plan = memory_plan.plan_engine_memory(
        CFG, dataclasses.replace(ecfg, auto_pool_pages=True),
        hbm_bytes_per_device=64 << 20)
    text = plan.breakdown()
    for line in ("state_pool", "tail_pool", "kv_pool"):
        assert line in text, text
    assert plan.page_bytes_per_device == page


def test_hf_loader_refuses_a_kimi_linear_snapshot(tmp_path):
    import json

    from generativeaiexamples_tpu.models import hf_loader
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in FILE.items() if k != "serving"}))
    with pytest.raises(ValueError, match="linear-attention layers"):
        hf_loader.llama_config_from_hf(str(tmp_path))
    with pytest.raises(ValueError,
                       match="'kimi_linear' has.*no tensor-name map"):
        hf_loader.load_llama(str(tmp_path))
