"""State-space layers beside attention (models/hybrid_ssm.py,
serving/ssm_state_update.py, kv_cache.HybridPool) at a tiny size on the
CPU, seeded weights, against the benchmark's plain reference
(benchmark/architectures/granitemoehybrid.py, which shares no code with
the program and runs the recurrence token by token): logits, not tokens."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.architectures import granitemoehybrid as ref
from benchmark.tests.test_granitemoehybrid import tiny_file
from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import hybrid_ssm as hs
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import engine_model as em
from generativeaiexamples_tpu.serving import memory_plan
from generativeaiexamples_tpu.serving import ssm_state_update as upd
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.serving.kv_cache import (
    HybridPool, PagePool, QuantPagePool)

PS = 8


def config_file(**over):
    """The benchmark tests' tiny configuration file in the source's keys
    (mamba, attention, mamba; 8 experts, 3 a token), with pages of 8 and
    a scan in chunks of 8."""
    c = tiny_file()
    c["serving"].update(kv_dtype="float32", n_pages=48)
    c["serving"]["engine"].update(max_seq_len=64, page_size=PS,
                                  prefill_buckets=[16, 32])
    c.update(over)
    return c


FILE = config_file()
CFG = ref.model_config(FILE)


@pytest.fixture(scope="module")
def params():
    return hs.init_params_on_device(CFG, 7, quantize=True)


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n).astype(np.int32)


# -- the program's forward against the plain reference ----------------------

@pytest.mark.parametrize("n", [5, 16, 29])
def test_forward_is_the_references_sequential_pass(params, n):
    """float32 activations over int8 weights: what is left is the order
    of float32 sums (a chunked scan against a loop over tokens), so the
    logits agree to 1e-4 of the largest and every top-3 set agrees."""
    ids = prompt(n, seed=n)
    want, states, choice = ref.reference_forward(FILE, params, ids)
    got, mine = hs.forward(params, CFG, jnp.asarray(ids)[None],
                           use_pallas=False)
    top = float(np.abs(want).max())
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() / top < 1e-4
    assert np.array_equal(np.sort(np.asarray(mine)[:, 0], -1),
                          np.sort(np.asarray(choice), -1))
    # and the states a decode step would continue from are the loop's
    _, _, mine_states, _, _ = hs.walk_prompt(params, CFG,
                                             jnp.asarray(ids)[None])
    np.testing.assert_allclose(mine_states[:, 0], states, rtol=1e-4,
                               atol=1e-7)


def test_the_tied_head_is_the_embedding(params):
    """int8 weights: the head is E^T quantised a vocabulary row."""
    e = np.asarray(params["tok_emb"], np.float32)
    head = np.asarray(params["lm_head"].q, np.float32) \
        * np.asarray(params["lm_head"].s)[None, :]
    assert np.abs(head - e.T).max() <= np.abs(e).max() / 127
    assert "lm_head" not in hs.init_params_on_device(CFG, 7)


# -- the chunked scan against the sequential recurrence ---------------------

def _recurrence(x, Bm, Cm, step, log_a, n):
    """The plain loop over the first n tokens of one row."""
    H, P = x.shape[1:]
    S = np.zeros((H, P, Bm.shape[-1]), np.float64)
    ys = []
    for t in range(n):
        S = np.exp(log_a[t])[:, None, None] * S \
            + (step[t][:, None] * x[t])[:, :, None] * Bm[t][None, None, :]
        ys.append((S * Cm[t][None, None, :]).sum(-1))
    return np.stack(ys), S


@pytest.mark.parametrize("S,lengths", [(24, (24, 13)), (32, (1, 19)),
                                       (21, (21, 8)), (8, (3, 8))],
                         ids=["whole-chunks", "one-token", "ragged-S",
                              "one-chunk"])
def test_chunked_scan_is_the_sequential_recurrence(S, lengths):
    """Lengths that are no multiple of the chunk (8), padded rows: the
    outputs of the real positions and the state after each row's LAST
    REAL token are the loop's; the padding advances nothing."""
    rng = np.random.default_rng(S)
    H, P, N = CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state
    x = rng.normal(size=(2, S, H, P)).astype(np.float32)
    Bm = rng.normal(size=(2, S, N)).astype(np.float32)
    Cm = rng.normal(size=(2, S, N)).astype(np.float32)
    step = rng.uniform(0.001, 0.3, (2, S, H)).astype(np.float32)
    log_a = -step * rng.uniform(1, 16, (H,)).astype(np.float32)
    y, state = hs.ssm_scan(CFG, *map(jnp.asarray, (x, Bm, Cm, step, log_a)),
                           jnp.asarray(lengths, jnp.int32))
    assert y.shape == x.shape
    for b, n in enumerate(lengths):
        want_y, want_s = _recurrence(x[b], Bm[b], Cm[b], step[b], log_a[b],
                                     n)
        np.testing.assert_allclose(y[b, :n], want_y, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(state[b], want_s, rtol=2e-4, atol=2e-5)


def test_the_convolutions_tail_is_the_last_three_real_inputs(params):
    w = hs.take_layer(params["ssm"], 0)
    xbc = jax.random.normal(jax.random.PRNGKey(0), (3, 16, CFG.conv_width))
    _, tail = hs.conv_prompt(CFG, xbc, w, jnp.asarray([16, 5, 2]))
    np.testing.assert_array_equal(tail[0], xbc[0, 13:16])
    np.testing.assert_array_equal(tail[1], xbc[1, 2:5])
    # zeros before the sequence
    np.testing.assert_array_equal(tail[2, 0], jnp.zeros(CFG.conv_width))
    np.testing.assert_array_equal(tail[2, 1:], xbc[2, :2])
    # one more token through conv_step is the prompt form one longer
    full, _ = hs.conv_prompt(CFG, xbc[:1], w, jnp.asarray([16]))
    _, tail15 = hs.conv_prompt(CFG, xbc[:1], w, jnp.asarray([15]))
    one, new_tail = hs.conv_step(CFG, xbc[:1, 15], tail15.transpose(1, 0, 2),
                                 w)
    np.testing.assert_allclose(one[0], full[0, 15], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new_tail[:, 0], xbc[0, 13:16])


# -- the state-update kernel ------------------------------------------------

@pytest.mark.parametrize("live", [(1, 1, 1, 1, 1), (0, 1, 0, 1, 1),
                                  (0, 0, 0, 0, 1), (0, 0, 0, 0, 0)],
                         ids=["all", "some", "last", "none"])
def test_state_update_kernel_is_its_xla_form_in_place(live):
    """Interpreted: the live slots' states and outputs are the XLA
    form's, an idle slot's state is bit for bit what it was (its block is
    never fetched nor written), and another layer's rows are untouched."""
    L, B, H, P, N = 3, 5, 8, 16, 128
    rng = np.random.default_rng(sum(live))
    state = jnp.asarray(rng.normal(size=(L, B, H, P, N)), jnp.float32)
    active = jnp.asarray(live, bool)
    step = jnp.asarray(rng.uniform(0.001, 0.1, (B, H)), jnp.float32)
    log_a = -step * 4.0
    x = jnp.asarray(rng.normal(size=(B, H, P)), jnp.float32)
    Bv = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    Cv = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    want_s, want_y = upd.ssm_state_update(state, 1, active, step, log_a, x,
                                          Bv, Cv, use_pallas=False)
    a = jnp.exp(log_a)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    got_s, got_y = upd.ssm_state_update_pallas(
        state, 1, order, jnp.sum(active, dtype=jnp.int32).reshape(1),
        jnp.broadcast_to(a[..., None], a.shape + (N,)), x * step[..., None],
        Bv, Cv, interpret=True)
    idle = ~np.asarray(active)
    np.testing.assert_array_equal(np.asarray(got_s)[1, idle],
                                  np.asarray(state)[1, idle])
    np.testing.assert_array_equal(got_s[0], state[0])
    np.testing.assert_array_equal(got_s[2], state[2])
    np.testing.assert_allclose(got_s, want_s, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y)[~idle],
                               np.asarray(want_y)[~idle], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(want_y)[idle].any()


def test_kernel_update_reads_the_backend_and_the_shape():
    ok = jnp.zeros((1, 1, 2, 8, 128))
    assert upd.kernel_update(ok, True) and not upd.kernel_update(ok, False)
    assert not upd.kernel_update(ok)  # the CPU
    assert not upd.kernel_update(jnp.zeros((1, 1, 2, 8, 16)), True)


# -- prefill then decode through both pools ---------------------------------

def _paged(params, ids, n_prompt, dtype, slot=2):
    """Prefill ids[:n_prompt] (a group of 2, one real row, its state to
    decode slot `slot`), then every later token through decode_step and
    both pools: logits at positions n_prompt - 1 .. len(ids) - 1."""
    def fresh():
        return PagePool.zeros(CFG, 24, PS, dtype=jnp.dtype(dtype), slots=4)
    bucket = 16 if n_prompt <= 16 else 32
    toks = np.zeros((2, bucket), np.int32)
    toks[0, :n_prompt] = ids[:n_prompt]
    rows = np.zeros((2, bucket // PS), np.int32)
    rows[0] = 1 + np.arange(bucket // PS)
    table = np.zeros((4, 8), np.int32)
    table[slot] = 1 + np.arange(8)
    logits, pool = em.prefill_step(
        params, CFG, fresh(), jnp.asarray(toks[:1]), jnp.int32(n_prompt),
        jnp.asarray(rows[0]), False, state_slot=jnp.int32(slot))
    out = [np.asarray(logits)]
    first, pool2 = em.prefill_batch_step(
        params, CFG, fresh(), jnp.asarray(toks),
        jnp.asarray([n_prompt, 1], jnp.int32), jnp.asarray(rows),
        jnp.zeros(2), jnp.ones(2), jnp.zeros(2, jnp.int32),
        jax.random.PRNGKey(0), False,
        state_slots=jnp.asarray([slot, 4], jnp.int32))  # 4: dropped
    assert int(first[0]) == int(np.argmax(out[0]))
    np.testing.assert_allclose(pool2.state, pool.state, atol=1e-6)
    np.testing.assert_allclose(pool2.tail, pool.tail, atol=1e-5)
    others = [s for s in range(4) if s != slot]
    assert not np.asarray(pool.state)[:, others].any()
    for i in range(n_prompt, len(ids)):
        cur = np.zeros((4,), np.int32)
        cur[slot] = ids[i]
        ln = np.ones((4,), np.int32)
        ln[slot] = i + 1
        logits, pool = em.decode_step(params, CFG, pool, jnp.asarray(cur),
                                      jnp.asarray(table), jnp.asarray(ln),
                                      False)
        out.append(np.asarray(logits[slot]))
    return np.stack(out)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("int8", 2e-2)])
@pytest.mark.parametrize("n_prompt,n_new", [(11, 9), (16, 3), (23, 12)])
def test_prefill_then_decode_through_both_pools_is_the_references_one_pass(
        params, n_prompt, n_new, dtype, tol):
    """A prompt padded to its bucket, then token by token through the
    page pool (the attention layer's row) and the per-slot state pool,
    against the reference's ONE forward pass of the whole sequence. A
    float32 page pool leaves the order of float32 sums; an int8 one its
    K and V rounding."""
    ids = prompt(n_prompt + n_new, seed=n_prompt)
    want = np.asarray(ref.reference_logits(FILE, params, ids))
    got = _paged(params, ids, n_prompt, dtype)
    top = float(np.abs(want).max())
    assert np.abs(got - want[n_prompt - 1:]).max() / top < tol


def test_a_reference_without_the_skip_term_disagrees(params):
    """The comparison can tell: a reference told D = 0 gives other
    logits."""
    ids = prompt(20, seed=5)
    want = np.asarray(ref.reference_logits(FILE, params, ids))
    ssm = dict(params["ssm"], D=params["ssm"]["D"] * 0.0)
    other = np.asarray(ref.reference_logits(FILE, dict(params, ssm=ssm), ids))
    assert np.abs(want - other).max() / np.abs(want).max() > 0.05


def test_an_idle_slot_is_left_alone_by_a_decode_block(params):
    """decode_multi_step with one live slot of four: the idle slots'
    states and tails are bit for bit what they were."""
    pool = PagePool.zeros(CFG, 24, PS, dtype=jnp.float32, slots=4)
    assert isinstance(pool, HybridPool)
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
    # the block carries the experts' pair counts below the token rows:
    # one live slot, 3 choices in each of the 3 layers a step
    load = np.asarray(block)[4:, 1:]
    assert load.shape == (3 * 8, 2) and (load.sum(axis=0) == 9).all()


# -- the experts --------------------------------------------------------------

def test_every_expert_is_held_and_the_gates_are_a_softmax_over_the_chosen(
        params):
    """8 of 8 experts held, the 3 largest logits a token, gates = softmax
    over those 3: the grouped matmul's combine against a dense sum over
    every expert with a gate of zero where it was not chosen."""
    w = hs.take_layer(hs.split_experts(params["ffn"])[0], 1)
    _, experts = hs.split_experts(params["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(4), (19, CFG.dim), jnp.float32)
    y, counts, idx = hs.moe_branch(CFG, h, w, experts, 1, False)
    assert int(counts.sum()) == 19 * 3 and counts.shape == (8,)
    logits = np.asarray(h) @ np.asarray(w["router"], np.float32)
    top = np.sort(np.argsort(-logits, -1)[:, :3], -1)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1), top)
    gates = np.zeros_like(logits)
    for t in range(19):
        e = np.exp(logits[t, top[t]] - logits[t, top[t]].max())
        gates[t, top[t]] = e / e.sum()
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)

    def dense(q):
        return np.asarray(q.q, np.float32) * np.asarray(q.s)[..., None, :]

    def glu(x, w_in, w_out):
        gu = x @ w_in
        m = gu.shape[-1] // 2
        return (np.asarray(jax.nn.silu(gu[:, :m])) * gu[:, m:]) @ w_out

    hn = np.asarray(h)
    want = glu(hn, dense(w["ws_in"]), dense(w["ws_out"]))
    gate_up, down = dense(experts["we_gate_up"])[1], dense(
        experts["we_down"])[1]
    for e in range(8):
        want = want + gates[:, e:e + 1] * glu(hn, gate_up[e], down[e])
    np.testing.assert_allclose(y, want, rtol=2e-3, atol=2e-4)
    # a masked token takes no pair
    mask = jnp.arange(19) < 7
    _, counts, _ = hs.moe_branch(CFG, h, w, experts, 1, False, mask)
    assert int(counts.sum()) == 7 * 3


# -- the engine ---------------------------------------------------------------

def _engine(params, **over):
    from benchmark.harness import system
    from benchmark.harness.bench_tokenizer import WordTokenizer

    ecfg = dataclasses.replace(system.engine_config(FILE), **over)
    return LLMEngine(params, CFG, WordTokenizer(512), ecfg, n_pages=48)


def _greedy(params, ids, n):
    seq = list(ids)
    for _ in range(n):
        logits, _ = hs.forward(params, CFG, jnp.asarray([seq], jnp.int32),
                               use_pallas=False)
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(ids):]


def test_the_engine_serves_the_forwards_tokens_and_counts(params):
    eng = _engine(params)
    assert isinstance(eng.pool, HybridPool)
    eng.start()
    try:
        ids = [int(t) for t in prompt(13, seed=9)]
        served = [ev["token_id"] for ev in eng.generate_stream(
            ids, max_new_tokens=10, temperature=0.0)]
    finally:
        eng.stop()
    assert served == _greedy(params, ids, 10)
    snap = eng.metrics.snapshot()
    assert snap["experts_held"] == 8 and snap["kv_cache_rows"] == 1
    assert snap["kv_bytes_per_token"] == 2 * 2 * 16 * 4  # K and V, float32
    assert snap["ssm_layers"] == 2
    assert snap["ssm_state_bytes_per_slot"] == 2 * (
        8 * 16 * 16 * 4 + 3 * (8 * 16 + 32) * 4)
    assert snap["ssm_slot_writes"] == 1
    assert snap["ssm_steps_kernel"] == 0  # off the chip: the XLA form
    steps = snap["decode_steps"]
    # one live slot: 3 choices in each of the 3 layers, all held here
    assert snap["moe_pairs_routed"] == steps * 3 * 3
    assert 0 < snap["moe_pairs_local"] <= snap["moe_pairs_routed"]
    loads = [e for e in eng.flight.snapshot_events() if e["kind"] == 19]
    assert loads and sum(e["a"] for e in loads) > 0
    assert em.expert_load_rows(CFG) == 3 * 8
    assert em.expert_load_rows(llama.LlamaConfig.tiny()) == 0


def test_a_reused_slot_gives_what_a_fresh_engine_gives(params):
    """One slot, two requests one after the other: the second finds its
    predecessor's state and tail in the slot's rows and must not see
    them (a prefill writes them whole)."""
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
    fresh = _engine(params, max_batch_size=1)
    fresh.start()
    try:
        alone = [ev["token_id"] for ev in fresh.generate_stream(
            b, max_new_tokens=6, temperature=0.0)]
    finally:
        fresh.stop()
    assert second == alone == _greedy(params, b, 6)
    assert first == _greedy(params, a, 6)


def test_a_llamas_engine_reports_the_state_counters_as_zero():
    cfg = llama.LlamaConfig.tiny()
    from benchmark.harness.bench_tokenizer import WordTokenizer
    eng = LLMEngine(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                    WordTokenizer(256), EngineConfig(
                        max_batch_size=2, max_seq_len=32, page_size=8,
                        prefill_buckets=(16,)))
    snap = eng.metrics.snapshot()
    assert (snap["ssm_state_bytes_per_slot"], snap["ssm_layers"],
            snap["ssm_slot_writes"], snap["ssm_steps_kernel"]) == (0, 0, 0, 0)
    from generativeaiexamples_tpu.serving.served_models import served
    assert served(cfg).prefill is em.llama_prefill
    assert cfg.experts_held == 0
    from generativeaiexamples_tpu.serving import fleet
    assert {"ssm_slot_writes", "ssm_steps_kernel"} <= set(fleet.counter_keys())


@pytest.mark.parametrize("lane,over", [
    ("speculative_k", dict(speculative_k=2)),
    ("step_plans", dict(step_plans=True)),
    ("fused_prefill", dict(fused_prefill=True)),
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_pager", dict(prefix_cache=True, kv_pager=True)),
    ("qos_preempt_prefill", dict(qos=True)),
])
def test_lanes_that_would_have_to_carry_the_state_are_refused_by_name(
        params, lane, over):
    with pytest.raises(ValueError, match=f"engine.{lane}"):
        _engine(params, **over)


def test_a_mesh_is_refused_by_name(params):
    from generativeaiexamples_tpu.serving.engine import (
        _refuse_unwalked_lanes)
    with pytest.raises(ValueError, match="engine.mesh.*state-space heads"):
        _refuse_unwalked_lanes(CFG, EngineConfig(), mesh=object())
    with pytest.raises(ValueError, match="engine.multihost"):
        _refuse_unwalked_lanes(CFG, EngineConfig(multihost=True))
    _refuse_unwalked_lanes(CFG, EngineConfig(qos=True,
                                             qos_preempt_prefill=False))


def test_a_prompt_past_the_largest_bucket_is_refused(params):
    from generativeaiexamples_tpu.serving.engine import (
        GenRequest, PromptTooLongError)
    eng = _engine(params)
    with pytest.raises(PromptTooLongError):
        eng.submit(GenRequest(prompt_ids=list(range(1, 40))))


def test_memory_plan_counts_the_state_pool(params):
    ecfg = dataclasses.replace(EngineConfig(), page_size=PS,
                               kv_dtype="int8", max_seq_len=64,
                               max_batch_size=4, prefill_buckets=(16,))
    pool = PagePool.zeros(CFG, 5, PS, dtype=jnp.int8, slots=4)
    assert isinstance(pool.pages, QuantPagePool)
    page = memory_plan.pool_page_bytes_per_device(CFG, ecfg, {})
    assert page == sum(x.nbytes for x in jax.tree.leaves(pool.pages)) // 5
    from generativeaiexamples_tpu.serving.served_models import served
    ((name, state, _),) = served(CFG).fixed_pools(CFG, ecfg)
    assert name == "state_pool"
    assert state == pool.state.nbytes + pool.tail.nbytes \
        == 4 * CFG.recurrent_state.bytes_per_slot
    tiny = llama.LlamaConfig.tiny()
    assert served(tiny).fixed_pools(tiny, ecfg) == ()
    weights = memory_plan.weight_bytes_per_device(CFG, {}, quantize=True)
    assert weights == sum(x.nbytes for x in jax.tree.leaves(params))
    with pytest.raises(memory_plan.MemoryPlanError, match="tensor"):
        memory_plan.weight_bytes_per_device(CFG, {"tensor": 2}, quantize=True)


def test_hf_loader_refuses_a_granitemoehybrid_snapshot(tmp_path):
    from generativeaiexamples_tpu.models import hf_loader
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in FILE.items() if k != "serving"}))
    with pytest.raises(ValueError, match="state-space"):
        hf_loader.llama_config_from_hf(str(tmp_path))
    with pytest.raises(ValueError,
                       match="'granitemoehybrid' has.*no tensor-name map"):
        hf_loader.load_llama(str(tmp_path))
