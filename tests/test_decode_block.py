"""The length of a decode block (serving/decode_block.py): the ONE
function that chooses K, the step time it is chosen with, and an engine
on the CPU whose ledger intervals are faked so that a step reads 30 ms
without anybody sleeping."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import decode_block, fleet
from generativeaiexamples_tpu.serving.decode_block import (
    BLOCK_BUDGET_MS, StepTime, choose_k, round_to_warm)
from generativeaiexamples_tpu.serving.engine import (
    EngineMetrics, GenRequest, LLMEngine)
from generativeaiexamples_tpu.serving.flight import (
    PROG_DECODE, prometheus_text)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

WARM = frozenset({1, 2, 8})      # what warmup() compiles for K = 8
FULL = dict(live=64, slots=64, arrival_waiting=False)
EMPTY_SLOT = dict(live=60, slots=64, arrival_waiting=True)


def _k(configured=8, warm=WARM, live=64, slots=64, arrival_waiting=False,
       long_prefill_cap=0, step_ms=None, budget_ms=BLOCK_BUDGET_MS):
    return choose_k(configured, warm, live, slots, arrival_waiting,
                    long_prefill_cap, step_ms, budget_ms)


@pytest.mark.parametrize("case, kwargs, want", [
    # every slot live and nobody queued: K is the configured one at ANY
    # step time (Kimi-Linear's 222 ms blocks stay, and so do the window's
    # blocks of Keye's 13.1 ms and SmallThinker's 14.7 ms steps)
    ("full batch, fast step", dict(FULL, step_ms=12.9), 8),
    ("full batch, slow step", dict(FULL, step_ms=27.7), 8),
    ("full batch, absurd step", dict(FULL, step_ms=500.0), 8),
    ("full batch, 7.6 ms", dict(FULL, step_ms=7.6), 8),
    ("full batch, 11.75 ms", dict(FULL, step_ms=11.75), 8),
    ("full batch, 13.1 ms", dict(FULL, step_ms=13.1), 8),
    ("full batch, 14.7 ms", dict(FULL, step_ms=14.7), 8),
    # an empty slot and eight steps over the budget: the largest warm K
    # that fits, never under the short block (2 steps of 28.8 ms fit)
    ("empty slot, 28.8 ms", dict(EMPTY_SLOT, step_ms=28.8), 2),
    ("empty slot, 24.8 ms", dict(EMPTY_SLOT, step_ms=24.8), 2),
    ("empty slot, 28.8 ms, 4 is warm", dict(EMPTY_SLOT, step_ms=28.8,
                                            warm={1, 2, 4, 8}), 2),
    ("empty slot, 28.8 ms, no warm-up ran", dict(EMPTY_SLOT, step_ms=28.8,
                                                 warm=()), 2),
    # never below the short block on the budget's account, however slow
    ("empty slot, one step over", dict(EMPTY_SLOT, step_ms=200.0), 2),
    # the 12-ms-step models: 4 steps fit, 4 is not warm, so 2
    ("empty slot, 12.9 ms", dict(EMPTY_SLOT, step_ms=12.9), 2),
    ("empty slot, 11.75 ms", dict(EMPTY_SLOT, step_ms=11.75), 2),
    ("empty slot, 14.7 ms (a ramp)", dict(EMPTY_SLOT, step_ms=14.7), 2),
    ("empty slot, 12.9 ms, 4 is warm", dict(EMPTY_SLOT, step_ms=12.9,
                                            warm={1, 2, 4, 8}), 4),
    ("empty slot, 12.9 ms, no warm-up ran", dict(EMPTY_SLOT, step_ms=12.9,
                                                 warm=()), 4),
    # a model whose eight steps fit keeps them: the budget is what keeps
    # a FAST model's blocks long
    ("empty slot, at the budget (7.5 ms)", dict(
        EMPTY_SLOT, step_ms=BLOCK_BUDGET_MS / 8), 8),
    ("empty slot, 7.6 ms", dict(EMPTY_SLOT, step_ms=7.6), 2),
    ("empty slot, 3 ms", dict(EMPTY_SLOT, step_ms=3.0), 8),
    # no step time yet: unchanged
    ("empty slot, nothing landed", dict(EMPTY_SLOT, step_ms=None), 8),
    ("empty slot, budget infinite", dict(EMPTY_SLOT, step_ms=28.8,
                                         budget_ms=math.inf), 8),
    # the rules that were there still win where they applied
    ("low occupancy", dict(live=16, slots=64, step_ms=1.0), 2),
    ("low occupancy, slow step", dict(live=3, slots=64,
                                      arrival_waiting=True, step_ms=90.0), 2),
    ("long prefill caps at 2", dict(FULL, long_prefill_cap=2), 2),
    ("long prefill caps at 1", dict(EMPTY_SLOT, long_prefill_cap=1,
                                    step_ms=28.8), 1),
    ("long prefill cap over K", dict(FULL, long_prefill_cap=16), 8),
    ("cap of 4, 4 not warm", dict(FULL, long_prefill_cap=4), 2),
    # a configured K that is no power of two dispatches the one below
    ("configured 12, warm 8", dict(FULL, configured=12), 8),
    ("configured 6, nothing warm", dict(FULL, configured=6, warm=()), 4),
    ("configured 1", dict(EMPTY_SLOT, configured=1, warm={1},
                          step_ms=300.0), 1),
])
def test_choose_k(case, kwargs, want):
    got = _k(**kwargs)
    assert got == want, case
    warm = kwargs.get("warm", WARM)
    assert not warm or got in warm, case          # never a cold variant
    assert got & (got - 1) == 0, case             # a power of two


@pytest.mark.parametrize("step_ms", [None, 0.5, 7.5, 7.6, 11.75, 15.7, 28.8,
                                     70.0])
@pytest.mark.parametrize("arrival", [False, True])
@pytest.mark.parametrize("live", [1, 16, 17, 64])
@pytest.mark.parametrize("cap", [0, 1, 2, 4])
def test_choose_k_is_warm_never_longer_and_only_an_arrival_shortens(
        step_ms, arrival, live, cap):
    """Over the whole grid: the answer is a warm K, the step time can
    only SHORTEN it, only while an arrival can be waiting, and the page
    bound still rounds DOWN after it (`round_to_warm` of a smaller
    bound is a warm K no larger)."""
    got = _k(live=live, arrival_waiting=arrival, long_prefill_cap=cap,
             step_ms=step_ms)
    plain = _k(live=live, arrival_waiting=arrival, long_prefill_cap=cap)
    assert got in WARM and got <= plain
    if not arrival:
        assert got == plain
    if got < plain:
        assert got == 2 and plain * step_ms > BLOCK_BUDGET_MS
    for pages_allow in (1, 2, 3, 5, 8):
        bounded = round_to_warm(min(got, pages_allow), WARM)
        assert bounded in WARM and bounded <= min(got, pages_allow)


@pytest.mark.parametrize("step_ms", [24.2, 26.0, 27.7, 30.2])
@pytest.mark.parametrize("warm, at_125", [(WARM, 2), ({1, 2, 4, 8}, 4)])
def test_the_long_step_cells_keep_the_k_a_budget_of_125_gave_them(
        step_ms, warm, at_125):
    """Ouro's, A.X-K1's, granite's and Kimi-Linear's steps: with the warm
    set that warm-up compiles, two steps while a slot is empty under
    either budget, and the ceiling while none is. (Were K = 4 warm, 125
    gave them 4 and 60 gives 2: ROADMAP S2 (ii).)"""
    assert _k(**EMPTY_SLOT, warm=warm, step_ms=step_ms) == 2
    assert _k(**EMPTY_SLOT, warm=warm, step_ms=step_ms,
              budget_ms=125.0) == at_125
    assert _k(**FULL, warm=warm, step_ms=step_ms) == 8


def test_step_time_is_the_median_of_the_last_landed_blocks():
    st = StepTime()
    assert st.ms is None                      # the rule does not engage
    st.note(0.0, 8)                           # an unresolved row: no sample
    assert st.ms is None
    st.note(230.4, 8)
    assert st.ms == pytest.approx(28.8)
    for ran, k in ((57.6, 2), (58.0, 2), (4550.0, 8), (57.8, 2)):
        st.note(ran, k)                       # one stalled block of 4.55 s
    assert st.ms == pytest.approx(28.9)       # is a sample, not the answer
    for _ in range(decode_block.STEP_SAMPLES):
        st.note(100.0, 8)                     # ... and the old ones leave
    assert st.ms == pytest.approx(12.5)


# -- the engine, with the ledger's intervals faked --------------------------

TINY = llama.LlamaConfig.tiny()
STEP_S = 0.030      # 8 steps are 240 ms: over the budget; 2 are 60


def _serve(monkeypatch, budget_ms):
    """Four greedy streams on four slots, one of them 4 tokens long, so
    that a slot stands empty for most of the run. Every decode program's
    ledger row is rewritten as it resolves to read STEP_S a step."""
    monkeypatch.setattr(decode_block, "BLOCK_BUDGET_MS", budget_ms)
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=128, page_size=8,
                        prefill_buckets=(16,), decode_steps_per_dispatch=8)
    eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, use_pallas=False)
    eng._warm_ks = set(WARM)     # as after warmup(): no K = 4 to pick
    drain, exec_plan, blocks = eng.programs.drain, eng._exec_plan, []

    def faked(proved=-1):
        rows = drain(proved)
        for prog in rows:
            if prog.cls == PROG_DECODE:
                prog.t_start = prog.t_ready - STEP_S * prog.n
        return rows

    def recording(rec):
        blocks.append((int(rec["plan_decode_k"]),
                       any(s is None for s in eng.slots),
                       eng._step_time.ms))
        return exec_plan(rec)

    eng.programs.drain, eng._exec_plan = faked, recording
    prompts = [[i + 1, 2, 3, 4 + i] for i in range(4)]
    reqs = [GenRequest(prompt_ids=p, max_new_tokens=4 if i == 0 else 41)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)            # all four queued: ONE prefill group
    eng.start()
    try:
        streams = []
        for r in reqs:
            toks = []
            while True:
                ev = r.stream.get(timeout=120)
                if ev["token_id"] >= 0:
                    toks.append(ev["token_id"])
                if ev["finished"]:
                    break
            streams.append(toks)
        snap = eng.metrics.snapshot()
    finally:
        eng.stop()
    return prompts, streams, blocks, snap, params


def test_a_freed_slot_gets_the_short_block_and_the_tokens_do_not_change(
        monkeypatch):
    prompts, streams, blocks, snap, params = _serve(monkeypatch,
                                                    BLOCK_BUDGET_MS)
    _, plain, plain_blocks, plain_snap, _ = _serve(monkeypatch, math.inf)
    # whatever K the blocks had, every stream is the greedy continuation
    assert streams == plain
    assert [len(s) for s in streams] == [4, 41, 41, 41]
    want = np.asarray(llama.greedy_generate(
        params, TINY, jnp.asarray([prompts[1]]), 41))[0, len(prompts[1]):]
    np.testing.assert_array_equal(streams[1], want)
    # before a block has landed there is no step time and K is 8; with
    # every slot live it stays 8; once the short stream's slot is empty
    # and a step reads 30 ms, every block is the short one
    assert blocks[0][0] == 8 and blocks[0][2] is None
    hurried = [k for k, empty, step in blocks if empty and step]
    assert hurried and max(hurried) == 2, blocks
    assert all(step == pytest.approx(STEP_S * 1e3)
               for _, _, step in blocks if step)
    assert snap["decode_blocks_short_for_arrival"] == len(hurried)
    # the rule off by construction: the same slot stands empty behind
    # blocks of 8, and nothing is counted
    assert max(k for k, empty, step in plain_blocks if empty and step) == 8
    assert plain_snap["decode_blocks_short_for_arrival"] == 0
    assert snap["decode_steps"] == sum(k for k, _, _ in blocks)
    name = "decode_blocks_short_for_arrival"
    assert name in fleet.counter_keys() and name in prometheus_text(snap)
    assert EngineMetrics().snapshot()[name] == 0
