"""The stratified generator's invariants."""

import collections
import json
import os

import pytest

from benchmark.harness import traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")))
SEEDS = (0, 7, 2**31 + 12345)


def _multiset(sched, phase):
    """(prompt lengths, output lengths) of one phase, as multisets."""
    reqs = [r for r in sched["requests"] if r["phase"] == phase]
    return (collections.Counter(len(r["prompt_ids"]) for r in reqs),
            collections.Counter(r["max_tokens"] for r in reqs))


def _window(sched):
    return [r for r in sched["requests"] if r["phase"] == "window"]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work_in_another_order(mix):
    spec = traffic.load_traffic(BENCH_DIR, mix)
    scheds = [traffic.build_schedule(spec, s, 45.0, 32768) for s in SEEDS]
    base = scheds[0]
    for other in scheds[1:]:
        assert len(other["requests"]) == len(base["requests"])
        assert _multiset(other, "window") == _multiset(base, "window")
        order = [len(r["prompt_ids"]) for r in _window(other)]
        assert order != [len(r["prompt_ids"]) for r in _window(base)]
        assert _window(other)[0]["prompt_ids"] != _window(base)[0]["prompt_ids"]
    again = traffic.build_schedule(spec, SEEDS[1], 45.0, 32768)
    assert json.dumps(again) == json.dumps(scheds[1])  # same seed, same run


def test_a_seed_rotates_the_cells_cycle():
    """Same sizes and the same gaps between arrivals, in another order:
    the window of one seed is a rotation of another's."""
    spec = traffic.load_traffic(BENCH_DIR, "chat-open")
    a, b = (_window(traffic.build_schedule(spec, s, 45.0, 32768))
            for s in SEEDS[:2])
    sizes_a = [(len(r["prompt_ids"]), r["max_tokens"]) for r in a]
    sizes_b = [(len(r["prompt_ids"]), r["max_tokens"]) for r in b]
    assert any(sizes_a[k:] + sizes_a[:k] == sizes_b for k in range(len(a)))

    def gaps(w):
        return sorted(round(y["due_s"] - x["due_s"], 9)
                      for x, y in zip(w, w[1:]))

    # all gaps but the one the window's edges cut are shared
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2


def test_open_loop_counts_and_arrivals():
    spec = traffic.load_traffic(BENCH_DIR, "chat-open")
    sched = traffic.build_schedule(spec, 3, 45.0, 32768)
    window = _window(sched)
    ramp = [r for r in sched["requests"] if r["phase"] == "ramp"]
    assert len(window) == round(spec["rate_per_s"] * 45.0)
    assert len(ramp) == round(spec["rate_per_s"] * spec["ramp_s"])
    assert all(0.0 <= r["due_s"] < 45.0 for r in window)
    assert all(-sched["ramp_s"] <= r["due_s"] < 0.0 for r in ramp)
    assert 0.5 * spec["ramp_s"] < sched["ramp_s"] < 2.0 * spec["ramp_s"]
    due = [r["due_s"] for r in sched["requests"]]
    assert due == sorted(due)
    # not one per slot: a Poisson process conditioned on its count bunches
    slots = collections.Counter(int(r["due_s"] * spec["rate_per_s"])
                                for r in window)
    assert max(slots.values()) >= 2 and len(slots) < len(window)
    # the ramp is the cycle's preceding requests: with the window they
    # continue one sequence of sizes
    other = _window(traffic.build_schedule(spec, 4, 45.0, 32768))
    seq = [len(r["prompt_ids"]) for r in ramp + window][: len(ramp) + 5]
    ring = [len(r["prompt_ids"]) for r in other] * 2
    assert any(ring[i:i + len(seq)] == seq for i in range(len(other)))


def test_stratified_quantiles_match_the_stated_distributions():
    chat = traffic.load_traffic(BENCH_DIR, "chat-open")
    p = sorted(traffic.stratified(chat["prompt_tokens"], 1000))
    o = sorted(traffic.stratified(chat["output_tokens"], 1000))
    assert p[0] == 64 and p[-1] <= 2048 and o[0] == 48 and o[-1] <= 384
    assert 120 <= p[500] <= 130            # stated median about 126
    assert 220 <= sum(p) / 1000 <= 240     # stated mean about 230
    assert 78 <= o[500] <= 88 and 100 <= sum(o) / 1000 <= 112
    u = traffic.stratified({"dist": "uniform", "lo": 32, "hi": 128}, 97)
    assert min(u) == 32 and max(u) == 128 and sorted(u) == u


def test_words_and_tokenizer_round_trip():
    from benchmark.harness.bench_tokenizer import WordTokenizer

    tk = WordTokenizer(32768)
    ids = [0, 5, 32767, 123]
    assert tk.encode(traffic.words(ids)) == ids
    assert tk.encode(tk.decode(ids)) == ids
    assert len(tk.encode("a question about the documents")) == 5
    assert not tk.eos_ids and tk.eos_id is None


def test_corpus_splits_into_the_stated_chunks():
    spec = traffic.load_traffic(BENCH_DIR, "chain-open")
    files = list(traffic.corpus_files(spec, 5, 32768))
    assert len(files) == spec["corpus"]["files"]
    n_words = sum(len(text.split()) for _, text in files)
    assert n_words == spec["corpus"]["chunks"] * spec["corpus"]["chunk_tokens"]
    assert files == list(traffic.corpus_files(spec, 5, 32768))
