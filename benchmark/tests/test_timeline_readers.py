"""The three readers of the request timeline on a recording: the chain
server's log and the engine's flight events of one CPU rehearsal of the
chain cell (data/chain-server.log, data/timeline_ctx.json: the reference
check's request and seven received between 0.18 s and 2.99 s of a 3 s
window, times on one CLOCK_MONOTONIC)."""

import copy
import json
import os
import shutil

import pytest

from benchmark import run as bench_run
from benchmark.harness import system
from benchmark.readers import (
    chain_engine_hop, chain_stage_percentile, engine_interval_percentile)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = bench_run.load_benchmark()
CHAIN_METRICS = [m["name"] for m in BENCH["per_layer"]
                 if m["name"].startswith(("chain.", "encoder."))
                 and m["name"] != "chain.pre_llm_p50_ms"]
ENGINE_METRICS = ["surface.pre_submit_p50_ms",
                  "sched.admit_to_dispatch_p50_ms",
                  "sched.dispatch_to_first_token_p50_ms"]


@pytest.fixture()
def ctx(tmp_path, monkeypatch):
    shutil.copy(os.path.join(DATA, "chain-server.log"), tmp_path)
    monkeypatch.setattr(system, "OUT_DIR", str(tmp_path))
    with open(os.path.join(DATA, "timeline_ctx.json")) as fh:
        return json.load(fh)


def _window(ctx, shift, seconds):
    """The same recording seen through a window that opens `shift`
    seconds later and lasts `seconds`."""
    out = copy.deepcopy(ctx)
    out["engine"]["open"]["t"] += shift
    for ev in out["engine"]["events"]:
        ev["t"] -= shift
    out["seconds"] = seconds
    return out


def _median(values):
    xs = sorted(values)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2


def test_the_twelve_are_the_issues_twelve():
    assert len(CHAIN_METRICS) == 9 and set(ENGINE_METRICS) <= {
        m["name"] for m in BENCH["per_layer"]}
    cells = bench_run.cell_metrics
    new = set(CHAIN_METRICS + ENGINE_METRICS)
    names = lambda cell: {m["name"] for m in cells(BENCH, cell, True)}  # noqa: E731
    assert names("rag.chain-open") >= new
    assert names("mistral7b.chat-open") & new == set(ENGINE_METRICS)
    for closed in ("mistral7b.decode-closed64",
                   "mistral-small-24b-tp4.decode-closed64"):
        assert not names(closed) & new
    assert not any(cells(BENCH, c["name"], False)[0]["name"] in new
                   for c in BENCH["workloads"])


@pytest.mark.parametrize("name", CHAIN_METRICS + ENGINE_METRICS)
def test_metric_reads_the_recording(ctx, name):
    value = bench_run.read_metric(name, ctx)
    assert value is not None and 0.0 <= value < 1000.0, (name, value)


def test_window_keeps_requests_received_inside_it(ctx):
    found = chain_stage_percentile.timelines(ctx)
    assert len(found) == 7
    rel = sorted(t["received"] - ctx["engine"]["open"]["t"] for t in found)
    assert 0.0 <= rel[0] and rel[-1] < ctx["seconds"]
    # The stages tile each request to its first frame: no holes.
    for t in found:
        stages = t["stages"]
        assert [s["name"] for s in stages] == [
            "dispatch", "embed", "search", "assemble", "llm_first_piece",
            "emit"]
        total = sum(s["end"] - s["start"] for s in stages)
        assert abs(total - (stages[-1]["end"] - t["received"])) < 1e-3
    # Requests straddling the window's edges: received before it opens
    # (0.18 s) or after it closes (2.99 s) do not count.
    assert len(chain_stage_percentile.timelines(_window(ctx, 0.3, 2.7))) == 5
    assert len(chain_stage_percentile.timelines(_window(ctx, 0.0, 2.5))) == 6
    assert len(chain_stage_percentile.timelines(_window(ctx, 0.3, 2.0))) == 4
    assert chain_stage_percentile.timelines(_window(ctx, 0.0, 0.1)) is None


def test_stage_percentile_is_the_plain_median(ctx):
    found = chain_stage_percentile.timelines(ctx)
    want = _median((s["end"] - s["start"]) * 1e3 for t in found
                   for s in t["stages"] if s["name"] == "embed")
    assert bench_run.read_metric("chain.embed_p50_ms", ctx) == \
        pytest.approx(want)
    ready = _median(s["server"]["ready"] for t in found
                    for s in t["stages"] if s["name"] == "embed")
    assert bench_run.read_metric("encoder.embed_ready_p50_ms", ctx) == \
        pytest.approx(ready)
    # The encoder's own total lies inside the stage that called it.
    assert bench_run.read_metric("encoder.embed_server_p50_ms", ctx) < want
    assert chain_stage_percentile.read(ctx, "no_such_stage") is None
    assert chain_stage_percentile.read(ctx, "search", field="total") is None


def test_every_chain_request_joins_its_engine_request(ctx):
    pairs = chain_engine_hop.joined(ctx)
    assert len(pairs) == 7
    hops = []
    for timeline, sub, first in pairs:
        assert sub["aux"] == timeline["rid"]
        assert sub["rid"] == first["rid"] and sub["rid"].startswith("cmpl-")
        stage = chain_stage_percentile.stage_ms(timeline, "llm_first_piece")
        hops.append(stage - (sub["b"] + first["a"]))
        assert hops[-1] > 0  # the hop is inside the stage, request by request
    assert chain_engine_hop.read(ctx) == pytest.approx(_median(hops))
    # The join follows the chain's window, not the engine's.
    assert len(chain_engine_hop.joined(_window(ctx, 0.3, 2.0))) == 4


def test_unjoinable_request_is_left_out(ctx):
    rid = chain_engine_hop.joined(ctx)[0][0]["rid"]
    for ev in ctx["engine"]["events"]:
        if ev["aux"] == rid:
            ev["aux"] = ""
    assert len(chain_engine_hop.joined(ctx)) == 6
    assert chain_engine_hop.read(ctx) is not None
    for ev in ctx["engine"]["events"]:
        ev["aux"] = ""
    assert chain_engine_hop.joined(ctx) == []
    assert chain_engine_hop.read(ctx) is None


def test_missing_log_reads_nothing_and_does_not_raise(ctx, tmp_path):
    os.unlink(os.path.join(str(tmp_path), "chain-server.log"))
    for name in CHAIN_METRICS:
        assert bench_run.read_metric(name, ctx) is None, name
    for name in ENGINE_METRICS:  # the engine's events are still there
        assert bench_run.read_metric(name, ctx) is not None, name
    # A log with no timeline line (a program that writes none).
    with open(os.path.join(str(tmp_path), "chain-server.log"), "w") as fh:
        fh.write('INFO:aiohttp.access:127.0.0.1 "POST /generate" 200\n'
                 "INFO:gaie.timeline:not json\n")
    assert bench_run.read_metric("chain.embed_p50_ms", ctx) is None
    assert bench_run.read_metric("chain.llm_hop_p50_ms", ctx) is None


def test_engine_intervals_request_by_request(ctx):
    events = ctx["engine"]["events"]

    def by_hand(c):
        admits = engine_interval_percentile.by_rid(c, "admit")
        return [(next(e for e in c["engine"]["events"] if e["kind"] == 4
                      and e["rid"] == rid)["t"] - evs[0]["t"]) * 1e3
                for rid, evs in admits.items()
                if 0 <= evs[0]["t"] < c["seconds"]]

    # Eight admits on record: the reference check's, before the window,
    # does not count.
    assert len(engine_interval_percentile.by_rid(ctx, "admit")) == 8
    assert len(by_hand(ctx)) == 7
    assert engine_interval_percentile.read(
        ctx, "admit", "prefill_dispatch") == pytest.approx(
            _median(by_hand(ctx)))
    cut = _window(ctx, 0.3, 2.0)
    assert len(by_hand(cut)) == 4
    assert engine_interval_percentile.read(
        cut, "admit", "prefill_dispatch") == pytest.approx(
            _median(by_hand(cut)))
    # A request without the later event has no sample; none at all: None.
    ctx["engine"]["events"] = [e for e in events if e["kind"] != 6]
    assert engine_interval_percentile.read(
        ctx, "prefill_dispatch", "first_token") is None
    assert engine_interval_percentile.read(
        ctx, "admit", "prefill_dispatch") is not None
    ctx["engine"]["events"] = []
    for name in ENGINE_METRICS:
        assert bench_run.read_metric(name, ctx) is None


def test_a_program_that_stamps_no_pre_submit_reads_nothing(ctx):
    """The parent commit records `b` of submit as 0.0 for every request:
    a field nobody wrote is not a reading of 0 ms."""
    for ev in ctx["engine"]["events"]:
        if ev["kind"] == 1:
            ev["b"] = 0.0
    assert bench_run.read_metric("surface.pre_submit_p50_ms", ctx) is None
    assert bench_run.read_metric("chain.llm_hop_p50_ms", ctx) is not None


def test_chain_rehearsal_prints_all_twelve():
    from benchmark.tests import test_rehearsal as rehearsal

    config = copy.deepcopy(rehearsal.TINY)
    config["serving"]["engine"]["prefill_buckets"] = [128]
    config["encoders"] = {"embedder": {
        "geometry": "tiny", "dtype": "float32",
        "overrides": {"vocab_size": 512},
        "engine": {"max_batch": 4, "buckets": [32, 64]}}}
    with open(os.path.join(bench_run.BENCH_DIR, "configs",
                           "rag-arctic-l-mistral-7b.json")) as fh:
        env = json.load(fh)["chain"]["env"]
    config["chain"] = {"env": dict(
        env, APP_EMBEDDINGS_DIMENSIONS="32", APP_TEXTSPLITTER_CHUNKSIZE="12",
        APP_RETRIEVER_MAXCONTEXTTOKENS="40")}
    out = rehearsal._run("rag.chain-open", config, rehearsal.CHAIN)
    for name in CHAIN_METRICS + ENGINE_METRICS:
        assert name in out["metrics"], (name, sorted(out["metrics"]))
