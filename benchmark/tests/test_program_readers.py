"""The readers of the program ledger on a recording: the engine's flight
events of one CPU rehearsal of the chain cell with the ledger in the
program (data/program_ctx.json: the reference check's request at -2.6 s,
four warm-up forwards at -0.6 s, then seven requests dispatched between
0.15 s and 2.997 s of a 3 s window: 28 programs complete inside it: 7
prefill groups, 7 encoder forwards, 14 decode blocks; one slot, retired
and admitted again six times). data/timeline_ctx.json is the older
recording of a program without the ledger: every reader gives None."""

import copy
import glob
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.readers import program_request, program_window, slot_interval
from benchmark.tests import test_rehearsal as rehearsal

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = bench_run.load_benchmark()
NEW = [m["name"] for m in BENCH["per_layer"]
       if ".window." in m["name"] or ".slot." in m["name"]
       or m["name"] in ("sched.prefill_queue_p50_ms",
                        "sched.prefill_run_p50_ms",
                        "sched.first_token_lag_p50_ms",
                        "sched.encoder_queue_p50_ms")]
OPEN_CELLS = ["mistral7b.chat-open", "rag.chain-open"]


def _load(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


@pytest.fixture()
def ctx():
    return _load("program_ctx.json")


def _window(ctx, shift, seconds):
    """The recording through a window that opens `shift` s later."""
    out = copy.deepcopy(ctx)
    for ev in out["engine"]["events"]:
        ev["t"] -= shift
    out["seconds"] = seconds
    return out


def _median(values):
    xs = sorted(values)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2


def _new_of(cell):
    return [m["name"] for m in bench_run.cell_metrics(BENCH, cell, True)
            if m["name"] in NEW]


def test_the_thirteen_are_the_issues_thirteen():
    assert len(NEW) == 13
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    closed = [w["name"] for w in BENCH["workloads"]
              if w["name"] not in OPEN_CELLS]
    assert len(closed) == 5
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span"
        if name.startswith("closed."):
            assert m["workloads"] == closed, name
        elif name == "sched.encoder_queue_p50_ms":
            assert m["workloads"] == ["rag.chain-open"]
            assert m["layer"] == "encoders"
        else:
            assert m["workloads"] == OPEN_CELLS, name
    assert len(_new_of("rag.chain-open")) == 8
    assert len(_new_of("mistral7b.chat-open")) == 7
    assert all(len(_new_of(c)) == 5 for c in closed)
    # none is an end-to-end metric, none is reported untraced
    assert not any(m["name"] in NEW for c in BENCH["workloads"]
                   for m in bench_run.cell_metrics(BENCH, c["name"], False))


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_the_recording(ctx, name):
    value = bench_run.read_metric(name, ctx)
    assert value is not None and 0.0 <= value < 1000.0, (name, value)
    if name.endswith("device_busy_share"):
        assert 0.0 < value <= 100.0


@pytest.mark.parametrize("name", NEW)
def test_no_program_events_reads_none_never_zero(name):
    old = _load("timeline_ctx.json")
    assert not [e for e in old["engine"]["events"] if e["kind"] == 20]
    assert bench_run.read_metric(name, old) is None
    # and with no events at all
    old["engine"]["events"] = []
    assert bench_run.read_metric(name, old) is None


def test_programs_parse_without_guessing(ctx):
    progs = program_window.programs(ctx)
    assert [p["seq"] for p in sorted(progs, key=lambda p: p["seq"])] == \
        list(range(34))
    first = next(p for p in progs if p["seq"] == 8)
    assert (first["cls"], first["n"], first["rows"], first["shape"]) == (
        1, 90, 1, "1x128")
    for p in progs:
        assert p["t_enqueue"] <= p["t_start"] + 1e-9 <= p["t_ready"] + 2e-9
    inside = program_window.in_window(ctx)
    assert len(inside) == 28
    assert [len(program_window.in_window(ctx, c)) for c in range(4)] == [
        14, 7, 0, 7]


def test_window_edges_follow_the_completion(ctx):
    # a program is in the window when its COMPLETION is
    assert len(program_window.in_window(_window(ctx, 0.15, 2.85))) == 26
    assert len(program_window.in_window(_window(ctx, 0.0, 2.99))) == 25
    assert len(program_window.in_window(_window(ctx, -3.0, 3.0))) == 6
    assert program_window.read(_window(ctx, 3.5, 1.0), "longest") is None
    # requests: dispatched inside it
    assert len(program_request.joined(ctx)) == 7
    assert len(program_request.joined(_window(ctx, 0.155, 2.845))) == 6
    assert len(program_request.joined(_window(ctx, 0.0, 2.9))) == 6
    assert program_request.read(_window(ctx, 1.7, 1.0), "run") is None
    # slots: retired inside it (the last retire lands at 3.00004 s)
    assert len(slot_interval.intervals(ctx)) == 6
    assert len(slot_interval.intervals(_window(ctx, 0.0, 3.1))) == 6
    assert len(slot_interval.intervals(_window(ctx, 0.16, 2.84))) == 5
    assert slot_interval.read(_window(ctx, 1.7, 1.0),
                              "retire_to_admit") is None


def test_queue_run_lag_tile_enqueue_to_first_token(ctx):
    pairs = program_request.joined(ctx)
    for disp, prog, first in pairs:
        queue_ms = prog["a"] - prog["b"]
        lag_ms = (first["t"] - prog["t_ready"]) * 1e3
        whole = (first["t"] - prog["t_enqueue"]) * 1e3
        assert queue_ms >= 0.0 and lag_ms >= 0.0
        assert queue_ms + prog["b"] + lag_ms == pytest.approx(whole,
                                                              abs=1e-3)
        # prefill_dispatch is stamped inside the program's span
        assert prog["t_enqueue"] <= disp["t"] <= first["t"]
    want = _median(p["b"] for _, p, _ in pairs)
    assert bench_run.read_metric("sched.prefill_run_p50_ms", ctx) == \
        pytest.approx(want)
    three = sum(bench_run.read_metric(n, ctx) for n in (
        "sched.prefill_queue_p50_ms", "sched.prefill_run_p50_ms",
        "sched.first_token_lag_p50_ms"))
    old = bench_run.read_metric("sched.dispatch_to_first_token_p50_ms", ctx)
    call = _median((d["t"] - p["t_enqueue"]) * 1e3 for d, p, _ in pairs)
    assert three == pytest.approx(old + call, abs=1.0)


def test_window_sums_are_plain_sums(ctx):
    blocks = program_window.in_window(ctx, 0)
    assert bench_run.read_metric("open.window.decode_ms", ctx) == \
        pytest.approx(sum(p["b"] for p in blocks)
                      / sum(p["n"] for p in blocks))
    prefills = program_window.in_window(ctx, 1)
    assert bench_run.read_metric("open.window.prefill_ms_per_ktok", ctx) == \
        pytest.approx(1000.0 * sum(p["b"] for p in prefills)
                      / sum(p["n"] for p in prefills))
    assert sum(p["n"] for p in prefills) == 636
    assert bench_run.read_metric("open.window.longest_program_ms", ctx) == \
        max(p["b"] for p in program_window.in_window(ctx))
    enc = program_window.in_window(ctx, 3)
    assert bench_run.read_metric("sched.encoder_queue_p50_ms", ctx) == \
        pytest.approx(_median(p["a"] - p["b"] for p in enc))
    # the closed file of one reader reads the same recording the same
    assert bench_run.read_metric("closed.window.decode_ms", ctx) == \
        bench_run.read_metric("open.window.decode_ms", ctx)


def test_busy_share_is_the_union_clipped_to_the_window(ctx):
    inside = program_window.in_window(ctx)
    # this recording's programs never overlap: the union is the sum
    want = 100.0 * sum(p["b"] for p in inside) / 1e3 / ctx["seconds"]
    got = bench_run.read_metric("open.window.device_busy_share", ctx)
    assert got == pytest.approx(want, rel=1e-6)
    # a window that opens in the middle of a program counts the part of
    # it inside, though the program itself (completion) may be outside
    p = next(p for p in inside if p["seq"] == 15)       # a 5 ms prefill
    mid = (p["t_start"] + p["t_ready"]) / 2
    half = _window(ctx, mid, 0.0005)                    # 0.5 ms inside it
    assert program_window.read(half, "busy") == pytest.approx(100.0)
    tail = _window(ctx, mid - 0.0005, 0.0005)           # closes inside it
    assert program_window.in_window(tail) == []
    assert program_window.read(tail, "busy") == pytest.approx(100.0)
    # overlapping intervals are not counted twice
    a = {"t_start": 0.0, "t_ready": 0.6}
    b = {"t_start": 0.4, "t_ready": 1.0}
    assert program_window.busy_seconds([b, a], 0.0, 2.0) == \
        pytest.approx(1.0)
    assert program_window.busy_seconds([a, b], 0.5, 0.8) == \
        pytest.approx(0.3)


def test_slot_intervals_follow_one_slot(ctx):
    found = slot_interval.intervals(ctx)
    for retire, admit, block in found:
        assert retire["slot"] == admit["slot"] == 0
        assert retire["rid"] != admit["rid"] and admit["t"] >= retire["t"]
        assert block is not None and block["cls"] == 0
        assert block["t_start"] >= admit["t"]
    gaps = [(a["t"] - r["t"]) * 1e3 for r, a, _ in found]
    assert bench_run.read_metric(
        "closed.slot.retire_to_admit_p50_ms", ctx) == \
        pytest.approx(_median(gaps))
    waits = [(b["t_start"] - a["t"]) * 1e3 for _, a, b in found]
    assert bench_run.read_metric(
        "closed.slot.admit_to_decode_p50_ms", ctx) == \
        pytest.approx(_median(waits))
    # a retire that carries no slot (a parent's) joins nothing
    for ev in ctx["engine"]["events"]:
        if ev["kind"] == 7:
            ev["slot"] = -1
    assert slot_interval.intervals(ctx) == []
    assert slot_interval.read(ctx, "admit_to_decode") is None


def test_unknown_part_is_an_error_not_a_zero(ctx):
    for reader, kw in ((program_window, {"stat": "nope"}),
                       (program_request, {"part": "nope"}),
                       (slot_interval, {"part": "nope"})):
        with pytest.raises(ValueError):
            reader.read(ctx, **kw)


def test_a_profile_keeps_the_bare_phase_names(tmp_path):
    """The ledger's sequence number rides the annotation around each
    enqueue as an argument: the host events a traced run's
    `breakdown.idle_gaps` is named by must stay the bare phase names."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import xplane

    double = jax.jit(lambda x: x * 2)
    double(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for seq in (7, 1234, 56789):
        with jax.profiler.TraceAnnotation("sched.prefill_dispatch", seq=seq):
            double(jnp.ones((8, 8))).block_until_ready()
        with jax.profiler.TraceAnnotation("encoder.embed", seq=seq + 1):
            double(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {e[0] for lines in xplane.load(path).values()
             for evs in lines.values() for e in evs
             if e[0].startswith(("sched.", "encoder."))}
    assert names == {"sched.prefill_dispatch", "encoder.embed"}
    assert {xplane.op_kind(n) for n in names} == names


# -- the CPU rehearsals print every new metric of their cell ----------------


def _chain_config():
    config = copy.deepcopy(rehearsal.TINY)
    config["serving"]["engine"]["prefill_buckets"] = [128]
    config["encoders"] = {"embedder": {
        "geometry": "tiny", "dtype": "float32",
        "overrides": {"vocab_size": 512},
        "engine": {"max_batch": 4, "buckets": [32, 64]}}}
    with open(os.path.join(rehearsal.BENCH_DIR, "configs",
                           "rag-arctic-l-mistral-7b.json")) as fh:
        env = json.load(fh)["chain"]["env"]
    config["chain"] = {"env": dict(
        env, APP_EMBEDDINGS_DIMENSIONS="32", APP_TEXTSPLITTER_CHUNKSIZE="12",
        APP_RETRIEVER_MAXCONTEXTTOKENS="40")}
    return config


@pytest.mark.parametrize("cell,config,traffic", [
    ("mistral7b.decode-closed64", rehearsal.TINY, rehearsal.CLOSED),
    ("mistral7b.chat-open", rehearsal.TINY, rehearsal.OPEN),
    ("rag.chain-open", None, rehearsal.CHAIN)],
    ids=["closed", "open", "chain"])
def test_rehearsal_prints_every_new_metric_of_its_cell(cell, config, traffic):
    out = rehearsal._run(cell, config or _chain_config(), traffic)
    want = _new_of(cell)
    assert len(want) in (5, 7, 8)
    for name in want:
        assert name in out["metrics"], (name, sorted(out["metrics"]))
        assert out["metrics"][name]["value"] >= 0.0
    share = out["metrics"][[n for n in want
                            if n.endswith("device_busy_share")][0]]
    assert 0.0 < share["value"] <= 100.0
