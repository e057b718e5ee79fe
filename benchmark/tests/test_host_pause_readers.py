"""The readers of the host's pauses (readers/host_pause.py) on a
recording: the `program` and `host_pause` events of the first second of
the window of one CPU rehearsal of the closed cell, from 0.2 s before it
to 0.2 s after (data/host_pause_ctx.json; `seconds` is 1.0), while
another thread made garbage and forced collections. 0.22 s to 0.43 s
holds three collections of generation 2 (75.0, 54.8 and 61.2 ms, on that
thread, which kept the interpreter's lock), a dispatch call that took 176.0 ms
to return meanwhile, a fetch poll that woke 57.1 ms late, and the decode
block seq 235 that "ran" 241.0 ms beside a median of 3: the engine's own
line for it reads `host=238.2 ms of 241.0 ms (gc 191.0 ms, dispatch_call
178.1 ms, late_wake 57.1 ms)`. data/program_ctx.json is PR 39's
recording, whose `program` events carry no `call=`: every reader gives
None."""

import copy
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.readers import host_pause, program_window
from benchmark.tests import test_rehearsal as rehearsal

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = bench_run.load_benchmark()
TAILS = ("host.gc_pause_ms", "host.longest_pause_ms",
         "window.longest_program_host_ms", "sched.dispatch_call_p99_ms")
CLOSED = ["closed." + t for t in TAILS]
OPEN = ["open." + t for t in TAILS]
OPEN_CELLS = ["mistral7b.chat-open", "rag.chain-open"]
STATS = ("gc_pause", "longest_pause", "longest_program_host", "call_p99")


def _load(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


@pytest.fixture()
def ctx():
    return _load("host_pause_ctx.json")


def _window(ctx, shift, seconds):
    """The recording through a window that opens `shift` s later."""
    out = copy.deepcopy(ctx)
    for ev in out["engine"]["events"]:
        ev["t"] -= shift
    out["seconds"] = seconds
    return out


def _program(t_ready, a, b, call, seq=0, stalled=""):
    return {"kind": 20, "code": 0, "slot": 4, "a": a, "b": b, "t": t_ready,
            "aux": f"seq={seq} n=8 shape=K8 call={call:.3f}{stalled}"}


def _pause(t_end, ms, cause, aux=""):
    return {"kind": 25, "code": cause, "slot": -1, "a": ms, "b": 0.0,
            "t": t_end, "aux": aux}


def _ctx(events, seconds=10.0):
    return {"seconds": seconds, "engine": {"events": events}}


def test_the_eight_are_the_issues_eight():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    closed_cells = [w["name"] for w in BENCH["workloads"]
                    if w["name"] not in OPEN_CELLS]
    assert len(closed_cells) == 9
    for name in CLOSED + OPEN:
        m = by_name[name]
        assert (m["layer"], m["source"], m["unit"], m["better"]) == (
            "scheduler", "program_span", "ms", "lower")
        with open(os.path.join(rehearsal.BENCH_DIR, "metrics",
                               name + ".json")) as fh:
            spec = json.load(fh)
        assert spec["reader"] == "host_pause"
        assert {k: spec[k] for k in ("layer", "source", "unit", "better",
                                     "moves")} == \
            {k: m[k] for k in ("layer", "source", "unit", "better", "moves")}
        if name.startswith("closed."):
            assert m["workloads"] == closed_cells
            assert m["moves"] == "out_tokens_per_s"
        else:
            assert m["workloads"] == OPEN_CELLS
            assert m["moves"] == "gap_p99_ms"
    # they are the LAST eight entries: nothing before them moved
    assert [m["name"] for m in BENCH["per_layer"][-8:]] == CLOSED + OPEN
    # per-layer only: none is reported untraced
    assert not any(m["name"] in CLOSED + OPEN for c in BENCH["workloads"]
                   for m in bench_run.cell_metrics(BENCH, c["name"], False))


@pytest.mark.parametrize("name,value", [
    (side + tail, value) for side in ("closed.", "open.")
    for tail, value in zip(TAILS, (190.9742, 175.977, 238.18, 4.4876))])
def test_metric_reads_the_recording(ctx, name, value):
    assert bench_run.read_metric(name, ctx) == pytest.approx(value, rel=1e-4)


def test_the_recording_is_what_the_docstring_says(ctx):
    held = host_pause.pauses(ctx)
    assert sorted(round(ms, 1) for _, _, ms, cause in held
                  if cause == host_pause.GC) == [54.8, 61.2, 75.0]
    assert [round(ms, 1) for _, _, ms, cause in held if cause == 1] == [57.1]
    longest = max(host_pause.programs(ctx), key=lambda p: p["b"])
    assert longest["b"] == 241.0435 and longest["call"] == 175.977
    # its own call is the longest single pause; the collections overlap
    # it, so the union is less than the sum of the parts
    parts = 191.0 + 176.0 + 57.1
    assert 238.0 < host_pause.read(ctx, "longest_program_host") < parts


@pytest.mark.parametrize("name", CLOSED + OPEN)
def test_a_parent_without_call_reads_none_never_zero(name):
    old = _load("program_ctx.json")
    assert [e for e in old["engine"]["events"] if e["kind"] == 20]
    assert not [e for e in old["engine"]["events"] if "call=" in e["aux"]]
    assert bench_run.read_metric(name, old) is None
    old["engine"]["events"] = []
    assert bench_run.read_metric(name, old) is None


@pytest.mark.parametrize("name", CLOSED + OPEN)
def test_instrumented_and_nothing_paused_reads_zero_never_none(ctx, name):
    quiet = copy.deepcopy(ctx)
    quiet["engine"]["events"] = [
        dict(e, aux=e["aux"].split(" call=")[0] + " call=0.000")
        for e in quiet["engine"]["events"] if e["kind"] == 20]
    assert bench_run.read_metric(name, quiet) == 0.0
    # ... and with no program inside the window at all
    assert bench_run.read_metric(name, _window(quiet, 50.0, 1.0)) == 0.0


def test_without_pause_events_the_calls_still_count(ctx):
    calls_only = copy.deepcopy(ctx)
    calls_only["engine"]["events"] = [
        e for e in calls_only["engine"]["events"] if e["kind"] == 20]
    assert host_pause.read(calls_only, "gc_pause") == 0.0
    assert host_pause.read(calls_only, "longest_pause") == 175.977
    # the block's own call and the call enqueued behind it
    assert 175.977 <= host_pause.read(calls_only, "longest_program_host") \
        < host_pause.read(ctx, "longest_program_host")


def test_window_edges_follow_the_pauses_end_and_the_programs_completion():
    events = [_pause(0.0, 30.0, 0), _pause(4.999, 40.0, 0),
              _pause(5.0, 50.0, 0), _pause(-0.001, 60.0, 0),
              _pause(2.0, 70.0, 1),
              _program(1.0, 12.0, 10.0, 2.0, seq=0),
              _program(5.0, 12.0, 11.0, 90.0, seq=1),
              _program(-0.001, 12.0, 99.0, 80.0, seq=2)]
    c = _ctx(events, seconds=5.0)
    # [0, seconds): the pause that ends at 0.0 is in, the one at 5.0 out
    assert host_pause.read(c, "gc_pause") == 70.0
    assert host_pause.read(c, "longest_pause") == 70.0   # the late wake
    assert host_pause.read(c, "call_p99") == 2.0         # seq 0 alone
    # the longest program IN the window is seq 0 (b = 10), not seq 2
    assert program_window.read(c, "longest") == 10.0
    wider = _ctx(events, seconds=5.001)
    assert host_pause.read(wider, "gc_pause") == 120.0
    assert host_pause.read(wider, "longest_pause") == 90.0


def test_a_collection_inside_a_late_wake_is_not_counted_twice():
    # a block that "ran" 1.0 -> 1.5 s; a late wake-up 1.10 -> 1.40 with
    # a collection 1.15 -> 1.35 inside it; a call 1.38 -> 1.45 that
    # overlaps the wake-up's end; a collection before the block began
    events = [_program(1.5, 520.0, 500.0, 4.0, seq=0),
              _pause(1.40, 300.0, 1), _pause(1.35, 200.0, 0),
              _program(1.9, 520.0, 400.0, 70.0, seq=1),   # enqueued at 1.38
              _pause(0.9, 100.0, 0)]
    c = _ctx(events)
    # seq 0's own call: 0.98 -> 0.984, outside its start (1.0)
    assert host_pause.read(c, "longest_program_host") == pytest.approx(
        300.0 + 50.0)
    assert host_pause.read(c, "gc_pause") == 300.0
    # never above the program's own length, whatever is claimed
    events.append(_pause(3.0, 3000.0, 1))
    assert host_pause.read(_ctx(events), "longest_program_host") == \
        pytest.approx(500.0)


def test_pauses_outside_the_window_still_cover_a_program_inside_it():
    # the program completes inside the window; the collection that held
    # it ended before the window opened
    events = [_program(0.05, 400.0, 400.0, 1.0), _pause(-0.01, 300.0, 0)]
    c = _ctx(events, seconds=1.0)
    assert host_pause.read(c, "gc_pause") == 0.0
    assert host_pause.read(c, "longest_program_host") == pytest.approx(
        300.0 + 1.0)


@pytest.mark.parametrize("shift,seconds", [(0.0, 1.0), (0.3, 0.5),
                                           (0.45, 0.5), (-0.1, 0.4),
                                           (0.0, 0.25)])
def test_host_is_within_the_program_and_p99_within_the_longest(
        ctx, shift, seconds):
    c = _window(ctx, shift, seconds)
    longest = program_window.read(c, "longest")
    assert longest is not None
    assert 0.0 <= host_pause.read(c, "longest_program_host") \
        <= longest + 1e-9
    assert host_pause.read(c, "longest_pause") \
        >= host_pause.read(c, "call_p99") >= 0.0


def test_unknown_aux_keys_and_an_unknown_stat(ctx):
    for e in ctx["engine"]["events"]:
        if e["kind"] == 20:
            e["aux"] += " later=1"
    assert host_pause.read(ctx, "longest_pause") == 175.977
    with pytest.raises(ValueError):
        host_pause.read(ctx, "no_such")


@pytest.mark.parametrize("cell,traffic,names", [
    ("mistral7b.decode-closed64", rehearsal.CLOSED, CLOSED),
    ("mistral7b.chat-open", rehearsal.OPEN, OPEN)])
def test_a_rehearsed_cell_prints_all_of_its_four(cell, traffic, names):
    out = rehearsal._run(cell, rehearsal.TINY, traffic)
    for name in names:
        assert name in out["metrics"], name
        assert out["metrics"][name]["value"] >= 0.0
        assert out["metrics"][name]["unit"] == "ms"
    side = names[0].split(".")[0]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m[f"{side}.window.longest_program_host_ms"] \
        <= m[f"{side}.window.longest_program_ms"] + 1e-9
    assert m[f"{side}.host.longest_pause_ms"] \
        >= m[f"{side}.sched.dispatch_call_p99_ms"] > 0.0
    other = "open." if side == "closed" else "closed."
    assert not any(k.startswith(other + "host.") for k in m)
