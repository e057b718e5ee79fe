"""What the HOST was doing while a program "ran long"
(serving/flight.py::HostPauses, `host_cover`, flight event kind 25):
the collector's hook on a fake clock, the hand-off's bound and cursors,
`gc.callbacks` as it was found after many engines, `call=` on every
`program` event and the accepted readers unmoved by it, the scheduler's
late wake-ups, a stall that names its cause, the operator's keys, the
timeline's slices and the analyzer's causes, and streams byte-identical
with the recorder on and off."""

import gc
import json
import logging
import os
import sys
import threading
import time

import jax
import pytest

from generativeaiexamples_tpu.serving import engine as engine_mod
from generativeaiexamples_tpu.serving import fleet, flight
from generativeaiexamples_tpu.serving.engine import GenRequest
from generativeaiexamples_tpu.serving.flight import (
    EV_HOST_PAUSE, EV_PREFILL_DISPATCH, EV_PROGRAM, PROG_DECODE,
    HostPauses, Program, ProgramLedger, chrome_trace, host_cover,
    parse_program_aux, spans_nest)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

from test_program_ledger import (  # noqa: E402  (the ledger's own drivers)
    TINY, FakeClock, drive_inline, events, make_engine, program_rows,
    stream_of, tokens)

COUNTERS = ("host_gc_collections", "host_gc_pauses", "host_gc_pause_ms",
            "host_late_wakes", "program_stalls_host")
HISTS = ("hist_host_pause_ms", "hist_dispatch_call_ms")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


class Recorder:
    """All the hook asks of a flight recorder."""

    def __init__(self, enabled=True):
        self.enabled = enabled


def collect(pauses, clock, ms, gen=0, collected=3):
    """One collection of `ms` through the hook, as the collector calls it."""
    info = {"generation": gen, "collected": collected, "uncollectable": 0}
    pauses._on_gc("start", info)
    clock.t += ms / 1e3
    pauses._on_gc("stop", info)


def acquire_by_hand(pauses, recorder):
    """`acquire`, and the hook out of `gc.callbacks` again: these tests
    call it by hand, the interpreter's own collections must not advance
    a fake clock's rows, and nothing of a test's may stay in that list."""
    cursor = pauses.acquire(recorder)
    gc.callbacks.remove(pauses._on_gc)
    return cursor


@pytest.fixture()
def hooked():
    """A `HostPauses` of the test's own on a fake clock, one recorder on."""
    clock = FakeClock()
    pauses = HostPauses(clock=clock, capacity=8)
    rec = Recorder()
    cursor = acquire_by_hand(pauses, rec)
    yield pauses, clock, rec, cursor
    assert pauses._on_gc not in gc.callbacks


# ---------------------------------------------------------------------------
# the hook, on a fake clock
# ---------------------------------------------------------------------------


class TestTheHookOnAFakeClock:
    @pytest.mark.parametrize("gen", [0, 1])
    def test_a_short_young_collection_is_summed_and_not_listed(
            self, hooked, gen):
        pauses, clock, _, cursor = hooked
        collect(pauses, clock, 0.4, gen=gen)
        rows, after = pauses.read(cursor)
        assert rows == []
        assert after[1] - cursor[1] == 1
        assert after[2] - cursor[2] == pytest.approx(0.4)

    @pytest.mark.parametrize("gen,ms", [(0, 1.0), (1, 7.5), (2, 0.2),
                                        (2, 850.0)])
    def test_a_long_or_old_collection_leaves_a_row(self, hooked, gen, ms):
        pauses, clock, _, cursor = hooked
        t0 = clock.t
        collect(pauses, clock, ms, gen=gen, collected=41)
        rows, after = pauses.read(cursor)
        assert len(rows) == 1
        start, end, g, collected, thread = rows[0]
        assert start == t0 and (end - start) * 1e3 == pytest.approx(ms)
        assert (g, collected) == (gen, 41)
        assert thread == threading.current_thread().name
        assert pauses.read(after) == ([], after)     # read once

    def test_the_hand_off_is_bounded_and_drops_the_oldest(self, hooked):
        pauses, clock, _, cursor = hooked
        for i in range(20):
            collect(pauses, clock, 2.0, collected=i)
        rows, after = pauses.read(cursor)
        assert [r[3] for r in rows] == list(range(12, 20))   # capacity 8
        assert after[1] - cursor[1] == 20                    # all summed
        assert after[2] - cursor[2] == pytest.approx(40.0)

    def test_two_engines_read_the_same_pause_each_by_its_cursor(
            self, hooked):
        pauses, clock, _, first = hooked
        collect(pauses, clock, 3.0)
        second = acquire_by_hand(pauses, Recorder())     # starts later
        collect(pauses, clock, 5.0, gen=2)
        rows_a, first = pauses.read(first)
        rows_b, second = pauses.read(second)
        assert [r[2] for r in rows_a] == [0, 2]
        assert [r[2] for r in rows_b] == [2]         # from its start on
        assert rows_a[1] == rows_b[0]                # the same pause
        collect(pauses, clock, 1.5)
        assert len(pauses.read(first)[0]) == len(pauses.read(second)[0]) == 1

    def test_no_stamp_while_every_recorder_is_off(self, hooked):
        pauses, clock, rec, cursor = hooked
        reads = []
        pauses._clock = lambda: reads.append(1) or clock.t
        rec.enabled = False
        collect(pauses, clock, 900.0, gen=2)
        assert reads == [] and pauses.read(cursor) == ([], cursor)
        other = Recorder()
        acquire_by_hand(pauses, other)               # one on is enough
        collect(pauses, clock, 2.0)
        assert len(reads) == 2 and len(pauses.read(cursor)[0]) == 1
        pauses.release(other)
        collect(pauses, clock, 2.0)
        assert len(reads) == 2

    @pytest.mark.parametrize("info", [{}, {"generation": 2}, None])
    def test_the_hook_swallows_its_own_errors(self, hooked, info):
        pauses, clock, _, cursor = hooked
        pauses._on_gc("start", info)
        clock.t += 0.5
        pauses._on_gc("stop", info)                  # no raise
        pauses._clock = lambda: 1 / 0
        pauses._on_gc("start", {"generation": 0, "collected": 0})
        pauses._on_gc("stop", {"generation": 0, "collected": 0})
        pauses._clock = clock
        collect(pauses, clock, 4.0)                  # and it goes on
        assert len(pauses.read(cursor)[0]) >= 1
        assert pauses.errors >= 1                    # counted, not printed

    def test_a_stop_with_no_start_is_nothing(self, hooked):
        pauses, clock, _, cursor = hooked
        pauses._on_gc("stop", {"generation": 2, "collected": 9})
        assert pauses.read(cursor) == ([], cursor)

    def test_the_annotation_is_entered_and_left_once_a_collection(
            self, hooked):
        pauses, clock, _, _ = hooked
        seen = []

        class Annotation:
            def __init__(self, name, **kw):
                seen.append(("new", name, kw))

            def __enter__(self):
                seen.append("enter")

            def __exit__(self, *exc):
                seen.append("exit")

        pauses._annotation = Annotation
        collect(pauses, clock, 0.1, gen=1)
        assert seen == [("new", "host.gc", {"gen": 1}), "enter", "exit"]


class TestTheHandOffUnderThreads:
    def test_collecting_threads_and_two_readers_lose_and_double_nothing(
            self):
        """The real collector, more threads than cores forcing
        collections while two readers poll with their own cursors and
        the interpreter switches threads every 10 us: every reader sees
        every row once, in the collector's order (collections do not
        nest: a row ends before the next begins), and the sums agree."""
        pauses = HostPauses(capacity=1 << 14)
        cursors = [pauses.acquire(Recorder()), pauses.acquire(Recorder())]
        workers = (os.cpu_count() or 2) + 2
        done = threading.Event()
        got = [[], []]

        def force():
            for _ in range(25):
                junk = [[i] for i in range(200)]
                junk[0].append(junk)
                del junk
                gc.collect()                # generation 2: always a row

        def poll(k):
            while True:
                last = done.is_set()
                rows, cursors[k] = pauses.read(cursors[k])
                got[k].extend(rows)
                if last:
                    return

        before = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            readers = [threading.Thread(target=poll, args=(k,))
                       for k in range(2)]
            forcers = [threading.Thread(target=force)
                       for _ in range(workers)]
            for t in readers + forcers:
                t.start()
            for t in forcers:
                t.join(timeout=120)
            done.set()
            for t in readers:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in readers + forcers)
        finally:
            sys.setswitchinterval(before)
            gc.callbacks.remove(pauses._on_gc)
        assert pauses.errors == 0
        # (a collect() called while another thread collects returns at
        # once and calls no hook: fewer rows than calls)
        assert len(got[0]) == len(got[1]) == pauses._n >= 10
        assert got[0] == got[1]
        for a, b in zip(got[0], got[0][1:]):
            assert a[0] <= a[1] <= b[0]
        assert cursors[0][1] == cursors[1][1] == pauses.collections
        assert pauses.collections >= pauses._n


class TestHostCover:
    def test_the_union_counts_an_overlap_once(self):
        # a collection inside a late wake-up, and a call that straddles
        # the interval's start
        total, by = host_cover(10.0, 10.4, [
            (10.10, 10.30, "late_wake"), (10.15, 10.25, "gc"),
            (9.95, 10.02, "dispatch_call"), (11.0, 12.0, "gc")])
        assert total == pytest.approx(220.0)
        assert by == {"late_wake": pytest.approx(200.0),
                      "gc": pytest.approx(100.0),
                      "dispatch_call": pytest.approx(20.0)}

    def test_nothing_known_covers_nothing(self):
        assert host_cover(1.0, 2.0, []) == (0.0, {})
        assert host_cover(1.0, 2.0, [(0.0, 1.0, "gc"),
                                     (2.0, 3.0, "gc")]) == (0.0, {})

    def test_never_more_than_the_interval(self):
        total, by = host_cover(5.0, 5.1, [(0.0, 9.0, "gc"),
                                          (4.0, 6.0, "late_wake")])
        assert total == pytest.approx(100.0)
        assert by["gc"] == by["late_wake"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# gc.callbacks is left as it was found
# ---------------------------------------------------------------------------


def ours():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__self__", None) is flight.HOST_PAUSES]


class TestOneHookAProcess:
    def test_ten_starts_and_stops_leave_gc_callbacks_as_found(self, params):
        before = list(gc.callbacks)
        assert ours() == []
        eng = make_engine(params)
        for _ in range(10):
            eng.start()
            assert len(ours()) == 1
            eng.stop()
            assert ours() == []
        assert gc.callbacks == before

    def test_many_engines_share_one_hook(self, params):
        before = list(gc.callbacks)
        a, b = make_engine(params).start(), make_engine(params).start()
        try:
            assert len(ours()) == 1
            a.stop()
            assert len(ours()) == 1              # b still runs
            a.stop()                             # a second stop is nothing
            assert len(ours()) == 1
        finally:
            a.stop()
            b.stop()
        assert ours() == [] and gc.callbacks == before

    def test_an_engine_never_started_installs_nothing(self, params):
        eng = make_engine(params)
        drive_inline(eng, [GenRequest(prompt_ids=[3, 4], max_new_tokens=2)])
        assert ours() == [] and eng._pause_cursor is None
        assert events(eng, EV_HOST_PAUSE) == []


# ---------------------------------------------------------------------------
# call= on every program event; the accepted readers unmoved
# ---------------------------------------------------------------------------


def as_ctx(eng, seconds=1e9):
    """The engine's events the way benchmark/run.py hands them to a
    reader: `t` relative to a window that holds everything."""
    evs = eng.flight.snapshot_events()
    base = min(e["ts"] for e in evs) - 1.0
    for e in evs:
        e["t"] = e["ts"] - base
    return {"seconds": seconds, "engine": {"events": evs}}


@pytest.fixture(scope="module")
def driven(params):
    eng = make_engine(params, max_batch_size=1)     # one slot, three turns
    drive_inline(eng, [
        GenRequest(prompt_ids=[3, 4, 5, 6], max_new_tokens=6,
                   request_id="hp-0"),
        GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=4,
                   request_id="hp-1"),
        GenRequest(prompt_ids=[5, 6], max_new_tokens=5,
                   request_id="hp-2")])
    return eng


class TestDispatchCall:
    def test_every_program_event_of_an_inline_engine_carries_call(
            self, driven):
        evs = events(driven, EV_PROGRAM)
        assert len(evs) == driven.programs.enqueued > 0
        for e in evs:
            aux = parse_program_aux(e["aux"])
            assert list(aux)[:4] == ["seq", "n", "shape", "call"]
            # the call ends before the result is ready, and took time
            assert 0.0 < float(aux["call"]) <= e["a"] + 1e-3

    def test_every_program_event_of_a_started_engine_carries_call(
            self, params):
        eng = make_engine(params).start()
        try:
            req = eng.submit(GenRequest(prompt_ids=[3, 4, 5],
                                        max_new_tokens=5))
            got = 0
            while got < 5:
                got += req.stream.get(timeout=60)["token_id"] >= 0
        finally:
            eng.stop()
        evs = events(eng, EV_PROGRAM)
        assert evs and all(
            float(parse_program_aux(e["aux"])["call"]) > 0.0 for e in evs)
        hist = eng.metrics.snapshot()["hist_dispatch_call_ms"]
        assert hist["count"] == len(evs)

    def test_an_encoder_forward_stamps_its_call_too(self):
        from test_program_ledger import tiny_embedder
        emb = tiny_embedder()
        emb.embed(["one text", "another"])
        prog = emb.programs.drain()[0]
        assert prog.t_enqueue < prog.t_dispatched <= prog.t_ready
        assert "call=" in prog.aux() and prog.call_ms > 0.0

    def test_the_ledger_stamps_the_return_on_its_own_clock(self):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        prog = led.enqueue(PROG_DECODE, rows=4, n=8, shape="K8")
        clock.t += 0.0043
        led.dispatched(prog)
        assert prog.call_ms == pytest.approx(4.3)
        assert parse_program_aux(prog.aux())["call"] == "4.300"
        assert led.calls() == ((100.0, prog.t_dispatched),)
        prog.stalled, prog.host_ms = True, 212.34
        assert prog.aux().endswith("call=4.300 stalled=1 host=212.3")

    @pytest.mark.parametrize("metric", [
        "closed.window.longest_program_ms", "closed.window.decode_ms",
        "closed.window.device_busy_share", "sched.prefill_queue_p50_ms",
        "sched.prefill_run_p50_ms", "sched.first_token_lag_p50_ms",
        "closed.slot.admit_to_decode_p50_ms"])
    def test_the_accepted_program_readers_are_unmoved_by_call(
            self, driven, metric):
        """program_window, program_request and slot_interval take `aux`
        through a dict: the same events with `call=` (and `stalled=`)
        cut out read the same number."""
        from benchmark import run as bench_run
        with_call = as_ctx(driven)
        without = json.loads(json.dumps(with_call))
        for e in without["engine"]["events"]:
            if e["kind"] == EV_PROGRAM:
                e["aux"] = e["aux"].split(" call=")[0]
        for e in with_call["engine"]["events"]:
            if e["kind"] == EV_PROGRAM:
                e["aux"] += " stalled=1 host=1.0"
        got = bench_run.read_metric(metric, with_call)
        assert got is not None and got == bench_run.read_metric(
            metric, without)

    def test_the_new_reader_reads_the_engine_and_none_without_call(
            self, driven):
        from benchmark.readers import host_pause, program_window
        ctx = as_ctx(driven)
        longest = program_window.read(ctx, "longest")
        host = host_pause.read(ctx, "longest_program_host")
        assert 0.0 <= host <= longest + 1e-6
        assert host_pause.read(ctx, "longest_pause") >= \
            host_pause.read(ctx, "call_p99") > 0.0
        assert host_pause.read(ctx, "gc_pause") == 0.0
        for e in ctx["engine"]["events"]:
            e["aux"] = e["aux"].split(" call=")[0]
        for stat in ("gc_pause", "longest_pause", "call_p99",
                     "longest_program_host"):
            assert host_pause.read(ctx, stat) is None


# ---------------------------------------------------------------------------
# late wake-ups
# ---------------------------------------------------------------------------


class Held:
    """An event whose first wait is held `hold_s` and comes back unset."""

    def __init__(self, hold_s):
        self.hold_s = hold_s
        self.waits = 0

    def wait(self, timeout=None):
        self.waits += 1
        if self.waits == 1:
            time.sleep(self.hold_s)
            return False
        return True


class TestLateWake:
    def test_a_poll_held_60_ms_is_one_late_wake_of_55_or_more(self, params):
        eng = make_engine(params)
        held = Held(0.060)
        assert eng._timed_wait(held, 0.005, "fetch") is False
        assert eng._timed_wait(held, 0.005, "fetch") is True
        assert eng.metrics.snapshot()["host_late_wakes"] == 1
        (ev,) = events(eng, EV_HOST_PAUSE)
        assert ev["code"] == flight.PAUSE_LATE_WAKE and ev["b"] == 0.0
        assert 55.0 <= ev["a"] < 500.0
        assert ev["aux"] == "where=fetch"
        hist = eng.metrics.snapshot()["hist_host_pause_ms"]
        assert hist["count"] == 1
        # the row a later stall is held against: [its timeout's end, now]
        (t0, t1, cause), = eng._recent_pauses
        assert cause == "late_wake" and t1 == ev["ts"]
        assert (t1 - t0) * 1e3 == pytest.approx(ev["a"])

    @pytest.mark.parametrize("hold_s,timeout", [(0.0, 0.005), (0.012, 0.005),
                                                (0.030, 0.02)])
    def test_a_wait_on_time_or_a_little_late_is_nothing(
            self, params, hold_s, timeout):
        eng = make_engine(params)
        assert eng._timed_wait(Held(hold_s), timeout, "idle") is False
        assert eng.metrics.host_late_wakes == 0
        assert events(eng, EV_HOST_PAUSE) == []

    def test_a_wait_that_is_set_is_never_late(self, params):
        eng = make_engine(params)
        late_but_set = Held(0.060)
        late_but_set.waits = 1
        assert eng._timed_wait(late_but_set, 0.005, "fetch") is True
        assert eng.metrics.host_late_wakes == 0

    def test_with_the_recorder_off_no_clock_is_read(
            self, params, monkeypatch):
        eng = make_engine(params, flight_recorder=False)
        monkeypatch.setattr(engine_mod.time, "perf_counter",
                            lambda: 1 / 0)
        assert eng._timed_wait(Held(0.060), 0.005, "fetch") is False
        assert eng.metrics.host_late_wakes == 0

    def test_the_schedulers_polls_go_through_it(self, params, monkeypatch):
        """A started engine's fetch poll held 60 ms: the live loop
        writes the row itself."""
        eng = make_engine(params).start()
        try:
            real = eng._fetch_done.wait
            state = {"held": False}

            def wait(timeout=None):
                if not state["held"] and timeout == 0.005:
                    state["held"] = True
                    time.sleep(0.060)
                    return False
                return real(timeout)

            monkeypatch.setattr(eng._fetch_done, "wait", wait)
            req = eng.submit(GenRequest(prompt_ids=[3, 4, 5],
                                        max_new_tokens=8))
            got = 0
            while got < 8:
                got += req.stream.get(timeout=60)["token_id"] >= 0
        finally:
            eng.stop()
        assert state["held"]
        late = [e for e in events(eng, EV_HOST_PAUSE)
                if e["code"] == flight.PAUSE_LATE_WAKE
                and e["aux"] == "where=fetch"]
        assert late and max(e["a"] for e in late) >= 55.0
        assert eng.metrics.host_late_wakes >= 1


# ---------------------------------------------------------------------------
# a stall names its cause
# ---------------------------------------------------------------------------


def stalled_run(params, monkeypatch, caplog, freeze):
    """tests/test_program_ledger.py's injected stall: the ninth block
    fetch of a warmed, inline-driven engine runs `freeze()` first. The
    engine holds the collector's hook as a started one does."""
    real = engine_mod._to_host
    calls = {"n": 0}

    def late(blk):
        calls["n"] += 1
        if calls["n"] == 9:
            freeze()
        return real(blk)

    eng = make_engine(params, max_batch_size=1)
    drive_inline(eng, [GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=30)])
    eng._pause_cursor = flight.HOST_PAUSES.acquire(eng.flight)
    try:
        before = eng.metrics.snapshot()
        caplog.clear()
        monkeypatch.setattr(engine_mod, "_to_host", late)
        req = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=30)
        with caplog.at_level(logging.WARNING, logger=engine_mod._LOG.name):
            drive_inline(eng, [req])
        assert len(tokens(req)) == 30
    finally:
        flight.HOST_PAUSES.release(eng.flight)
    rows = program_rows(eng)
    first = int(events(eng, EV_PREFILL_DISPATCH)[-1]["b"])
    longest = max((r[0] for s, r in rows.items() if s >= first),
                  key=lambda e: e["b"])
    seq = parse_program_aux(longest["aux"])["seq"]
    (line,) = [r.getMessage() for r in caplog.records
               if "device program stalled" in r.getMessage()
               and f"seq={seq} " in r.getMessage()]
    return eng, before, longest, line


class TestAStallNamesItsCause:
    def test_a_collection_that_overlaps_the_stall_is_named(
            self, params, monkeypatch, caplog):
        """The freeze is a forced collection made 300 ms long by a
        callback that sleeps between the hook's two stamps."""
        slow = {"on": False}

        def sleeper(phase, info):
            if slow["on"] and phase == "start":
                slow["on"] = False
                time.sleep(0.3)

        def freeze():
            gc.callbacks.append(sleeper)    # behind the engine's hook
            slow["on"] = True
            gc.collect()

        try:
            eng, before, longest, line = stalled_run(
                params, monkeypatch, caplog, freeze)
        finally:
            if sleeper in gc.callbacks:
                gc.callbacks.remove(sleeper)
        pause = max((e for e in events(eng, EV_HOST_PAUSE)
                     if e["code"] == flight.PAUSE_GC), key=lambda e: e["a"])
        assert 300.0 <= pause["a"] < 1500.0 and pause["b"] == 2.0
        aux = parse_program_aux(pause["aux"])
        assert aux["gen"] == "2" and int(aux["collected"]) >= 0
        assert aux["thread"] == threading.current_thread().name.replace(
            " ", "_")
        prog = parse_program_aux(longest["aux"])
        assert prog["stalled"] == "1"
        # within a thread switch of the collection's own length
        assert abs(float(prog["host"]) - pause["a"]) <= 10.0
        assert float(prog["host"]) <= longest["b"]
        after = eng.metrics.snapshot()
        assert after["program_stalls_host"] \
            == before["program_stalls_host"] + 1
        assert after["host_gc_pauses"] > before["host_gc_pauses"]
        assert after["host_gc_collections"] > before["host_gc_collections"]
        assert after["host_gc_pause_ms"] >= before["host_gc_pause_ms"] + 300
        assert f"host={float(prog['host']):.1f} ms of " \
            f"{longest['b']:.1f} ms (gc " in line
        assert "dispatch_call " in line and "late_wake 0.0 ms)" in line
        gc_ms = float(line.split("(gc ")[1].split(" ms")[0])
        assert abs(gc_ms - pause["a"]) <= 10.0

    def test_a_stall_with_no_pause_is_not_the_hosts(
            self, params, monkeypatch, caplog):
        """The freeze is the fetch itself, 0.4 s late, as a device that
        stopped would make it: nothing the host did covers it."""
        eng, before, longest, line = stalled_run(
            params, monkeypatch, caplog, lambda: time.sleep(0.4))
        assert 400.0 <= longest["b"] < 1500.0
        prog = parse_program_aux(longest["aux"])
        assert prog["stalled"] == "1"
        # all the host did meanwhile was a dispatch call or two
        assert float(prog["host"]) < 0.25 * longest["b"]
        after = eng.metrics.snapshot()
        assert after["program_stalls"] > before["program_stalls"]
        assert after["program_stalls_host"] == before["program_stalls_host"]
        assert "(gc 0.0 ms, dispatch_call " in line

    def test_nothing_known_reads_host_0(self, params, caplog):
        eng = make_engine(params)
        prog = Program(7, PROG_DECODE, 4, 8, "K8", 50.0)
        prog.t_start, prog.t_ready = 50.0, 50.9
        prog.stalled, prog.median_ms = True, 100.0
        with caplog.at_level(logging.WARNING, logger=engine_mod._LOG.name):
            eng._note_stall(prog)
        assert prog.host_ms == 0.0
        assert eng.metrics.program_stalls == 1
        assert eng.metrics.program_stalls_host == 0
        assert caplog.records[-1].getMessage().endswith(
            "host=0.0 ms of 900.0 ms (gc 0.0 ms, dispatch_call 0.0 ms, "
            "late_wake 0.0 ms)")
        assert prog.aux().endswith("stalled=1 host=0.0")

    @pytest.mark.parametrize("covered_ms,hosts", [(390.0, 0), (410.0, 1),
                                                  (900.0, 1)])
    def test_half_of_what_it_ran_over_the_median_makes_it_the_hosts(
            self, params, covered_ms, hosts):
        eng = make_engine(params)
        prog = Program(7, PROG_DECODE, 4, 8, "K8", 50.0)
        prog.t_start, prog.t_ready = 50.0, 50.9      # ran 900, median 100
        prog.stalled, prog.median_ms = True, 100.0
        eng._recent_pauses.append((50.1, 50.1 + covered_ms / 1e3,
                                   "late_wake"))
        eng._note_stall(prog)
        assert prog.host_ms == pytest.approx(min(covered_ms, 800.0))
        assert eng.metrics.program_stalls_host == hosts

    def test_a_call_enqueued_inside_the_interval_counts(self, params):
        eng = make_engine(params)
        clock = FakeClock(50.2)
        eng.programs._clock = clock
        blocked = eng.programs.enqueue(PROG_DECODE, 4, 8, "K8")
        clock.t = 50.5                               # a call of 300 ms
        eng.programs.dispatched(blocked)
        prog = Program(7, PROG_DECODE, 4, 8, "K8", 50.0)
        prog.t_start, prog.t_ready = 50.0, 50.6
        prog.stalled, prog.median_ms = True, 60.0
        eng._note_stall(prog)
        assert prog.host_ms == pytest.approx(300.0)
        assert eng.metrics.program_stalls_host == 1


# ---------------------------------------------------------------------------
# the operator's keys, the timeline, the analyzer, on and off
# ---------------------------------------------------------------------------


class TestOperatorsKeys:
    @pytest.mark.parametrize("key", COUNTERS + HISTS)
    def test_new_keys_zero_and_present_on_an_idle_engine(self, params, key):
        snap = make_engine(params).metrics.snapshot()
        assert key in snap
        if key.startswith("hist_"):
            assert snap[key]["count"] == 0 and snap[key]["buckets"] == {}
            assert key in flight.HIST_KEYS
        else:
            assert snap[key] == 0 and key in fleet.counter_keys()
        text = flight.prometheus_text(snap)
        name = "gaie_" + (key[5:] if key.startswith("hist_") else key)
        assert name in text

    def test_a_fleet_sums_them(self, params):
        from generativeaiexamples_tpu.serving.fleet import (
            EngineFleet, LocalReplica)
        a, b = make_engine(params), make_engine(params)
        a.metrics.host_late_wakes, b.metrics.host_late_wakes = 2, 3
        a.metrics.host_gc_pause_ms, b.metrics.host_gc_pause_ms = 1.5, 2.25
        a.metrics.program_stalls_host = 1
        snap = EngineFleet([LocalReplica("a", a), LocalReplica("b", b)],
                           ByteTokenizer(), 8).metrics.snapshot()
        assert snap["host_late_wakes"] == 5
        assert snap["host_gc_pause_ms"] == pytest.approx(3.75)
        assert snap["program_stalls_host"] == 1
        assert snap["host_gc_collections"] == 0
        assert snap["hist_host_pause_ms"]["count"] == 0

    def test_event_names_and_kinds(self):
        assert EV_HOST_PAUSE == 25
        assert flight.EVENT_NAMES[EV_HOST_PAUSE] == "host_pause"
        assert len(flight.EVENT_NAMES) == 26  # 25 kinds at PR 54; `residual_mix` (26) since PR 57
        assert flight.PAUSE_CAUSES == ("gc", "late_wake")
        assert (flight.PAUSE_MIN_MS, flight.LATE_WAKE_MS) == (1.0, 20.0)


class TestTimelineAndAnalyzer:
    def recorder(self):
        """A block, then 300 ms of nothing the device did, in which a
        collection of 200 ms lies inside a late wake-up of 250 ms and a
        dispatch call of 20 ms follows; then the next block."""
        rec = flight.FlightRecorder(ring_size=64)
        beat = dict(decode_k=8, spec_k=0, tree_branches=0, rider_width=0,
                    spec_state=False, fused_rider=False, qos_paused=False,
                    busy=(0, 4, 0), wait=(0, 0, 0), tokens_emitted=32,
                    kv_demote_pages=0, kv_promote_pages=0)
        rec.record_beat(10.000, 10.100, 0.0, **beat)
        rec.record_event(EV_HOST_PAUSE, 10.330, code=flight.PAUSE_GC,
                         a=200.0, b=2.0, aux="gen=2 collected=5 thread=t")
        rec.record_event(EV_HOST_PAUSE, 10.370,
                         code=flight.PAUSE_LATE_WAKE, a=250.0,
                         aux="where=fetch")
        rec.record_event(EV_PROGRAM, 10.500, code=PROG_DECODE, slot=4,
                         a=120.0, b=100.0,
                         aux="seq=1 n=8 shape=K8 call=20.000")
        rec.record_beat(10.400, 10.500, 10.100, **beat)
        return rec

    def test_pauses_are_slices_on_the_scheduler_lane_and_nest(self):
        trace = chrome_trace({"r0": self.recorder()})
        slices = [e for e in trace["traceEvents"]
                  if e.get("cat") == "host-pause"]
        assert {e["name"] for e in slices} == {
            "gc", "late_wake", "dispatch_call"}
        assert all(e["ph"] == "X" and e["tid"] == flight.TID_SCHED
                   for e in slices)
        by = {e["name"]: e for e in slices}
        assert by["gc"]["dur"] == pytest.approx(200e3, abs=1)
        assert by["late_wake"]["dur"] == pytest.approx(250e3, abs=1)
        assert by["dispatch_call"]["dur"] == pytest.approx(20e3, abs=1)
        assert by["dispatch_call"]["args"]["seq"] == 1
        assert spans_nest(trace)

    def test_partly_overlapping_pauses_still_nest(self):
        rec = flight.FlightRecorder(ring_size=64)
        for end, ms, code in ((10.0, 10000.0, 0), (12.0, 8000.0, 1),
                              (6.0, 1000.0, 0), (11.0, 5500.0, 0)):
            rec.record_event(EV_HOST_PAUSE, end, code=code, a=ms)
        trace = chrome_trace({"r0": rec})
        assert spans_nest(trace)
        slices = [e for e in trace["traceEvents"]
                  if e.get("cat") == "host-pause"]
        assert len(slices) == 4
        # their union is what it was: 0 -> 12 s
        assert min(e["ts"] for e in slices) == 0.0
        assert max(e["ts"] + e["dur"] for e in slices) == pytest.approx(12e6)

    def test_the_analyzer_charges_a_gap_to_the_pause_that_covers_it(self):
        import analyze_timeline
        report = analyze_timeline.analyze(
            chrome_trace({"r0": self.recorder()}))
        cats = report["overall"]["categories"]
        # the 300 ms gap: 200 gc, the 50 of the late wake-up the
        # collection does not cover, 20 the call, 30 nobody's
        assert cats["gc"]["ms"] == pytest.approx(200.0, abs=0.01)
        assert cats["late_wake"]["ms"] == pytest.approx(50.0, abs=0.01)
        assert cats["dispatch_call"]["ms"] == pytest.approx(20.0, abs=0.01)
        assert cats["host_gap"]["ms"] == pytest.approx(30.0, abs=0.01)
        assert cats["device_busy"]["ms"] == pytest.approx(200.0, abs=0.01)
        assert report["overall"]["attributed_pct"] == pytest.approx(
            100.0, abs=0.05)
        assert report["overall"]["top_causes"][0] == "gc"

    def test_a_timeline_without_pauses_reads_as_before(self):
        import analyze_timeline
        rec = self.recorder()
        trace = chrome_trace({"r0": rec})
        trace["traceEvents"] = [e for e in trace["traceEvents"]
                                if e.get("cat") != "host-pause"]
        cats = analyze_timeline.analyze(trace)["overall"]["categories"]
        assert cats["idle"]["ms"] == pytest.approx(300.0, abs=0.01)
        assert not {"gc", "late_wake", "dispatch_call"} & set(cats)


class TestOnAndOff:
    def test_streams_byte_identical_with_the_recorder_on_and_off(
            self, params):
        on, toks_on = stream_of(params, True)
        off, toks_off = stream_of(params, False)
        assert toks_on == toks_off and [len(t) for t in toks_on] == [7, 5]
        snap = off.metrics.snapshot()
        assert snap["hist_dispatch_call_ms"]["count"] == 0
        assert snap["hist_host_pause_ms"]["count"] == 0
        assert on.metrics.snapshot()["hist_dispatch_call_ms"]["count"] \
            == on.programs.enqueued > 0

    def test_a_started_engine_with_the_recorder_off_stamps_nothing(
            self, params):
        eng = make_engine(params, flight_recorder=False).start()
        try:
            req = eng.submit(GenRequest(prompt_ids=[3, 4, 5],
                                        max_new_tokens=5))
            got = 0
            while got < 5:
                got += req.stream.get(timeout=60)["token_id"] >= 0
            gc.collect()
            time.sleep(0.05)
        finally:
            eng.stop()
        snap = eng.metrics.snapshot()
        assert [snap[k] for k in COUNTERS] == [0, 0, 0, 0, 0]
        assert snap["flight_events"] == 0 and eng.programs.enqueued == 0

    def test_a_forced_collection_reaches_a_started_engines_ring(
            self, params):
        eng = make_engine(params).start()
        try:
            gc.collect()                 # generation 2: always listed
            req = eng.submit(GenRequest(prompt_ids=[3, 4, 5],
                                        max_new_tokens=5))
            got = 0
            while got < 5:
                got += req.stream.get(timeout=60)["token_id"] >= 0
        finally:
            eng.stop()
        pauses = [e for e in events(eng, EV_HOST_PAUSE)
                  if e["code"] == flight.PAUSE_GC]
        assert any(e["b"] == 2.0 for e in pauses)
        snap = eng.metrics.snapshot()
        assert snap["host_gc_pauses"] == len(pauses) >= 1
        assert snap["host_gc_collections"] >= snap["host_gc_pauses"]
        assert snap["host_gc_pause_ms"] >= sum(e["a"] for e in pauses) - 1e-6
        assert snap["hist_host_pause_ms"]["count"] >= len(pauses)


def test_flight_still_needs_no_jax_at_import():
    """The chain server imports serving/flight.py and must not get JAX
    with it: the annotation is looked up when the first engine starts."""
    assert "jax" not in vars(flight)
    assert HostPauses()._annotation is None
