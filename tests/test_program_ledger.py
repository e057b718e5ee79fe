"""The ledger of every program the process puts on the device
(serving/flight.py::ProgramLedger) and what the engine and the encoders
write through it: in-order start inference on a fake clock, the
hand-off that keeps the flight ring single-writer, the stall rule, the
`program` and `decode_join` events, the slot on `retire` and
`first_token`, the sequence number on `prefill_dispatch`, the tiling of
a request's way to its first token, streams byte-identical with the
recorder on and off, and the operator's keys."""

import logging
import os
import queue
import sys
import threading

import jax
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import bert, llama
from generativeaiexamples_tpu.serving import engine as engine_mod
from generativeaiexamples_tpu.serving import fleet, flight
from generativeaiexamples_tpu.serving.encoders import (
    EmbeddingEngine, RerankEngine)
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.flight import (
    EV_ADMIT, EV_DECODE_JOIN, EV_FIRST_TOKEN, EV_PREFILL_DISPATCH,
    EV_PROGRAM, EV_RETIRE, PROG_CHUNK, PROG_DECODE, PROG_ENCODER,
    PROG_PREFILL, FlightRecorder, ProgramLedger, chrome_trace,
    parse_program_aux, spans_nest)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = llama.LlamaConfig.tiny()
NEW_HISTS = ("hist_device_queue_ms", "hist_program_ms_prefill")


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def make_engine(params, **over):
    cfg = dict(max_batch_size=2, max_seq_len=128, page_size=8,
               prefill_buckets=(16,), decode_steps_per_dispatch=2,
               pace_emission_max_streams=0)
    cfg.update(over)
    return LLMEngine(params, TINY, ByteTokenizer(), EngineConfig(**cfg),
                     use_pallas=False)


def drive_inline(eng, reqs, max_iters=400):
    """The scheduler's loop body on this thread (tests/test_flight.py)."""
    for r in reqs:
        eng.submit(r)
    for _ in range(max_iters):
        eng._admit_waiting()
        eng._advance_long_prefills()
        eng._emit_ready_first_tokens()
        while (len(eng._inflight) < eng.pipeline_depth
               and any(s is not None for s in eng.slots)):
            if not eng._dispatch_decode():
                break
        if eng._inflight:
            eng._land_next_block()
        if (all(s is None for s in eng.slots) and not eng.waiting
                and not eng._inflight and not eng._pending_first):
            break
    eng._emit_ready_first_tokens()  # one last drain of the ledger


def tokens(req):
    out = []
    while True:
        try:
            ev = req.stream.get_nowait()
        except queue.Empty:
            return out
        if ev["token_id"] >= 0:
            out.append(ev["token_id"])


def events(eng, kind):
    return [e for e in eng.flight.snapshot_events() if e["kind"] == kind]


def program_rows(eng):
    """`program` events parsed: seq -> (event, t_enqueue, t_start)."""
    out = {}
    for e in events(eng, EV_PROGRAM):
        seq = int(parse_program_aux(e["aux"])["seq"])
        out[seq] = (e, e["ts"] - e["a"] / 1e3, e["ts"] - e["b"] / 1e3)
    return out


# ---------------------------------------------------------------------------
# the ledger alone, on a fake clock
# ---------------------------------------------------------------------------


class TestLedgerOnAFakeClock:
    def test_start_is_the_later_of_enqueue_and_the_previous_ready(self):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        block = led.enqueue(PROG_DECODE, rows=4, n=8, shape="K8")
        clock.t = 100.010
        prefill = led.enqueue(PROG_PREFILL, rows=1, n=90, shape="1x128")
        assert (block.seq, prefill.seq) == (0, 1)
        assert led.drain() == []            # nothing complete yet
        clock.t = 100.100
        led.ready(block)
        clock.t = 100.130
        led.ready(prefill)
        done = led.drain()
        assert [p.seq for p in done] == [0, 1]
        assert block.t_start == 100.0 and block.t_prev_ready == 0.0
        assert block.ran_ms == pytest.approx(100.0)
        # the prefill waited 90 ms behind the block and ran 30 ms
        assert prefill.t_start == 100.100
        assert prefill.t_prev_ready == 100.100
        assert prefill.queued_ms == pytest.approx(90.0)
        assert prefill.ran_ms == pytest.approx(30.0)
        assert prefill.waited_ms == pytest.approx(120.0)
        assert led.drain() == []

    def test_an_idle_queue_starts_a_program_at_its_enqueue(self):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        a = led.enqueue(PROG_DECODE, n=2, shape="K2")
        clock.t = 100.02
        led.ready(a)
        clock.t = 105.0                     # five idle seconds
        b = led.enqueue(PROG_PREFILL, n=10, shape="1x16")
        clock.t = 105.03
        led.ready(b)
        led.drain()
        assert b.t_start == 105.0 and b.queued_ms == 0.0
        assert b.ran_ms == pytest.approx(30.0)

    def test_an_encoder_forward_between_a_block_and_a_prefill(self):
        """Three classes, three threads' stamps, one queue: the forward
        is charged its own 8 ms, and the prefill behind it starts where
        the forward ended, not where the block did."""
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        block = led.enqueue(PROG_DECODE, rows=60, n=8, shape="K8")
        clock.t = 100.020
        enc = led.enqueue(PROG_ENCODER, rows=1, n=14, shape="16x32")
        clock.t = 100.030
        prefill = led.enqueue(PROG_PREFILL, rows=2, n=260, shape="2x512")
        clock.t = 100.105
        led.ready(block)
        clock.t = 100.113
        led.ready(enc)
        clock.t = 100.200
        led.ready(prefill)
        led.drain()
        assert enc.t_start == 100.105 and enc.ran_ms == pytest.approx(8.0)
        assert enc.queued_ms == pytest.approx(85.0)
        assert prefill.t_start == 100.113
        assert prefill.ran_ms == pytest.approx(87.0)
        # device time adds up to the busy stretch, class by class
        assert block.ran_ms + enc.ran_ms + prefill.ran_ms == \
            pytest.approx(200.0)

    def test_drain_waits_for_the_head_and_keeps_enqueue_order(self):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        first = led.enqueue(PROG_PREFILL, n=5, shape="1x16")
        second = led.enqueue(PROG_ENCODER, n=5, shape="4x32")
        clock.t = 100.05
        led.ready(second)                   # its thread woke first
        assert led.drain() == []            # the head is still open
        clock.t = 100.06
        led.ready(first)
        assert [p.seq for p in led.drain()] == [0, 1]
        # a late stamp on the program before never makes a negative run
        assert second.ran_ms == 0.0 and second.t_start == second.t_ready

    def test_a_landed_block_proves_the_programs_before_it(self):
        """No waiter thread (an inline driver): the prefill nobody
        stamped takes the bound the block behind it proves."""
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        prefill = led.enqueue(PROG_PREFILL, n=12, shape="1x16")
        block = led.enqueue(PROG_DECODE, n=2, shape="K2")
        later = led.enqueue(PROG_PREFILL, n=12, shape="1x16")
        clock.t = 100.04
        led.ready(block)
        assert led.drain() == []            # unproved: still waits
        done = led.drain(proved=block.seq)
        assert [p.seq for p in done] == [0, 1]
        assert prefill.t_ready == block.t_ready
        assert later.t_ready == 0.0 and led.drain(proved=block.seq) == []

    def test_a_cancelled_dispatch_leaves_no_row(self):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        bad = led.enqueue(PROG_PREFILL, n=5, shape="1x16")
        led.cancel(bad)
        good = led.enqueue(PROG_DECODE, n=2, shape="K2")
        clock.t = 100.01
        led.ready(good)
        assert [p.seq for p in led.drain()] == [1]
        assert good.t_start == 100.0

    def test_first_stamp_stands(self):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)
        p = led.enqueue(PROG_PREFILL)
        clock.t = 100.5
        led.ready(p)
        clock.t = 101.0
        led.ready(p)
        led.ready(p, 102.0)
        assert p.t_ready == 100.5

    def test_undrained_ledger_drops_the_oldest(self):
        led = ProgramLedger(clock=FakeClock(), capacity=8)
        for _ in range(20):
            led.ready(led.enqueue(PROG_ENCODER, n=1, shape="4x32"))
        assert led.enqueued == 20 and led.dropped == 12
        assert [p.seq for p in led.drain()] == list(range(12, 20))

    @pytest.mark.parametrize("late_ms,stalled", [(79.0, False),
                                                 (81.0, True)])
    def test_stall_is_eight_times_the_running_median(self, late_ms, stalled):
        clock = FakeClock()
        led = ProgramLedger(clock=clock)

        def run(ms, cls=PROG_DECODE, shape="K8"):
            p = led.enqueue(cls, n=8, shape=shape)
            clock.t += ms / 1e3
            led.ready(p)
            led.drain()
            return p

        assert not any(run(10.0).stalled for _ in range(6))
        assert run(late_ms).stalled is stalled
        assert not run(10.0).stalled        # the median did not move
        # another shape and another class have medians of their own:
        # too few samples say nothing
        assert not run(500.0, shape="K2").stalled
        assert not run(500.0, cls=PROG_PREFILL, shape="1x2048").stalled

    def test_aux_round_trips(self):
        led = ProgramLedger(clock=FakeClock())
        p = led.enqueue(PROG_CHUNK, rows=1, n=300, shape="W512/S4096")
        assert parse_program_aux(p.aux()) == {
            "seq": "0", "n": "300", "shape": "W512/S4096",
            "call": "0.000"}        # (PR 54: nobody stamped the return)


# ---------------------------------------------------------------------------
# the hand-off: other threads stamp, one thread writes the ring
# ---------------------------------------------------------------------------


def tiny_embedder():
    cfg = bert.BertConfig.tiny()
    return EmbeddingEngine(bert.init_params(cfg, jax.random.PRNGKey(1)),
                           cfg, ByteTokenizer(), max_batch=4,
                           buckets=(32, 64), use_pallas=False)


class TestHandOff:
    def test_encoder_threads_never_write_the_ring(self):
        emb = tiny_embedder()
        rec = FlightRecorder(ring_size=64)
        led = emb.programs = ProgramLedger()
        threads = [threading.Thread(
            target=lambda i=i: emb.embed([f"text number {i}"] * (1 + i % 3)))
            for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # six forwards stamped by six threads; the ring saw none of it
        assert led.enqueued == 6
        assert rec.stats()["flight_events"] == 0
        done = led.drain()                  # the ring's one writer
        for p in done:
            rec.record_event(EV_PROGRAM, p.t_ready, code=p.cls, slot=p.rows,
                             a=p.waited_ms, b=p.ran_ms, aux=p.aux())
        evs = rec.snapshot_events()
        assert [int(parse_program_aux(e["aux"])["seq"]) for e in evs] == \
            list(range(6))
        assert {e["code"] for e in evs} == {PROG_ENCODER}
        assert all(e["a"] >= e["b"] >= 0.0 for e in evs)
        assert sorted(e["slot"] for e in evs) == [1, 1, 2, 2, 3, 3]
        assert all(parse_program_aux(e["aux"])["shape"] == "4x32"
                   for e in evs)

    def test_an_encoder_alone_keeps_a_bounded_ledger_of_its_own(self):
        emb = tiny_embedder()
        own = emb.programs
        for i in range(3):
            emb.embed(["alone"])
        assert own.enqueued == 3 and own.dropped == 0
        assert own._capacity == 256

    def test_the_reranker_passes_the_same_stamp(self):
        import dataclasses

        cfg = dataclasses.replace(bert.BertConfig.tiny(), n_labels=1,
                                  normalize=False)
        rr = RerankEngine(bert.init_params(cfg, jax.random.PRNGKey(2)),
                          cfg, ByteTokenizer(), max_batch=2,
                          buckets=(32, 64), use_pallas=False)
        scores = rr.score("which passage", ["first", "second", "third"])
        assert scores.shape == (3,)
        done = rr.programs.drain()
        assert [(p.cls, p.rows, p.shape) for p in done] == [
            (PROG_ENCODER, 2, "2x32"), (PROG_ENCODER, 1, "2x32")]
        assert all(p.n > 0 and p.ran_ms >= 0.0 for p in done)

    def test_the_server_points_the_encoders_at_the_engines_ledger(
            self, params):
        from generativeaiexamples_tpu.serving.openai_server import (
            OpenAIServer)

        eng = make_engine(params)
        emb = tiny_embedder()
        alone = emb.programs
        OpenAIServer(eng, emb, None)
        assert emb.programs is eng.programs and alone is not eng.programs
        emb.embed(["one query"])
        drive_inline(eng, [GenRequest(prompt_ids=[3, 4, 5],
                                      max_new_tokens=4)])
        rows = program_rows(eng)
        assert sorted(rows) == list(range(len(rows)))  # one sequence
        assert rows[0][0]["code"] == PROG_ENCODER
        assert {e["code"] for e, _, _ in rows.values()} == {
            PROG_ENCODER, PROG_PREFILL, PROG_DECODE}
        # a surface with no engine leaves the encoder its own
        lone = tiny_embedder()
        own = lone.programs
        OpenAIServer(None, lone, None)
        assert lone.programs is own


# ---------------------------------------------------------------------------
# the engine, driven inline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def driven(params):
    eng = make_engine(params)
    reqs = [GenRequest(prompt_ids=[3, 4, 5, 6], max_new_tokens=6,
                       request_id="pl-0"),
            GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=9,
                       request_id="pl-1"),
            GenRequest(prompt_ids=[5, 5], max_new_tokens=4,
                       request_id="pl-2")]
    drive_inline(eng, reqs)
    return eng, reqs


class TestEngineEvents:
    def test_every_program_has_one_event_in_sequence(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        assert sorted(rows) == list(range(eng.programs.enqueued))
        classes = [rows[s][0]["code"] for s in sorted(rows)]
        assert classes.count(PROG_PREFILL) == 2   # a group of 2, then 1
        assert classes.count(PROG_DECODE) == len(eng.flight.snapshot_beats())
        for ev, t_enq, t_start in rows.values():
            assert ev["a"] >= ev["b"] >= 0.0
            assert t_enq <= t_start + 1e-9 <= ev["ts"] + 2e-9

    def test_starts_follow_the_previous_ready(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        for seq in sorted(rows)[1:]:
            ev, t_enq, t_start = rows[seq]
            prev_ready = max(rows[s][0]["ts"] for s in rows if s < seq)
            want = min(max(t_enq, prev_ready), ev["ts"])
            assert t_start == pytest.approx(want, abs=2e-6)

    def test_program_rows_carry_rows_steps_tokens_and_shape(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        first = rows[0][0]                  # pl-0 and pl-1 in one group
        assert first["code"] == PROG_PREFILL and first["slot"] == 2
        aux = parse_program_aux(first["aux"])
        assert float(aux.pop("call")) > 0.0     # (PR 54: its call's ms)
        assert aux == {"seq": "0", "n": "7", "shape": "2x16"}
        block = next(e for e, _, _ in rows.values()
                     if e["code"] == PROG_DECODE)
        aux = parse_program_aux(block["aux"])
        assert aux["shape"] == "K" + aux["n"] and 1 <= block["slot"] <= 2

    def test_prefill_dispatch_names_its_program(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        disp = {e["rid"]: e for e in events(eng, EV_PREFILL_DISPATCH)}
        assert disp["pl-0"]["b"] == disp["pl-1"]["b"] == 0.0
        late = int(disp["pl-2"]["b"])
        assert late > 0 and rows[late][0]["code"] == PROG_PREFILL
        # stamped after the dispatch call returned: inside the program's
        # enqueue -> ready, on the same clock
        for rid, e in disp.items():
            ev, t_enq, _ = rows[int(e["b"])]
            assert t_enq <= e["ts"]

    def test_first_token_and_retire_carry_the_slot(self, driven):
        eng, _ = driven
        admitted = {e["rid"]: e["slot"] for e in events(eng, EV_ADMIT)}
        assert sorted(admitted.values()) == [0, 0, 1] \
            or sorted(admitted.values()) == [0, 1, 1]
        for kind in (EV_FIRST_TOKEN, EV_RETIRE):
            got = {e["rid"]: e["slot"] for e in events(eng, kind)}
            assert got == admitted, kind

    def test_decode_join_is_the_first_block_a_slot_rides(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        joins = events(eng, EV_DECODE_JOIN)
        assert sorted(e["rid"] for e in joins) == ["pl-0", "pl-1", "pl-2"]
        admitted = {e["rid"]: e for e in events(eng, EV_ADMIT)}
        for e in joins:
            block, t_enq, t_start = rows[int(e["b"])]
            assert block["code"] == PROG_DECODE
            assert e["ts"] == pytest.approx(t_enq, abs=2e-6)
            assert e["slot"] == admitted[e["rid"]]["slot"]
            # admit -> the block's device start: the slot's second
            # empty interval, never negative
            assert t_start >= admitted[e["rid"]]["ts"]
        # pl-0 and pl-1 were prefilled together and ride one block
        by_rid = {e["rid"]: e["b"] for e in joins}
        assert by_rid["pl-0"] == by_rid["pl-1"] < by_rid["pl-2"]

    def test_queue_run_lag_tile_enqueue_to_first_token(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        firsts = {e["rid"]: e for e in events(eng, EV_FIRST_TOKEN)}
        for d in events(eng, EV_PREFILL_DISPATCH):
            ev, t_enq, t_start = rows[int(d["b"])]
            queue_ms = (t_start - t_enq) * 1e3
            run_ms = ev["b"]
            lag_ms = (firsts[d["rid"]]["ts"] - ev["ts"]) * 1e3
            assert queue_ms >= -1e-6 and lag_ms >= 0.0
            whole = (firsts[d["rid"]]["ts"] - t_enq) * 1e3
            assert queue_ms + run_ms + lag_ms == pytest.approx(
                whole, abs=1e-3)            # to the microsecond
            # and they exceed dispatch -> first token by the dispatch
            # call's own host time
            call_ms = (d["ts"] - t_enq) * 1e3
            assert call_ms >= 0.0
            assert whole - call_ms == pytest.approx(
                (firsts[d["rid"]]["ts"] - d["ts"]) * 1e3, abs=1e-3)

    def test_beat_rows_take_their_ends_from_the_ledger(self, driven):
        eng, _ = driven
        rows = program_rows(eng)
        blocks = [s for s in sorted(rows)
                  if rows[s][0]["code"] == PROG_DECODE]
        beats = eng.flight.snapshot_beats()
        assert len(beats) == len(blocks)
        for beat, seq in zip(beats, blocks):
            assert float(beat["t_ready"]) == rows[seq][0]["ts"]
            # the ready of the programs ENQUEUED before it (the latest
            # of them: stamps only move forward), a prefill's too
            earlier = [rows[s][0]["ts"] for s in rows if s < seq]
            assert float(beat["t_prev_ready"]) == (
                max(earlier) if earlier else 0.0)

    def test_histograms_are_fed_by_prefill_programs(self, driven):
        eng, _ = driven
        snap = eng.metrics.snapshot()
        assert snap["hist_device_queue_ms"]["count"] == 2
        assert snap["hist_program_ms_prefill"]["count"] == 2
        assert snap["program_stalls"] == 0

    def test_timeline_draws_programs_on_the_device_lane(self, driven):
        eng, _ = driven
        trace = chrome_trace({"engine": eng.flight})
        assert spans_nest(trace)
        lane = [e for e in trace["traceEvents"]
                if e.get("ph") == "X" and e["tid"] == flight.TID_BEATS]
        progs = [e for e in lane if e["cat"] == "program"]
        assert [e["args"]["class"] for e in progs] == ["prefill", "prefill"]
        assert progs[0]["name"] == "prefill 2x16"
        assert all(e["ts"] >= 0.0 for e in lane)
        from scripts.analyze_timeline import analyze

        report = analyze(trace)["overall"]
        classes = report["device_busy_by_class"]
        assert set(classes) == {"decode", "prefill"}
        assert sum(v["ms"] for v in classes.values()) == pytest.approx(
            report["categories"]["device_busy"]["ms"], abs=0.05)
        assert 99.0 <= report["attributed_pct"] <= 101.0


class TestWaiterAndFetch:
    def test_the_waiters_earlier_stamp_stands_over_the_fetchs(self, params):
        """The scheduler hands a block to the reader when it gets to
        it; a block that completed meanwhile keeps the stamp of the
        thread that waited on it since its dispatch."""
        eng = make_engine(params)
        fl = engine_mod._InFlight(None, [], 2)
        fl.prog = eng.programs.enqueue(PROG_DECODE, 1, 2, "K2")
        eng.programs.ready(fl.prog, fl.prog.t_enqueue + 0.100)  # waiter
        eng._note_block_ready(fl, fl.prog.t_enqueue + 0.180)    # fetch
        assert fl.t_ready == fl.prog.t_ready == fl.prog.t_enqueue + 0.100
        # no row (recorder off): the fetch's own clock reading
        bare = engine_mod._InFlight(None, [], 2)
        eng._note_block_ready(bare, 12.5)
        assert bare.t_ready == 12.5

    def test_a_started_engine_stamps_through_its_waiter(self, params):
        eng = make_engine(params).start()
        try:
            req = eng.submit(GenRequest(prompt_ids=[3, 4, 5],
                                        max_new_tokens=6,
                                        request_id="live-0"))
            got = []
            while len(got) < 6:
                ev = req.stream.get(timeout=60)
                if ev["token_id"] >= 0:
                    got.append(ev["token_id"])
            assert eng._waiter is not None and eng._waiter.is_alive()
        finally:
            eng.stop()
        assert eng._waiter is None
        rows = program_rows(eng)
        assert {e["code"] for e, _, _ in rows.values()} >= {
            PROG_PREFILL, PROG_DECODE}
        for ev, t_enq, t_start in rows.values():
            assert t_enq <= t_start + 1e-9 <= ev["ts"] + 2e-9
        inline = make_engine(params)
        again = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=6)
        drive_inline(inline, [again])
        assert tokens(again) == got     # threads change no stream


class TestChunksAndCommit:
    def test_a_long_prompt_writes_chunk_and_commit_programs(self, params):
        eng = make_engine(params, prefill_buckets=(8,), max_seq_len=64)
        req = GenRequest(prompt_ids=list(range(3, 23)), max_new_tokens=3,
                         request_id="long-0")
        drive_inline(eng, [req])
        assert len(tokens(req)) == 3
        rows = program_rows(eng)
        assert sorted(rows) == list(range(eng.programs.enqueued))
        chunks = [parse_program_aux(e["aux"]) for e, _, _ in rows.values()
                  if e["code"] == PROG_CHUNK]
        assert [c["n"] for c in chunks] == ["8", "8", "4", "0"]
        assert chunks[-1]["shape"] == "commit"
        assert chunks[0]["shape"] == "W8/S24"
        assert len(events(eng, EV_DECODE_JOIN)) == 1


# ---------------------------------------------------------------------------
# on, off, idle, stalled
# ---------------------------------------------------------------------------


def stream_of(params, on):
    eng = make_engine(params, flight_recorder=on)
    reqs = [GenRequest(prompt_ids=[3, 4, 5, 6], max_new_tokens=7),
            GenRequest(prompt_ids=[9, 8], max_new_tokens=5,
                       temperature=0.0)]
    drive_inline(eng, reqs)
    return eng, [tokens(r) for r in reqs]


class TestOnOffIdle:
    def test_streams_byte_identical_with_the_recorder_on_and_off(
            self, params):
        on, toks_on = stream_of(params, True)
        off, toks_off = stream_of(params, False)
        assert toks_on == toks_off and [len(t) for t in toks_on] == [7, 5]
        assert on.programs.enqueued > 0
        # off: no row stamped, no event, no observation
        assert off.programs.enqueued == 0
        assert off.flight.stats()["flight_events"] == 0
        assert off.metrics.snapshot()["hist_program_ms_prefill"]["count"] == 0

    def test_runtime_toggle_stops_the_stamps(self, params):
        eng = make_engine(params)
        eng.flight.set_enabled(False)
        drive_inline(eng, [GenRequest(prompt_ids=[3, 4], max_new_tokens=3)])
        assert eng.programs.enqueued == 0
        eng.flight.set_enabled(True)
        drive_inline(eng, [GenRequest(prompt_ids=[3, 4], max_new_tokens=3)])
        assert eng.programs.enqueued > 0 and events(eng, EV_PROGRAM)

    @pytest.mark.parametrize("key", NEW_HISTS + ("program_stalls",))
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

    def test_event_names_cover_the_new_kinds(self):
        assert flight.EVENT_NAMES[EV_PROGRAM] == "program"
        assert flight.EVENT_NAMES[EV_DECODE_JOIN] == "decode_join"
        assert (EV_PROGRAM, EV_DECODE_JOIN) == (20, 21)
        assert flight.PROGRAM_CLASSES == (
            "decode", "prefill", "chunk", "encoder")


class TestInjectedStall:
    def test_a_late_program_counts_once_and_logs_once(
            self, params, monkeypatch, caplog):
        """The test double is the block fetch: its ninth completion
        comes 0.4 s late, as a device that stopped would make it."""
        real = engine_mod._to_host
        calls = {"n": 0}

        def late(blk):
            calls["n"] += 1
            if calls["n"] == 9:
                import time
                time.sleep(0.4)
            return real(blk)

        eng = make_engine(params, max_batch_size=1)
        # the same request once before: every program compiled (a cold
        # compile inside a dispatch is a long program too, and counts)
        drive_inline(eng, [GenRequest(prompt_ids=[3, 4, 5],
                                      max_new_tokens=30)])
        before = eng.metrics.snapshot()["program_stalls"]
        caplog.clear()
        monkeypatch.setattr(engine_mod, "_to_host", late)
        req = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=30,
                         request_id="stall-0")
        with caplog.at_level(logging.WARNING,
                             logger=engine_mod._LOG.name):
            drive_inline(eng, [req])
        assert len(tokens(req)) == 30
        # every stall is counted once and logged once ...
        stalls = eng.metrics.snapshot()["program_stalls"] - before
        lines = [r.getMessage() for r in caplog.records
                 if "device program stalled" in r.getMessage()]
        assert len(lines) == stalls >= 1
        # ... and the late program is one of them, once. (Held on ITS row
        # alone: while other workers load the host, any other program of
        # these few milliseconds that runs over 8 x its class's median
        # counts too, and rightly.)
        rows = program_rows(eng)
        first = int(events(eng, EV_PREFILL_DISPATCH)[-1]["b"])
        rows = {s: r for s, r in rows.items() if s >= first}
        longest = max(rows.values(), key=lambda r: r[0]["b"])[0]
        assert 400.0 <= longest["b"] < 1500.0
        seq = int(parse_program_aux(longest["aux"])["seq"])
        mine = [line for line in lines if f"seq={seq} " in line]
        assert len(mine) == 1
        assert "class=decode" in mine[0] and "shape=K2" in mine[0]
        assert f"b={longest['b']:.1f} ms" in mine[0]
        # the block behind the late one waited, and ran its own time
        nxt = rows[seq + 1][0]
        assert nxt["b"] < 200.0


def test_the_ledger_needs_no_jax():
    """serving/flight.py imports numpy and the standard library only
    (serving/fleet.py and the encoders import it at start-up)."""
    assert "jax" not in vars(flight)
    assert np.zeros(1, flight.EVENT_DTYPE)["a"][0] == 0.0
