"""Engine flight recorder (serving/flight.py): ring-buffer semantics
(wrap, single-writer torn-row tolerance), exponential histograms
(observe/quantile/merge/always-present shape), Chrome-trace schema +
span nesting, the Prometheus text exposition, the analyzer's 100%
attribution invariant, the engine integration (beats/events recorded,
off = zeros but keys present), and the obs/tracing satellite (one bad
span attribute no longer drops the rest; failures are counted)."""

import json
import os
import queue
import sys
import threading

import jax
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving import flight
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.flight import (
    EV_ADMIT, EV_FIRST_TOKEN, EV_KV_PROMOTE, EV_RETIRE, EV_SUBMIT,
    ExpHistogram, FlightRecorder, chrome_trace, hist_quantile,
    merge_hist_snapshots, prometheus_text, zero_hist_snapshot)
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()

# scripts/ is not a package on the import path under every pytest
# invocation; the analyzer tests import it explicitly.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


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
    """Deterministic single-thread scheduler drive (the smoke_* idiom),
    through the same _land_next_block the live loop uses so beats are
    recorded."""
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


def drain(req):
    out = []
    while True:
        try:
            ev = req.stream.get_nowait()
        except queue.Empty:
            return out
        if ev["token_id"] >= 0:
            out.append(ev["token_id"])


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

class TestExpHistogram:
    def test_observe_count_sum_and_buckets(self):
        h = ExpHistogram()
        for v in (0.5, 1.0, 2.0, 100.0):
            h.observe(v)
        s = h.snapshot()
        assert s["count"] == 4
        assert s["sum"] == pytest.approx(103.5)
        assert sum(s["buckets"].values()) == 4
        assert s["overflow"] == 0

    def test_quantile_interpolation_brackets_the_value(self):
        h = ExpHistogram()
        for _ in range(100):
            h.observe(10.0)
        s = h.snapshot()
        # sqrt(2)-bucket scheme: the estimate lands within one bucket
        # (relative error <= sqrt(2)) of the true value.
        assert s["p50"] is not None
        assert 10.0 / 1.5 <= s["p50"] <= 10.0 * 1.5
        assert s["p95"] == pytest.approx(s["p50"], rel=0.5)

    def test_empty_histogram_shape_and_none_quantiles(self):
        s = ExpHistogram().snapshot()
        assert s == zero_hist_snapshot()
        assert s["p50"] is None and s["count"] == 0
        assert hist_quantile(s, 0.5) is None

    def test_merge_sums_counts_and_requantiles(self):
        a, b = ExpHistogram(), ExpHistogram()
        for _ in range(10):
            a.observe(1.0)
        for _ in range(10):
            b.observe(1000.0)
        # JSON round trip: the merge must work on scraped dicts too.
        sa = json.loads(json.dumps(a.snapshot()))
        merged = merge_hist_snapshots([sa, b.snapshot(), None])
        assert merged["count"] == 20
        assert merged["sum"] == pytest.approx(10010.0)
        assert 0.5 <= merged["p50"] <= 1000.0
        assert merged["p95"] > 500  # upper mode dominates the tail

    def test_overflow_bucket(self):
        h = ExpHistogram(bounds=(1.0, 2.0))
        h.observe(99.0)
        s = h.snapshot()
        assert s["overflow"] == 1 and s["count"] == 1


# ---------------------------------------------------------------------------
# ring buffers
# ---------------------------------------------------------------------------

def _beat_kwargs(i: float):
    return dict(t_dispatch=i, t_ready=i + 0.5, t_prev_ready=i - 0.5,
                decode_k=2, spec_k=0, tree_branches=0, rider_width=0,
                spec_state=False, fused_rider=False,
                qos_paused=False, busy=(0, 1, 0), wait=(0, 0, 0),
                tokens_emitted=3, kv_demote_pages=0, kv_promote_pages=0)


class TestRing:
    def test_wrap_keeps_last_ring_size_records_in_order(self):
        rec = FlightRecorder(ring_size=64)
        for i in range(3 * 64 + 7):
            rec.record_beat(**_beat_kwargs(float(i)))
        beats = rec.snapshot_beats()
        assert len(beats) == 64
        seqs = beats["seq"].tolist()
        assert seqs == list(range(3 * 64 + 7 - 64, 3 * 64 + 7))
        assert rec.stats()["flight_beats"] == 3 * 64 + 7

    def test_event_ring_wrap_and_rid_slots(self):
        rec = FlightRecorder(ring_size=64)  # event ring = 256
        for i in range(300):
            rec.record_event(EV_SUBMIT, float(i), rid=f"r{i}")
        evs = rec.snapshot_events()
        assert len(evs) == 256
        assert evs[0]["rid"] == "r44" and evs[-1]["rid"] == "r299"
        assert evs[-1]["seq"] == 299

    def test_disabled_recorder_records_nothing_but_stats_present(self):
        rec = FlightRecorder(ring_size=64, enabled=False)
        rec.record_beat(**_beat_kwargs(1.0))
        rec.record_event(EV_SUBMIT, 1.0, rid="x")
        assert len(rec.snapshot_beats()) == 0
        assert rec.snapshot_events() == []
        assert rec.stats() == {"flight_beats": 0, "flight_events": 0,
                               "flight_enabled": 0}

    def test_runtime_toggle(self):
        rec = FlightRecorder(ring_size=64, enabled=False)
        rec.set_enabled(True)
        rec.record_beat(**_beat_kwargs(1.0))
        assert rec.stats()["flight_beats"] == 1
        rec.set_enabled(False)
        rec.record_beat(**_beat_kwargs(2.0))
        assert rec.stats()["flight_beats"] == 1

    def test_single_writer_reader_race_yields_only_valid_rows(self):
        """A reader snapshotting DURING live writes must never see a
        torn row: every returned row's seq is in the live window and
        strictly increasing; the reader never crashes."""
        rec = FlightRecorder(ring_size=64)
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            while not stop.is_set():
                rec.record_beat(**_beat_kwargs(float(i)))
                rec.record_event(EV_SUBMIT, float(i), rid=f"r{i}")
                i += 1

        def reader():
            try:
                for _ in range(300):
                    beats = rec.snapshot_beats()
                    seqs = beats["seq"].tolist()
                    assert seqs == sorted(seqs)
                    assert len(set(seqs)) == len(seqs)
                    # Field coherence: t_ready was written with
                    # t_dispatch + 0.5 in the same record; a torn row
                    # would break the pairing.
                    assert np.allclose(beats["t_ready"],
                                       beats["t_dispatch"] + 0.5)
                    # A surviving event's rid must belong to ITS seq —
                    # snapshot_events drops rows the writer lapped
                    # between the array copy and the string reads.
                    for ev in rec.snapshot_events():
                        assert ev["rid"] == f"r{ev['seq']}"
            except Exception as e:  # surfaced on the main thread
                errors.append(e)

        w = threading.Thread(target=writer)
        rs = [threading.Thread(target=reader) for _ in range(2)]
        w.start()
        for r in rs:
            r.start()
        for r in rs:
            r.join()
        stop.set()
        w.join()
        assert not errors, errors


# ---------------------------------------------------------------------------
# chrome trace + analyzer + prometheus
# ---------------------------------------------------------------------------



def _synthetic_recorder():
    rec = FlightRecorder(ring_size=64)
    t = 100.0
    rec.record_event(EV_SUBMIT, t, rid="req-1", tier=1, a=16.0)
    rec.record_event(EV_ADMIT, t + 0.01, rid="req-1", tier=1, slot=0,
                     a=10.0)
    for i in range(4):
        lo = t + 0.02 + i * 0.1
        rec.record_beat(t_dispatch=lo, t_ready=lo + 0.08,
                        t_prev_ready=lo - 0.02 if i else 0.0,
                        decode_k=2, spec_k=0, tree_branches=0,
                        rider_width=0, spec_state=False,
                        fused_rider=False, qos_paused=False,
                        busy=(0, 1, 0), wait=(0, 0, 0), tokens_emitted=2,
                        kv_demote_pages=0, kv_promote_pages=0)
    rec.record_event(EV_FIRST_TOKEN, t + 0.1, rid="req-1", tier=1,
                     a=90.0)
    # A gap cause inside the 3rd inter-beat gap.
    rec.record_event(EV_KV_PROMOTE, t + 0.31, a=4.0, b=2.0)
    rec.record_event(EV_RETIRE, t + 0.42, rid="req-1", tier=1, code=0,
                     a=8.0, b=320.0, aux="deadbeef" * 4)
    return rec


class TestChromeTrace:
    def test_schema_round_trips_and_nests(self):
        trace = json.loads(json.dumps(chrome_trace(
            {"r0": _synthetic_recorder()})))
        assert trace["displayTimeUnit"] == "ms"
        evs = trace["traceEvents"]
        assert all({"ph", "pid", "tid", "name"} <= set(e) for e in evs)
        xs = [e for e in evs if e["ph"] == "X"]
        assert all("ts" in e and "dur" in e and e["dur"] >= 0
                   for e in xs)
        assert flight.spans_nest(trace)
        names = {e["name"] for e in evs}
        assert "queue_wait" in names and "ttft" in names
        assert any(n.startswith("req req-1") for n in names)
        assert "kv_promote" in names  # gap-cause instant
        # rid <-> trace-id correlation rides the request span.
        req_span = next(e for e in evs
                        if e["name"].startswith("req req-1"))
        assert req_span["args"]["trace_id"] == "deadbeef" * 4
        assert req_span["args"]["finish_reason"] == "stop"

    def test_two_recorders_get_two_lanes(self):
        trace = chrome_trace({"r0": _synthetic_recorder(),
                              "r1": _synthetic_recorder()})
        pids = {e["pid"] for e in trace["traceEvents"]}
        assert pids == {0, 1}

    def test_plan_labels(self):
        assert flight.plan_label(8, 0, 0, 0, False) == "decode K=8"
        assert flight.plan_label(2, 3, 4, 512, False) == \
            "decode K=2 spec k=3 tree=4 rider W=512"
        assert flight.plan_label(0, 0, 0, 256, False) == "chunk W=256"
        assert "spec-fallback" in flight.plan_label(2, 0, 0, 0, True)


class TestAnalyzer:
    def test_attribution_sums_to_100_and_names_causes(self):
        from scripts.analyze_timeline import analyze

        trace = chrome_trace({"r0": _synthetic_recorder()})
        rep = analyze(trace, host_gap_ms=25.0)
        assert rep["overall"]["attributed_pct"] == pytest.approx(
            100.0, abs=0.5)
        cats = rep["overall"]["categories"]
        assert cats["device_busy"]["ms"] > 0
        # The kv_promote instant inside a gap names it pager_gather.
        assert "pager_gather" in cats
        assert "pager_gather" in rep["overall"]["top_causes"]

    def test_empty_trace(self):
        from scripts.analyze_timeline import analyze

        rep = analyze({"traceEvents": []})
        assert rep["overall"]["wall_ms"] == 0.0


class TestPrometheus:
    def test_scalars_maps_and_histograms(self):
        h = ExpHistogram()
        for v in (1.0, 5.0, 5.0):
            h.observe(v)
        snap = {"tokens_generated": 42, "tokens_per_sec": 1.5,
                "qos_queue_depth": {"latency": 1, "batch": 0},
                "hist_ttft_ms": h.snapshot(),
                "per_replica": {"r0": {"nested": {}}},
                "none_key": None}
        txt = prometheus_text(snap)
        assert "# TYPE gaie_tokens_generated gauge" in txt
        assert "gaie_tokens_generated 42" in txt
        assert 'gaie_qos_queue_depth{key="latency"} 1' in txt
        assert "# TYPE gaie_ttft_ms histogram" in txt
        assert 'gaie_ttft_ms_bucket{le="+Inf"} 3' in txt
        assert "gaie_ttft_ms_count 3" in txt
        assert "per_replica" not in txt and "none_key" not in txt
        # Cumulative buckets are monotone non-decreasing.
        cums = [int(line.rsplit(" ", 1)[1]) for line in txt.splitlines()
                if line.startswith("gaie_ttft_ms_bucket")]
        assert cums == sorted(cums)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

class TestEngineIntegration:
    def test_beats_events_and_histograms_recorded(self, params):
        eng = make_engine(params)
        reqs = [GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=8,
                           request_id="it-0")]
        drive_inline(eng, reqs)
        assert drain(reqs[0]) and len(drain(reqs[0])) == 0
        snap = eng.metrics.snapshot()
        assert snap["flight_enabled"] == 1
        assert snap["flight_beats"] > 0
        assert snap["flight_events"] >= 4  # submit/admit/first/retire
        beats = eng.flight.snapshot_beats()
        assert len(beats) == snap["flight_beats"]
        assert (beats["t_ready"] >= beats["t_dispatch"]).all()
        assert beats["decode_k"].max() >= 1
        kinds = {e["kind"] for e in eng.flight.snapshot_events()}
        assert {EV_SUBMIT, EV_ADMIT, EV_FIRST_TOKEN, EV_RETIRE} <= kinds
        ev = next(e for e in eng.flight.snapshot_events()
                  if e["kind"] == EV_RETIRE)
        assert ev["rid"] == "it-0" and ev["a"] == 8.0
        assert snap["hist_ttft_ms"]["count"] == 1
        assert snap["hist_e2e_ms"]["count"] == 1
        assert snap["hist_queue_wait_ms_standard"]["count"] == 1
        assert snap["ttft_p50_ms"] is not None

    def test_recorder_off_zeros_but_keys_present(self, params):
        eng = make_engine(params, flight_recorder=False)
        req = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=4)
        drive_inline(eng, [req])
        snap = eng.metrics.snapshot()
        for key in flight.FLIGHT_KEYS:
            assert key in snap
        assert snap["flight_beats"] == 0
        assert snap["flight_enabled"] == 0
        assert len(eng.flight.snapshot_beats()) == 0
        # Histograms stay live (they are metrics, not the ring).
        for key in flight.HIST_KEYS:
            assert key in snap and "count" in snap[key]
        assert snap["hist_ttft_ms"]["count"] == 1
        assert snap["trace_export_errors"] >= 0

    def test_queue_wait_tier_tagging(self, params):
        eng = make_engine(params)
        req = GenRequest(prompt_ids=[3, 4, 5], max_new_tokens=4,
                         priority="batch", request_id="b-0")
        drive_inline(eng, [req])
        snap = eng.metrics.snapshot()
        assert snap["hist_queue_wait_ms_batch"]["count"] == 1
        assert snap["hist_queue_wait_ms_latency"]["count"] == 0
        sub = next(e for e in eng.flight.snapshot_events()
                   if e["kind"] == EV_SUBMIT)
        from generativeaiexamples_tpu.serving.qos import tier_id
        assert sub["tier"] == tier_id("batch")


# ---------------------------------------------------------------------------
# obs/tracing satellites
# ---------------------------------------------------------------------------

class TestTracingSatellite:
    def test_manual_span_end_failure_is_counted(self):
        """A span whose end() raises is counted, never propagated to the
        scheduler thread that retires the slot, and ended only once."""
        from generativeaiexamples_tpu.obs import tracing

        before = tracing.trace_export_errors()

        class _FlakySpan:
            ends = 0

            def set_attribute(self, k, v):
                raise AssertionError("end() decorates nothing any more")

            def end(self):
                self.ends += 1
                raise RuntimeError("exporter hiccup")

        ms = tracing.ManualSpan.__new__(tracing.ManualSpan)
        ms._span = sp = _FlakySpan()
        ms.end()
        ms.end()
        assert sp.ends == 1 and ms._span is None
        assert ms.context() is None
        assert tracing.trace_export_errors() == before + 1

    def test_mini_exporter_failure_is_counted(self):
        from generativeaiexamples_tpu.obs import tracing

        before = tracing.trace_export_errors()

        class _BadExporter:
            def export(self, spans):
                raise IOError("collector down")

        sp = tracing._MiniSpan("t", tracing._MiniContext(1, 2), None,
                               [_BadExporter()])
        sp.end()
        assert tracing.trace_export_errors() == before + 1

    def test_span_trace_id(self):
        from generativeaiexamples_tpu.obs import tracing

        ms = tracing.ManualSpan.__new__(tracing.ManualSpan)
        ms._span = tracing._MiniSpan(
            "t", tracing._MiniContext(0xabc123, 2), None, [])
        assert tracing.span_trace_id(ms) == f"{0xabc123:032x}"
        ms._span = None
        assert tracing.span_trace_id(ms) == ""
