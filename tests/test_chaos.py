"""Chaos harness + elastic-fleet robustness: seeded injectors
(kill / blackhole / slow / submit_error), the K-consecutive probe
rule under a blackhole, rolling upgrades under live traffic including
a DETERMINISTIC upgrade-vs-submit race, restore-vs-evict concurrency,
and the end-to-end kill-mid-trace gate (zero lost non-mid-stream
requests).
"""

import threading
import time

import jax
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving.chaos import (
    ChaosEvent, ChaosMonkey, ChaosSubmitError, classify, run_chaos_trace)
from generativeaiexamples_tpu.serving.engine import GenRequest, LLMEngine
from generativeaiexamples_tpu.serving.fleet import EngineFleet, LocalReplica
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

TINY = llama.LlamaConfig.tiny()
PS = 8


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def make_engine(params, **over):
    cfg = dict(max_batch_size=2, max_seq_len=256, page_size=PS,
               prefill_buckets=(16, 32), prefix_cache=True,
               pace_emission_max_streams=0)
    cfg.update(over)
    return LLMEngine(params, TINY, ByteTokenizer(), EngineConfig(**cfg),
                     use_pallas=False)


def make_fleet(params, n=2, **fleet_kw):
    fleet_kw.setdefault("health_fail_threshold", 1)
    engines = [make_engine(params) for _ in range(n)]
    reps = [LocalReplica(f"r{i}", e) for i, e in enumerate(engines)]
    fleet = EngineFleet(reps, ByteTokenizer(), PS, **fleet_kw).start()
    return fleet, engines


def collect(req, timeout=120):
    toks = []
    while True:
        ev = req.stream.get(timeout=timeout)
        if ev["token_id"] >= 0:
            toks.append(ev["token_id"])
        if ev["finished"]:
            return toks, ev["finish_reason"]


class FakeReplica:
    def __init__(self, rid):
        self.rid = rid
        self.state = "active"
        self.has_prefix_cache = False
        self.submitted = []
        self.alive = True

    def set_reporter(self, fn):
        pass

    def submit(self, req):
        self.submitted.append(req)

    def healthy(self):
        return self.alive

    def start(self):
        pass

    def stop(self):
        pass

    def warmup(self, **kw):
        pass

    def metrics_snapshot(self):
        return {}


# ---------------------------------------------------------------------------
# injector units (fakes, no engines)
# ---------------------------------------------------------------------------

class TestInjectors:
    def _fleet(self, threshold=2):
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        fleet = EngineFleet(fakes, ByteTokenizer(), PS,
                            health_fail_threshold=threshold).start()
        return fleet, fakes

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            ChaosEvent(t=0.0, kind="meteor")

    def test_blackhole_shorter_than_k_probes_does_not_evict(self):
        """The K-consecutive rule's whole point: a transient probe
        blackhole (or one slow poll) must not kill a serving
        replica."""
        fleet, fakes = self._fleet(threshold=2)
        monkey = ChaosMonkey(fleet, seed=0)
        monkey.inject(ChaosEvent(t=0.0, kind="blackhole", rid="r0",
                                 duration_s=5.0))
        assert fleet.check_health()["r0"] is False  # 1/2: no eviction
        assert fakes[0].state == "active"
        monkey.undo_all()  # probe path heals before the 2nd failure
        assert fleet.check_health()["r0"] is True
        assert fleet.fleet_health()["replicas"]["r0"]["probe_fails"] == 0
        # A blackhole that OUTLIVES K probes evicts.
        monkey.inject(ChaosEvent(t=0.0, kind="blackhole", rid="r0",
                                 duration_s=5.0))
        fleet.check_health()
        fleet.check_health()
        assert fakes[0].state == "evicted"
        snap = fleet.metrics.snapshot()
        assert snap["chaos_injected_blackholes"] == 2
        assert snap["replica_evictions"] == 1
        monkey.undo_all()

    def test_submit_error_unwinds_tracking(self):
        """An injected submit fault surfaces to the caller and leaves
        NO record or router accounting behind — the leak would count
        phantom load against the replica forever."""
        fleet, fakes = self._fleet()
        monkey = ChaosMonkey(fleet, seed=0)
        monkey.inject(ChaosEvent(t=0.0, kind="submit_error", rid="r0",
                                 duration_s=5.0))
        monkey.inject(ChaosEvent(t=0.0, kind="submit_error", rid="r1",
                                 duration_s=5.0))
        req = GenRequest(prompt_ids=[3] * 16, max_new_tokens=4)
        with pytest.raises(ChaosSubmitError):
            fleet.submit(req)
        assert sum(len(d) for d in fleet._records.values()) == 0
        assert all(v == 0 for v in fleet.router.queue_depths().values())
        assert fleet.metrics.snapshot()["chaos_injected_submit_errors"] == 2
        monkey.undo_all()  # restored: submits work again
        req2 = GenRequest(prompt_ids=[3] * 16, max_new_tokens=4)
        fleet.submit(req2)
        assert any(req2 in f.submitted for f in fakes)

    def test_seeded_random_pick_is_deterministic(self):
        picks = []
        for _ in range(2):
            # The random pick targets local replicas; dummy engines
            # suffice (the pick never touches them).
            reps = [LocalReplica(f"r{i}", object()) for i in range(3)]
            fleet = EngineFleet(reps, ByteTokenizer(), PS)
            monkey = ChaosMonkey(fleet, seed=42)
            picks.append([monkey._pick("").rid for _ in range(5)])
        assert picks[0] == picks[1]

    def test_slow_injector_sets_and_restores_beat_delay(self, params):
        fleet, engines = make_fleet(params, n=1)
        try:
            monkey = ChaosMonkey(fleet, seed=0)
            th = monkey.run_schedule(
                [ChaosEvent(t=0.0, kind="slow", rid="r0",
                            duration_s=0.15, magnitude=0.02)])
            deadline = time.monotonic() + 5
            while engines[0].chaos_beat_delay_s == 0.0:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            th.join(timeout=10)
            monkey.wait(timeout_s=10)
            assert engines[0].chaos_beat_delay_s == 0.0  # undone
            snap = fleet.metrics.snapshot()
            assert snap["chaos_injected_slow_beats"] == 1
            # ... and the engine still serves afterwards.
            req = GenRequest(prompt_ids=[5] * 16, max_new_tokens=4)
            fleet.submit(req)
            toks, reason = collect(req)
            assert toks and reason != "error"
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# rolling upgrade (real engines)
# ---------------------------------------------------------------------------

class TestRollingUpgrade:
    def test_upgrade_under_live_traffic_zero_failed_streams(self, params):
        """The tentpole invariant: a full roll across 2 replicas while
        streams are in flight finishes every stream, swaps every
        engine object, and counts the roll."""
        fleet, engines = make_fleet(params)
        try:
            reqs = [GenRequest(prompt_ids=[7 + i] * 24, max_new_tokens=24,
                               session_id=f"s{i}") for i in range(4)]
            for r in reqs:
                fleet.submit(r)
            swapped = []

            def factory(old):
                swapped.append(old)
                return make_engine(params)

            summary = fleet.rolling_upgrade(factory, drain_timeout_s=120.0)
            assert summary["failed_streams"] == 0
            assert summary["replicas_rolled"] == 2
            assert swapped == engines  # both OLD engines retired
            for r in reqs:
                toks, reason = collect(r, timeout=60)
                assert toks and reason != "error"
            snap = fleet.metrics.snapshot()
            assert snap["upgrade_rolls"] == 1
            assert snap["upgrade_replicas_rolled"] == 2
            # Upgrade events on the fleet control lane.
            evs = fleet.control_flight.snapshot_events()
            assert len(evs) == 2
            # The fleet serves on the NEW engines afterwards.
            req = GenRequest(prompt_ids=[9] * 16, max_new_tokens=8,
                             session_id="s0")
            fleet.submit(req)
            toks, reason = collect(req)
            assert toks and reason != "error"
            assert all(r.engine not in engines for r in fleet.replicas)
        finally:
            fleet.stop()

    def test_upgrade_requeues_unadmitted_and_repins_affinity(self, params):
        """A replica whose queue holds un-admitted requests at swap
        time re-places them on survivors: tier/tenant ride the
        request, and the session re-pins to wherever it lands."""
        fleet, engines = make_fleet(params, n=2)
        try:
            # Stop r0's scheduler so its queue can only accumulate.
            engines[0].stop()
            # Pin a session onto r0 while it still admits.
            req = GenRequest(prompt_ids=[4] * 24, max_new_tokens=6,
                             priority="latency", tenant_id="acme",
                             session_id="sess-a")
            # Force placement onto r0 (drain r1 -> only r0 admits).
            fleet.router.set_admitting("r1", False)
            fleet.submit(req)
            fleet.router.set_admitting("r1", True)
            assert len(engines[0].waiting) == 1

            def factory(old):
                return make_engine(params)

            summary = fleet.rolling_upgrade(factory, drain_timeout_s=0.3)
            assert summary["failed_streams"] == 0
            assert summary["requeued"] >= 1
            toks, reason = collect(req, timeout=60)
            assert toks and reason != "error"
            assert req.priority == "latency" and req.tenant_id == "acme"
            # (The affinity entry itself is gone by now — rolling the
            # replica the request landed on legitimately drops its
            # pins; the eviction-path re-pin is asserted in
            # test_fleet.TestRequeueFidelity.)
        finally:
            fleet.stop()

    def test_deterministic_upgrade_vs_submit_race_rescues_request(
            self, params):
        """THE race: a submit parked inside the old engine's submit()
        while the roll swaps engines would strand the request on the
        discarded engine's frozen queue. The engine-identity handshake
        in fleet.submit must detect the swap and requeue."""
        fleet, engines = make_fleet(params)
        try:
            entered, hold = threading.Event(), threading.Event()
            old_submit = engines[0].submit

            def slow_submit(req):
                entered.set()
                assert hold.wait(30)
                return old_submit(req)

            engines[0].submit = slow_submit
            fleet.router.set_admitting("r1", False)  # force r0
            req = GenRequest(prompt_ids=[6] * 24, max_new_tokens=6,
                             priority="latency", tenant_id="t9")
            t = threading.Thread(target=fleet.submit, args=(req,),
                                 daemon=True)
            t.start()
            assert entered.wait(30)  # parked mid-submit on r0
            fleet.router.set_admitting("r1", True)

            def factory(old):
                return make_engine(params)

            # Short drain: the parked record can't drain; the roll
            # sweeps submitted records, leaves ours (still unmarked),
            # swaps, and our submit detects the swap on release.
            summary = fleet.rolling_upgrade(factory, drain_timeout_s=0.2)
            hold.set()
            t.join(timeout=30)
            assert not t.is_alive()
            toks, reason = collect(req, timeout=60)
            assert toks and reason != "error"
            assert summary["replicas_rolled"] == 2
            # Nothing stranded anywhere.
            assert sum(len(d) for d in fleet._records.values()) == 0
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# restore-vs-evict concurrency
# ---------------------------------------------------------------------------

class TestRestoreEvictRace:
    def test_concurrent_restore_and_evict_stay_consistent(self):
        """Hammer evict/restore from two threads: whatever the
        interleaving, the replica ends in a legal state, no exception
        escapes, and the fleet still serves."""
        fakes = [FakeReplica("r0"), FakeReplica("r1")]
        fleet = EngineFleet(fakes, ByteTokenizer(), PS,
                            health_fail_threshold=1).start()
        errs = []
        barrier = threading.Barrier(2)

        def run(fn):
            try:
                barrier.wait(timeout=10)
                for _ in range(25):
                    fn("r0")
            except Exception as e:  # pragma: no cover - the assertion
                errs.append(e)

        threads = [threading.Thread(target=run, args=(fleet.evict,)),
                   threading.Thread(target=run, args=(fleet.restore,))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs
        assert fakes[0].state in ("active", "evicted")
        fleet.restore("r0")
        req = GenRequest(prompt_ids=[2] * 16, max_new_tokens=4)
        fleet.submit(req)
        assert any(req in f.submitted for f in fakes)
        assert sum(len(d) for d in fleet._records.values()) == 1


# ---------------------------------------------------------------------------
# end-to-end kill mid-trace (real engines)
# ---------------------------------------------------------------------------

class TestKillMidTrace:
    def test_kill_mid_trace_loses_nothing_not_midstream(self, params):
        from generativeaiexamples_tpu.serving.qos import bursty_trace

        fleet, engines = make_fleet(params, health_interval_s=0.05,
                                    health_fail_threshold=2)
        try:
            trace = bursty_trace(seed=5, horizon_s=1.5, latency_rps=2.0,
                                 batch_requests=4,
                                 batch_prompt=(1.4, 24, 64),
                                 batch_out=(1.6, 8, 24))
            results, monkey = run_chaos_trace(
                fleet, trace, [ChaosEvent(t=0.5, kind="kill")], seed=7,
                timeout_s=120.0)
            buckets = classify(results)
            assert buckets["lost"] == 0
            assert buckets["completed"] >= 1
            snap = fleet.metrics.snapshot()
            assert snap["chaos_injected_kills"] == 1
            assert snap["replica_evictions"] == 1
            # The kill landed on the chaos flight lane.
            evs = fleet.extra_flight_lanes["chaos"].snapshot_events()
            assert any(e["aux"].startswith("kill:") for e in evs)
        finally:
            fleet.stop()


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
