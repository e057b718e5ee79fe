"""BENCH_CHAOS: goodput floor and recovery through replica kill,
rolling upgrade, and autoscale-up on the seeded bursty trace.

The elastic-fleet operational gate (ROADMAP item 5): BENCH_FLEET and
BENCH_QOS measure a static, healthy topology; this scenario replays
the SAME seeded bursty multi-tenant trace (serving/qos.py
bursty_trace) against a 2-replica fleet four ways —

  baseline   no faults (the reference goodput)
  kill       chaos kill of one replica mid-burst (serving/chaos.py):
             gate material — latency-tier goodput must hold >= 0.9x
             baseline with ZERO lost non-mid-stream requests
             (requeue keeps tier/tenant, affinity re-pins)
  upgrade    EngineFleet.rolling_upgrade across both replicas while
             the trace replays: zero failed streams, zero dropped
  scaleup    1 active replica + autoscaler (warm pool of 1): a
             sustained burst must trigger scale-up, restore goodput,
             and leave the scale events on the timeline lane

Runs on the CPU backend as a bench.py child (scripts/bench_fleet.py
precedent): the subject is control-plane behavior under wall-clock
arrival timing, not chip throughput.

Keys (merged into the bench artifact's extras):
  chaos_goodput_baseline / chaos_goodput_kill /
  chaos_kill_goodput_ratio   latency-tier goodput and its floor ratio
  chaos_kill_lost            errored streams with zero tokens (gate: 0)
  chaos_kill_midstream       unavoidable mid-stream casualties
  chaos_kill_requeued        requests moved to the survivor
  chaos_upgrade_failed_streams / chaos_upgrade_errors  (gates: 0)
  chaos_upgrade_replicas_rolled / chaos_upgrade_wall_s
  chaos_scaleup_events       autoscale_ups counted during the burst
  chaos_scaleup_goodput      latency goodput with the scaler active
  chaos_scaleup_active_after admitting replicas once the burst ends
  chaos_timeline_fleet_events  control-plane events on /debug/timeline

Env knobs: BENCH_CHAOS_SEED / _HORIZON_S / _BATCH_REQUESTS /
_LATENCY_RPS / _SLO_TTFT_MS / _KILL_T.

Usage: JAX_PLATFORMS=cpu python scripts/bench_chaos.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def _engine():
    from generativeaiexamples_tpu.config.schema import EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    cfg = llama.LlamaConfig.tiny()
    params = _engine.params
    if params is None:
        params = _engine.params = llama.init_params(cfg,
                                                    jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_batch_size=4, max_seq_len=512, page_size=8,
                        prefill_buckets=(16,), decode_steps_per_dispatch=4,
                        pace_emission_max_streams=0)
    return LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)


_engine.params = None


def _fleet(n=2, **kw):
    from generativeaiexamples_tpu.serving.fleet import (
        EngineFleet, LocalReplica)
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    kw.setdefault("health_interval_s", 0.05)
    kw.setdefault("health_fail_threshold", 2)
    reps = [LocalReplica(f"r{i}", _engine()) for i in range(n)]
    return EngineFleet(reps, ByteTokenizer(), 8, **kw).start()


def _prewarm(fleet) -> None:
    from generativeaiexamples_tpu.serving.engine import GenRequest

    reqs = [GenRequest(prompt_ids=[(i * 5) % 250 + 1 for i in range(120)],
                       max_new_tokens=4, priority="batch",
                       session_id=f"warm{i}") for i in range(2)]
    reqs.append(GenRequest(prompt_ids=[7, 8, 9], max_new_tokens=4,
                           priority="latency"))
    for r in reqs:
        fleet.submit(r)
    for r in reqs:
        while not r.stream.get(timeout=600)["finished"]:
            pass


def _lat_goodput(results, slos):
    from generativeaiexamples_tpu.serving.qos import goodput

    return goodput(results, slos).get("latency", 0.0)


def main() -> int:
    from generativeaiexamples_tpu.serving.chaos import (
        ChaosEvent, classify, run_chaos_trace)
    from generativeaiexamples_tpu.serving.qos import (
        bursty_trace, run_trace_on_engine)

    seed = int(os.environ.get("BENCH_CHAOS_SEED", "13"))
    horizon = float(os.environ.get("BENCH_CHAOS_HORIZON_S", "4"))
    batch_n = int(os.environ.get("BENCH_CHAOS_BATCH_REQUESTS", "8"))
    rps = float(os.environ.get("BENCH_CHAOS_LATENCY_RPS", "2.5"))
    slo_ttft_ms = float(os.environ.get("BENCH_CHAOS_SLO_TTFT_MS", "3000"))
    kill_t = float(os.environ.get("BENCH_CHAOS_KILL_T", "1.2"))

    trace = bursty_trace(seed=seed, horizon_s=horizon, latency_rps=rps,
                         batch_requests=batch_n)
    slos = {"latency": {"ttft_s": slo_ttft_ms / 1e3, "gap_p95_s": 3.0},
            "batch": {"wall_s": 120.0}, "standard": {"ttft_s": 10.0}}

    # -- throwaway warm replay (module-level jitted steps: the first
    # run pays every compile; all MEASURED runs start equally warm).
    fleet = _fleet()
    _prewarm(fleet)
    run_trace_on_engine(fleet, trace, seed=1, timeout_s=120.0)
    fleet.stop()

    # -- baseline: no faults ---------------------------------------------
    fleet = _fleet()
    _prewarm(fleet)
    base_res = run_trace_on_engine(fleet, trace, seed=1, timeout_s=120.0)
    fleet.stop()
    base_good = _lat_goodput(base_res, slos)

    # -- kill mid-burst ----------------------------------------------------
    fleet = _fleet()
    _prewarm(fleet)
    kill_res, _ = run_chaos_trace(
        fleet, trace, [ChaosEvent(t=kill_t, kind="kill")], seed=seed,
        timeout_s=120.0)
    kill_snap = fleet.metrics.snapshot()
    fleet.stop()
    kill_good = _lat_goodput(kill_res, slos)
    kill_buckets = classify(kill_res)

    # -- rolling upgrade while the trace replays ---------------------------
    fleet = _fleet()
    _prewarm(fleet)
    roll_summary = {}

    def roll():
        time.sleep(0.6)
        roll_summary.update(fleet.rolling_upgrade(
            lambda old: _engine(), drain_timeout_s=60.0))

    roll_thread = threading.Thread(target=roll, daemon=True)
    roll_thread.start()
    up_res = run_trace_on_engine(fleet, trace, seed=1, timeout_s=120.0)
    roll_thread.join(timeout=180.0)
    up_snap = fleet.metrics.snapshot()
    fleet.stop()
    up_buckets = classify(up_res)
    up_good = _lat_goodput(up_res, slos)

    # -- autoscale-up under a sustained burst ------------------------------
    from generativeaiexamples_tpu.serving.autoscaler import FleetAutoscaler

    # A heavier sustained burst than the kill/upgrade trace: the
    # point is a load 1 replica cannot clear inside the hysteresis
    # window, so the scaler MUST act to restore goodput.
    scale_trace = bursty_trace(seed=seed, horizon_s=horizon,
                               latency_rps=rps, batch_requests=16,
                               batch_out=(1.6, 48, 96))
    fleet = _fleet(n=1)
    FleetAutoscaler(fleet, engine_factory=_engine, min_replicas=1,
                    max_replicas=3, warm_pool=1, interval_s=0.1,
                    up_depth=3.0, down_depth=0.5, up_ticks=2,
                    down_ticks=50, cooldown_s=0.5)
    fleet.autoscaler.start()
    _prewarm(fleet)
    scale_res = run_trace_on_engine(fleet, scale_trace, seed=1,
                                    timeout_s=120.0)
    scale_snap = fleet.metrics.snapshot()
    scale_events = len(fleet.extra_flight_lanes["autoscaler"]
                       .snapshot_events())
    active_after = sum(1 for r in fleet.replicas if r.state == "active")
    fleet.stop()
    scale_good = _lat_goodput(scale_res, slos)

    out = {
        "chaos_trace_requests": len(trace),
        "chaos_goodput_baseline": round(base_good, 3),
        "chaos_goodput_kill": round(kill_good, 3),
        "chaos_kill_goodput_ratio": round(kill_good / base_good, 3)
        if base_good else None,
        "chaos_kill_lost": kill_buckets["lost"],
        "chaos_kill_midstream": kill_buckets["midstream"],
        "chaos_kill_requeued": kill_snap["router_requeued"],
        "chaos_upgrade_failed_streams":
            roll_summary.get("failed_streams"),
        "chaos_upgrade_errors": up_buckets["lost"] + up_buckets["midstream"],
        "chaos_upgrade_replicas_rolled":
            roll_summary.get("replicas_rolled"),
        "chaos_upgrade_wall_s": roll_summary.get("wall_s"),
        "chaos_upgrade_goodput": round(up_good, 3),
        "chaos_upgrade_rolls": up_snap["upgrade_rolls"],
        "chaos_scaleup_events": scale_snap["autoscale_ups"],
        "chaos_scaleup_goodput": round(scale_good, 3),
        "chaos_scaleup_active_after": active_after,
        "chaos_timeline_fleet_events": scale_events,
        "chaos_slo_ttft_ms": slo_ttft_ms,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
