"""Stall attribution over a flight-recorder timeline: split wall time
into device-busy / host-gap / idle and NAME the top gap causes.

Input is the Chrome trace-event JSON served at `/debug/timeline` (or
dumped by bench/smoke under build/). Attribution per replica lane:

- **device_busy** — the union of PROGRAM slices on the device lane: a
  beat's slice (a decode block) and the slice of every prefill group,
  chunk / commit and encoder forward, each from its inferred device
  start (the later of its enqueue and the completion of the program
  enqueued before it: one queue runs in order) to its completion, as
  the engine's program ledger stamps them (`serving/flight.py`). A
  beat's slice is therefore the block's own device time: the prefill
  that ran before it is not charged to it. `device_busy_by_class`
  splits the total into decode / prefill / chunk / encoder. What the
  slices cannot show: time the device spent on a program of ANOTHER
  process, and a completion stamped up to a thread switch late.
- the part of a gap that a HOST PAUSE covers is charged to it first,
  by the pause's own length and not the whole gap's (the `host-pause`
  slices of the scheduler lane, `flight.py::_host_pause_events`): **gc**
  (a collection of the interpreter's), **late_wake** (a timed wait of
  the scheduler's that came back late: another thread kept the
  interpreter's lock, or the OS the core), **dispatch_call** (a
  program's dispatch call of 1 ms or more), in that order where they
  overlap.
- what is left of a gap is charged to the FIRST known cause
  whose marker falls inside the gap (priority order): **qos_pause**
  (a latency-tier TTFT phase paused lower-tier prefills),
  **pager_gather** (KV pager promote — the host-side tier read),
  **admission_retry** (page exhaustion requeues), **prefill_chunk**
  (interleaved-lane chunk staging/dispatch), **kv_demote** (reclaim
  demotion flushes).
- a gap whose leading edge is a beat whose plan label was never seen
  before is **cold_plan** (a lattice point compiling mid-traffic).
- uncaused gaps <= --host-gap-ms (default 50) are **host_gap**
  (scheduler bookkeeping between blocks); longer ones are **idle**
  (no work offered).

Categories partition [first event, last event] exactly, so the
attribution always sums to 100% of wall — "unattributed" time cannot
exist, only honestly-named idle. Turning the next headline regression
into one command is the point: run it on a flight-recorder dump and
read which category grew.

Usage:
    python scripts/analyze_timeline.py build/timeline.json [--json]
        [--lane N] [--host-gap-ms 50]
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

# Gap-cause instant names (flight.EVENT_NAMES) -> category, in priority
# order: a gap containing several markers is charged to the first.
CAUSE_PRIORITY = (
    ("qos_pause", "qos_pause"),
    ("kv_promote", "pager_gather"),
    ("kv_transfer", "disagg"),
    ("admission_retry", "admission_retry"),
    ("prefill_chunk", "prefill_chunk"),
    ("kv_demote", "kv_demote"),
)

# The host's pauses (the names of the `host-pause` slices), in priority
# order: the part of a gap one covers is charged to it before anything
# else, and where two overlap the first takes the overlap.
HOST_PAUSES = ("gc", "late_wake", "dispatch_call")

CATEGORIES = ("device_busy",) + HOST_PAUSES + (
    "cold_plan", "qos_pause", "pager_gather",
    "disagg", "admission_retry", "prefill_chunk", "kv_demote",
    "host_gap", "idle")


def _merge_intervals(iv: List[Tuple[float, float]]
                     ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(iv):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _subtract(pieces: List[Tuple[float, float]], lo: float, hi: float
              ) -> Tuple[List[Tuple[float, float]], float]:
    """`pieces` without [lo, hi], and the length taken out."""
    out: List[Tuple[float, float]] = []
    taken = 0.0
    for a, b in pieces:
        cut_lo, cut_hi = max(a, lo), min(b, hi)
        if cut_hi <= cut_lo:
            out.append((a, b))
            continue
        taken += cut_hi - cut_lo
        if a < cut_lo:
            out.append((a, cut_lo))
        if cut_hi < b:
            out.append((cut_hi, b))
    return out, taken


def busy_by_class(beats: List[Dict[str, Any]],
                  programs: List[Dict[str, Any]],
                  span: Tuple[float, float]) -> Dict[str, float]:
    """Program class -> microseconds of its slices inside the span (the
    slices of one device lane do not overlap, so the classes add up to
    device_busy)."""
    t0, t1 = span
    out: Dict[str, float] = {}
    for ev in list(beats) + list(programs):
        cls = "decode" if ev.get("cat") == "beat" \
            else ev.get("args", {}).get("class", "program")
        lo, hi = max(ev["ts"], t0), min(ev["ts"] + ev.get("dur", 0.0), t1)
        if hi > lo:
            out[cls] = out.get(cls, 0.0) + hi - lo
    return out


def attribute_lane(beats: List[Dict[str, Any]],
                   instants: List[Dict[str, Any]],
                   span: Tuple[float, float],
                   host_gap_us: float,
                   programs: Tuple[Dict[str, Any], ...] = (),
                   pauses: Tuple[Dict[str, Any], ...] = ()
                   ) -> Dict[str, float]:
    """Category -> microseconds over one lane's [t0, t1] span."""
    out = {c: 0.0 for c in CATEGORIES}
    t0, t1 = span
    if t1 <= t0:
        return out
    busy = _merge_intervals(
        [(b["ts"], b["ts"] + b.get("dur", 0.0))
         for b in list(beats) + list(programs)])
    busy = [(max(lo, t0), min(hi, t1)) for lo, hi in busy
            if hi > t0 and lo < t1]
    out["device_busy"] = sum(hi - lo for lo, hi in busy)
    # First sighting of each plan label: the beat AFTER a gap carrying
    # a brand-new label marks that gap as a cold compile.
    seen: set = set()
    cold_edges: set = set()
    for b in sorted(beats, key=lambda b: b["ts"]):
        if b["name"] not in seen:
            seen.add(b["name"])
            cold_edges.add(b["ts"])
    # Gaps: the complement of `busy` over [t0, t1].
    gaps: List[Tuple[float, float]] = []
    cursor = t0
    for lo, hi in busy:
        if lo > cursor:
            gaps.append((cursor, lo))
        cursor = max(cursor, hi)
    if cursor < t1:
        gaps.append((cursor, t1))
    inst_sorted = sorted(instants, key=lambda e: e["ts"])
    for lo, hi in gaps:
        # the host's pauses first, each by what it covers of the gap
        left = [(lo, hi)]
        for cause in HOST_PAUSES:
            for p in pauses:
                if p["name"] == cause:
                    left, taken = _subtract(
                        left, p["ts"], p["ts"] + p.get("dur", 0.0))
                    out[cause] += taken
        rest = sum(b - a for a, b in left)
        inside = [e["name"] for e in inst_sorted if lo <= e["ts"] <= hi]
        cat = None
        for name, category in CAUSE_PRIORITY:
            if name in inside:
                cat = category
                break
        if cat is None and any(abs(edge - hi) < 1.0 for edge in cold_edges):
            cat = "cold_plan"
        if cat is None:
            cat = "host_gap" if rest <= host_gap_us else "idle"
        out[cat] += rest
    return out


def analyze(trace: Dict[str, Any], host_gap_ms: float = 50.0,
            lane: Optional[int] = None) -> Dict[str, Any]:
    """Per-lane + overall attribution of a Chrome trace dict. Returns
    {"lanes": {pid: {...}}, "overall": {"wall_ms", "categories":
    {name: {"ms", "pct"}}, "attributed_pct", "top_causes": [...]}}."""
    events = trace.get("traceEvents", [])
    by_pid: Dict[int, Dict[str, List]] = {}
    for ev in events:
        if ev.get("ph") == "M":
            continue
        pid = int(ev.get("pid", 0))
        if lane is not None and pid != lane:
            continue
        d = by_pid.setdefault(pid, {"beats": [], "programs": [],
                                    "instants": [], "pauses": [],
                                    "all_ts": []})
        ts = float(ev.get("ts", 0.0))
        end = ts + float(ev.get("dur", 0.0) or 0.0)
        d["all_ts"] += [ts, end]
        if ev.get("cat") == "beat" and ev.get("ph") == "X":
            d["beats"].append(ev)
        elif ev.get("cat") == "program" and ev.get("ph") == "X":
            d["programs"].append(ev)
        elif ev.get("cat") == "gap-cause" and ev.get("ph") == "i":
            d["instants"].append(ev)
        elif ev.get("cat") == "host-pause" and ev.get("ph") == "X":
            d["pauses"].append(ev)
    lanes: Dict[str, Any] = {}
    total = {c: 0.0 for c in CATEGORIES}
    by_class: Dict[str, float] = {}
    wall_us = 0.0
    for pid, d in sorted(by_pid.items()):
        if not d["all_ts"]:
            continue
        span = (min(d["all_ts"]), max(d["all_ts"]))
        cats = attribute_lane(d["beats"], d["instants"], span,
                              host_gap_ms * 1e3, tuple(d["programs"]),
                              tuple(d["pauses"]))
        classes = busy_by_class(d["beats"], d["programs"], span)
        lane_wall = span[1] - span[0]
        lanes[str(pid)] = {
            "wall_ms": round(lane_wall / 1e3, 3),
            "beats": len(d["beats"]),
            "programs": len(d["programs"]),
            "categories": {c: round(v / 1e3, 3)
                           for c, v in cats.items() if v > 0},
            "device_busy_by_class": {c: round(v / 1e3, 3)
                                     for c, v in classes.items()},
        }
        for c, v in cats.items():
            total[c] += v
        for c, v in classes.items():
            by_class[c] = by_class.get(c, 0.0) + v
        wall_us += lane_wall
    cats_out = {}
    for c in CATEGORIES:
        ms = total[c] / 1e3
        pct = (100.0 * total[c] / wall_us) if wall_us else 0.0
        if ms > 0 or c == "device_busy":
            cats_out[c] = {"ms": round(ms, 3), "pct": round(pct, 2)}
    attributed = sum(v["pct"] for v in cats_out.values())
    gap_causes = sorted(
        ((c, v) for c, v in cats_out.items()
         if c not in ("device_busy", "idle")),
        key=lambda kv: -kv[1]["ms"])
    return {
        "lanes": lanes,
        "overall": {
            "wall_ms": round(wall_us / 1e3, 3),
            "categories": cats_out,
            "device_busy_by_class": {
                c: {"ms": round(v / 1e3, 3),
                    "pct": round(100.0 * v / wall_us, 2) if wall_us else 0.0}
                for c, v in sorted(by_class.items())},
            # Partition of [first, last] by construction — ~100 up to
            # rounding; the smoke gate pins >= 95.
            "attributed_pct": round(attributed, 2),
            "top_causes": [c for c, _ in gap_causes[:4]],
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Stall attribution over a /debug/timeline artifact")
    ap.add_argument("path", help="Chrome trace JSON file")
    ap.add_argument("--json", action="store_true",
                    help="emit the attribution dict as one JSON line")
    ap.add_argument("--lane", type=int, default=None,
                    help="restrict to one replica lane (pid)")
    ap.add_argument("--host-gap-ms", type=float, default=50.0,
                    help="uncaused gaps longer than this are idle")
    args = ap.parse_args()
    with open(args.path) as f:
        trace = json.load(f)
    report = analyze(trace, host_gap_ms=args.host_gap_ms, lane=args.lane)
    if args.json:
        print(json.dumps(report))
        return 0
    ov = report["overall"]
    print(f"wall: {ov['wall_ms']:.1f} ms over {len(report['lanes'])} "
          f"lane(s); attribution {ov['attributed_pct']:.1f}%")
    print(f"{'category':<18}{'ms':>12}{'pct':>8}")
    for c, v in sorted(ov["categories"].items(), key=lambda kv: -kv[1]["ms"]):
        print(f"{c:<18}{v['ms']:>12.1f}{v['pct']:>7.1f}%")
    for c, v in ov["device_busy_by_class"].items():
        print(f"  device_busy.{c:<11}{v['ms']:>10.1f}{v['pct']:>7.1f}%")
    if ov["top_causes"]:
        print("top gap causes: " + ", ".join(ov["top_causes"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
