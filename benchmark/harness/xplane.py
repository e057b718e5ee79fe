"""Reduction of a JAX profiler trace (.xplane.pb) to what the readers
need: device busy time, device time by program and by operation, the
longest idle gaps and what the host was doing in them.

Device planes are named "/device:TPU:<n>". Each has a line "XLA Modules"
(one event per execution of a jitted program, named "jit_<fn>(<id>)")
and a line "XLA Ops" (one event per executed operation; control-flow
operations enclose the operations of their bodies, so totals are taken
over SELF time). The host plane "/host:CPU" has one line per thread.
`load()` turns the file into plain lists so that the arithmetic below is
testable without a profiler (benchmark/tests/test_xplane.py).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SHORT_GAP_S = 0.5e-3


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(name, start_s, duration_s)]}}, sorted
    by start within a line."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                   for e in line.events]
            evs.sort(key=lambda e: (e[1], -e[2]))
            lines.setdefault(line.name, []).extend(evs)
        out[plane.name] = lines
    return out


def device_planes(planes: Dict[str, Dict[str, List[Event]]]) -> List[str]:
    return sorted(p for p in planes if p.startswith("/device:TPU:")
                  and OPS_LINE in planes[p])


def union_seconds(events: Sequence[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """(name, start_s, self_s) of nested events on one line: an event's
    duration less the time its children cover. Events sorted by start,
    longest first, as load() leaves them."""
    out: List[List[Any]] = []
    stack: List[Tuple[int, float]] = []  # (index in out, end)
    for name, start, dur in events:
        while stack and start >= stack[-1][1] - 1e-12:
            stack.pop()
        if stack:
            out[stack[-1][0]][2] -= dur
        out.append([name, start, dur])
        stack.append((len(out) - 1, start + dur))
    return [(n, s, max(d, 0.0)) for n, s, d in out]


def program_of(module_event_name: str) -> str:
    """"jit_decode_multi_step(1234)" -> "decode_multi_step"."""
    name = module_event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_kind(op_event_name: str) -> str:
    """"%fusion.123 = ..." / "fusion.123" -> "fusion"; a kernel keeps
    its given name: "paged_attention_int8.3" -> "paged_attention_int8"."""
    name = op_event_name.split(" = ")[0].lstrip("%").strip()
    return re.sub(r"(\.\d+)+$", "", name) or name


def reduce_device(lines: Dict[str, List[Event]]) -> Dict[str, Any]:
    """One device plane -> window, busy time, per-program executions and
    self time by (program, operation kind)."""
    modules = lines.get(MODULE_LINE, [])
    ops = lines.get(OPS_LINE, [])
    both = modules + ops
    if not both:
        return {"window_s": 0.0, "busy_s": 0.0, "programs": {}, "ops": {},
                "gaps": []}
    t0 = min(e[1] for e in both)
    t1 = max(e[1] + e[2] for e in both)
    busy_events = ops or modules
    starts = [m[1] for m in modules]
    programs: Dict[str, Dict[str, Any]] = {}
    for name, start, dur in modules:
        p = programs.setdefault(program_of(name),
                                {"executions": 0, "device_s": 0.0,
                                 "kernel_calls": {}})
        p["executions"] += 1
        p["device_s"] += dur
    op_time: Dict[str, float] = {}
    for name, start, self_s in self_times(ops):
        i = bisect.bisect_right(starts, start + 1e-12) - 1
        prog = "-"
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            prog = program_of(modules[i][0])
        kind = op_kind(name)
        op_time[f"{prog}/{kind}"] = op_time.get(f"{prog}/{kind}", 0.0) + self_s
        if prog in programs:
            calls = programs[prog]["kernel_calls"]
            calls[kind] = calls.get(kind, 0) + 1
    # idle gaps between consecutive busy intervals
    gaps, end = [], None
    for _, start, dur in sorted(busy_events, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append((end, start - end))
        end = max(end, start + dur) if end is not None else start + dur
    return {"t0": t0, "window_s": t1 - t0,
            "busy_s": union_seconds(busy_events), "programs": programs,
            "ops": op_time, "gaps": gaps}


HOST_LOOKBACK = 64  # events before instant t that may still cover it


def _host_doing(host_lines: Dict[str, List[Event]], t: float) -> str:
    """The innermost (shortest) host event, on any thread, that covers
    instant t."""
    best, best_dur = "host_idle_or_untraced", float("inf")
    for evs in host_lines.values():
        hi = bisect.bisect_right(evs, t, key=lambda e: e[1])
        for name, start, dur in evs[max(0, hi - HOST_LOOKBACK):hi]:
            if 0 < dur < best_dur and t < start + dur:
                best, best_dur = name, dur
    return best


def _top(totals: Dict[str, float], n: int) -> List[List[Any]]:
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(planes: Dict[str, Dict[str, List[Event]]],
           top: int = 10) -> Optional[Dict[str, Any]]:
    """All device planes -> the summary the readers and the printed
    `breakdown` use. busy_s and window_s are averaged over the chips;
    programs, ops and gaps are chip 0's."""
    names = device_planes(planes)
    if not names:
        return None
    per = [reduce_device(planes[n]) for n in names]
    first = per[0]
    host = planes.get("/host:CPU", {})
    by_cause: Dict[str, float] = {}
    short = 0.0
    for start, dur in first["gaps"]:
        if dur < SHORT_GAP_S:
            short += dur
            continue
        cause = op_kind(_host_doing(host, start + dur / 2))
        by_cause[cause] = by_cause.get(cause, 0.0) + dur
    if short:
        by_cause["gaps_under_0.5_ms_between_operations"] = short
    return {
        "chips": len(per),
        "window_s": sum(p["window_s"] for p in per) / len(per),
        "busy_s": sum(p["busy_s"] for p in per) / len(per),
        "programs": first["programs"],
        "ops": first["ops"],
        "device_ops": _top(first["ops"], top),
        "idle_gaps": _top(by_cause, top),
    }
