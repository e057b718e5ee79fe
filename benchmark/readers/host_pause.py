"""What the HOST was doing while the window's programs ran, from the
engine's flight events on the ledger's clock: the `host_pause` events
(kind 25: one a pause of the process; `t` = its end, `a` = its length in
ms, `code` = cause: 0 a collection of the interpreter's, 1 a timed wait
of the scheduler's that came back late) and the `call=<ms>` that every
`program` event (kind 20, readers/program_window.py) carries in its
`aux`: enqueue -> the dispatch call returned.

A pause is IN the window when its end is, a program when its completion
is (the rule of readers/program_window.py). `stat`:
  gc_pause              sum of `a` over the window's pauses of cause 0
  longest_pause         the longest single pause in the window: a
                        `host_pause` event's `a` of either cause, or a
                        window's program's `call`
  call_p99              p99 of `call` over the window's programs of every
                        class (never above `longest_pause`)
  longest_program_host  of the window's program with the largest `b`
                        (the one `*.window.longest_program_ms` names),
                        the ms of its start -> completion that the UNION
                        of the host's pauses covers: every `host_pause`
                        event's [end - a, end] and every program's
                        [enqueue, enqueue + call], whether or not they
                        are in the window, each part counted once. Never
                        above that program's `b`; beside it, it says
                        whether the host stood still or the device ran.

A program that writes no `call=` (a parent without it, the recorder
off) gives None for every stat; one that does gives a number, 0.0 where
nothing paused."""
from benchmark.harness import stats

PROGRAM = 20
HOST_PAUSE = 25
GC = 0


def programs(ctx):
    """Every `program` event that carries `call=`: its `b`, its call and
    its instants in seconds from the window's opening."""
    out = []
    for e in ctx["engine"]["events"]:
        if e["kind"] != PROGRAM:
            continue
        aux = dict(kv.split("=", 1) for kv in e["aux"].split() if "=" in kv)
        if "call" not in aux:
            continue
        t_enqueue = e["t"] - e["a"] / 1e3
        out.append({"b": e["b"], "call": float(aux["call"]),
                    "t_ready": e["t"], "t_start": e["t"] - e["b"] / 1e3,
                    "t_enqueue": t_enqueue})
    return out


def pauses(ctx):
    """Every `host_pause` event: (start, end, ms, cause)."""
    return [(e["t"] - e["a"] / 1e3, e["t"], e["a"], e["code"])
            for e in ctx["engine"]["events"] if e["kind"] == HOST_PAUSE]


def covered_ms(lo, hi, intervals):
    """Length in ms of the union of `intervals` inside [lo, hi]."""
    total, cursor = 0.0, lo
    for start, end in sorted(intervals):
        start, end = max(start, cursor), min(end, hi)
        if end > start:
            total += end - start
            cursor = end
    return total * 1e3


def read(ctx, stat):
    progs = programs(ctx)
    if not progs:
        return None
    seconds = ctx["seconds"]
    inside = [p for p in progs if stats.in_window(p["t_ready"], seconds)]
    every = pauses(ctx)
    held = [p for p in every if stats.in_window(p[1], seconds)]
    if stat == "gc_pause":
        return sum(ms for _, _, ms, cause in held if cause == GC)
    if stat == "longest_pause":
        return max([ms for _, _, ms, _ in held]
                   + [p["call"] for p in inside], default=0.0)
    if stat == "call_p99":
        return stats.percentile([p["call"] for p in inside], 99) or 0.0
    if stat == "longest_program_host":
        if not inside:
            return 0.0
        longest = max(inside, key=lambda p: p["b"])
        host = [(start, end) for start, end, _, _ in every]
        host += [(p["t_enqueue"], p["t_enqueue"] + p["call"] / 1e3)
                 for p in progs]
        return covered_ms(longest["t_start"], longest["t_ready"], host)
    raise ValueError(f"host_pause: no stat {stat!r}")
