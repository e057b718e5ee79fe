"""The window's programs, from the engine's ledger of everything the
process enqueued on the device (flight event kind 20, `program`: one a
program the device finished; `t` = its completion, `code` = class: 0 a
decode block, 1 a prefill group, 2 a chunk or commit, 3 an encoder
forward; `a` = enqueue -> completion in ms, what the host waited; `b` =
inferred start -> completion in ms, what the device ran, the start
being the later of the enqueue and the completion of the program
enqueued before it; `slot` = live rows; `aux` = "seq=<n> n=<steps or
real tokens> shape=<shape>").

A program is IN the window when its completion is. `stat`:
  per_unit  sum of `b` over the window's programs of class `cls` over
            the sum of their `n` (x `scale`: 1000 makes ms per steps
            into ms per thousand tokens)
  queue     percentile `q` of `a - b` (enqueue -> start) of class `cls`
  longest   the largest `b` of any class
  busy      % of the window covered by the union of [start, completion]
            of every program, clipped to the window (a program that
            straddles an edge counts for the part inside)
The ledger infers a start from completions that threads stamp under
the interpreter's lock, so a stamp can be up to a thread switch (5 ms)
late: sums over a window are good to a few tenths of a percent, one
program's `b` to that switch.

A program that writes no such event (a parent without the ledger, the
recorder off) gives None, never 0."""
from benchmark.harness import stats

PROGRAM = 20


def programs(ctx):
    """Every `program` event, parsed: seq, cls, n, rows, a, b and the
    three instants in seconds from the window's opening."""
    out = []
    for e in ctx["engine"]["events"]:
        if e["kind"] != PROGRAM:
            continue
        aux = dict(kv.split("=", 1) for kv in e["aux"].split() if "=" in kv)
        out.append({
            "seq": int(aux["seq"]), "cls": e["code"], "n": int(aux["n"]),
            "shape": aux.get("shape", ""), "rows": e["slot"],
            "a": e["a"], "b": e["b"], "t_ready": e["t"],
            "t_start": e["t"] - e["b"] / 1e3,
            "t_enqueue": e["t"] - e["a"] / 1e3})
    return out


def in_window(ctx, cls=None):
    return [p for p in programs(ctx)
            if stats.in_window(p["t_ready"], ctx["seconds"])
            and (cls is None or p["cls"] == cls)]


def busy_seconds(progs, lo, hi):
    """Length of the union of [start, completion] inside [lo, hi]."""
    total, cursor = 0.0, lo
    for p in sorted(progs, key=lambda p: p["t_start"]):
        start, end = max(p["t_start"], cursor), min(p["t_ready"], hi)
        if end > start:
            total += end - start
            cursor = end
    return total


def read(ctx, stat, cls=None, q=50, scale=1.0):
    if stat == "busy":
        everything = programs(ctx)
        if not everything:
            return None
        return 100.0 * busy_seconds(everything, 0.0, ctx["seconds"]) \
            / ctx["seconds"]
    progs = in_window(ctx, cls)
    if not progs:
        return None
    if stat == "per_unit":
        units = sum(p["n"] for p in progs)
        return scale * sum(p["b"] for p in progs) / units if units else None
    if stat == "queue":
        return stats.percentile([p["a"] - p["b"] for p in progs], q)
    if stat == "longest":
        return max(p["b"] for p in progs)
    raise ValueError(f"program_window: no stat {stat!r}")
