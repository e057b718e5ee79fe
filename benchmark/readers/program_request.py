"""A request's way to its first token through its prefill program, from
the ledger (readers/program_window.py): the request's `prefill_dispatch`
event (kind 4) carries its program's sequence number in `b`, and that
program's `program` event gives enqueue, start and completion. `part`:
  queue  t_enqueue -> t_start: the wait behind the blocks in flight
  run    t_start -> t_ready: the prefill on the device (the event's `b`)
  lag    t_ready -> the request's `first_token` event: the small copy
         and the scheduler's poll
The three tile t_enqueue -> first_token exactly; `prefill_dispatch` is
stamped after the dispatch call returned, so they exceed
`sched.dispatch_to_first_token_p50_ms` by that call's own host time.

A request counts when its `prefill_dispatch` lies inside the window.
Percentile `q` over those requests; None where no request joins a
program (a parent without the ledger writes none)."""
from benchmark.harness import stats
from benchmark.readers import engine_interval_percentile, program_window

PREFILL = 1


def joined(ctx):
    """[(prefill_dispatch event, its program, first_token event or None)]
    for the window's requests."""
    by_seq = {p["seq"]: p for p in program_window.programs(ctx)
              if p["cls"] == PREFILL}
    firsts = engine_interval_percentile.by_rid(ctx, "first_token")
    out = []
    for rid, evs in engine_interval_percentile.by_rid(
            ctx, "prefill_dispatch").items():
        disp = evs[0]
        prog = by_seq.get(int(disp["b"]))
        if prog is None or not stats.in_window(disp["t"], ctx["seconds"]):
            continue
        after = [e for e in firsts.get(rid, ()) if e["t"] >= disp["t"]]
        out.append((disp, prog, after[0] if after else None))
    return out


def read(ctx, part, q=50):
    values = []
    for _, prog, first in joined(ctx):
        if part == "queue":
            values.append(prog["a"] - prog["b"])
        elif part == "run":
            values.append(prog["b"])
        elif part == "lag":
            if first is not None:
                values.append((first["t"] - prog["t_ready"]) * 1e3)
        else:
            raise ValueError(f"program_request: no part {part!r}")
    return stats.percentile(values, q)
