"""How long a decode slot stands empty between two occupants, slot by
slot, from the flight recorder's events: `retire` (kind 7) and
`admit` (kind 3) carry the slot, `decode_join` (kind 21) is written when
the first decode block in which a request's slot is live is enqueued
and carries that block's ledger sequence number in `b`. `part`:
  retire_to_admit  a `retire` in the window -> the next `admit` on that
                   slot: the client's turn-around, the surface, the
                   queue
  admit_to_decode  that `admit` -> the inferred device START of the
                   occupant's `decode_join` block (its `program` event,
                   readers/program_window.py): prefill and the wait
                   behind the blocks in flight
By Little's law their sum times the rate of requests is the number of
slots standing empty. Percentile `q`; None where `retire` carries no
slot or no request joins a block (a parent writes neither)."""
from benchmark.harness import stats
from benchmark.readers import program_window

ADMIT, RETIRE, DECODE_JOIN, DECODE = 3, 7, 21, 0


def intervals(ctx):
    """[(retire, next admit on its slot, the admitted request's decode
    block or None)] for retires inside the window."""
    events = ctx["engine"]["events"]
    admits = {}
    for e in events:
        if e["kind"] == ADMIT and e["slot"] >= 0:
            admits.setdefault(e["slot"], []).append(e)
    blocks = {p["seq"]: p for p in program_window.programs(ctx)
              if p["cls"] == DECODE}
    joins = {e["rid"]: blocks.get(int(e["b"])) for e in events
             if e["kind"] == DECODE_JOIN}
    out = []
    for e in events:
        if e["kind"] != RETIRE or e["slot"] < 0 \
                or not stats.in_window(e["t"], ctx["seconds"]):
            continue
        nxt = [a for a in admits.get(e["slot"], ()) if a["t"] >= e["t"]]
        if nxt:
            first = min(nxt, key=lambda a: a["t"])
            out.append((e, first, joins.get(first["rid"])))
    return out


def read(ctx, part, q=50):
    values = []
    for retire, admit, block in intervals(ctx):
        if part == "retire_to_admit":
            values.append((admit["t"] - retire["t"]) * 1e3)
        elif part == "admit_to_decode":
            if block is not None:
                values.append((block["t_start"] - admit["t"]) * 1e3)
        else:
            raise ValueError(f"slot_interval: no part {part!r}")
    return stats.percentile(values, q)
