"""A percentile over the window's requests of an interval between two
events of the engine's flight recorder, request by request (`start` and
`end` are event names: the time from a request's first `start` event to
its first `end` event at or after it, in ms), or, with `field` and no
`end`, of that field of the `start` event (`b` of `submit` is the ms the
OpenAI surface spent on the request before the engine saw it; a field
the program never wrote reads 0.0 exactly, and such events are left out:
a program that stamps none gives no reading, not one of 0 ms).

A request counts when its `start` event lies inside the window. Where
the program records no such event, or none of these requests has both,
there is nothing to read: None."""
from benchmark.harness import stats

KINDS = {"submit": 1, "admit": 3, "prefill_dispatch": 4, "first_token": 6,
         "retire": 7}


def by_rid(ctx, kind):
    """rid -> that request's events of one kind, in the order recorded."""
    out = {}
    for e in ctx["engine"]["events"]:
        if e["kind"] == KINDS[kind] and e["rid"]:
            out.setdefault(e["rid"], []).append(e)
    return out


def read(ctx, start, end=None, q=50, field=None):
    starts = {rid: evs[0] for rid, evs in by_rid(ctx, start).items()
              if stats.in_window(evs[0]["t"], ctx["seconds"])}
    if end is None:
        return stats.percentile(
            [e[field] for e in starts.values() if e[field] != 0.0], q)
    ends = by_rid(ctx, end)
    values = []
    for rid, first in starts.items():
        after = [e for e in ends.get(rid, ()) if e["t"] >= first["t"]]
        if after:
            values.append((after[0]["t"] - first["t"]) * 1e3)
    return stats.percentile(values, q)
