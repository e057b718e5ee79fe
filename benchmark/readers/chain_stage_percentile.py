"""A percentile over the window's /generate requests of one stage of the
chain server's request timeline (obs/tracing.py::Timeline): how long the
stage took, or, with `field`, what the callee of that stage reported
about its own inside in its `Server-Timing` header (the encoder's
`total`, `tokenize`, `queue`, `ready`).

The chain server writes one JSON line per request on the logger
`gaie.timeline`, after the request's last frame; the harness sends the
child's output to `.bench_out/chain-server.log` and stops the child
before any reader runs. `received` is `time.monotonic()` in the child,
`ctx["engine"]["open"]["t"]` the same clock at the window's opening
(CLOCK_MONOTONIC is one clock for every process of a Linux machine)."""
import json
import os

from benchmark.harness import stats, system

LOGGER = "gaie.timeline"


def timelines(ctx):
    """The timelines of the requests RECEIVED inside the window; None
    where the log or the lines are missing (a parent that writes none)."""
    try:
        fh = open(os.path.join(system.OUT_DIR, "chain-server.log"))
    except OSError:
        return None
    t_open = ctx["engine"]["open"]["t"]
    out = []
    with fh:
        for line in fh:
            at = line.find(LOGGER)
            brace = line.find("{", at)
            if at < 0 or brace < 0:
                continue
            try:
                rec = json.loads(line[brace:])
            except ValueError:
                continue
            if {"rid", "received", "stages"} <= rec.keys() and stats.in_window(
                    rec["received"] - t_open, ctx["seconds"]):
                out.append(rec)
    return out or None


def stage_ms(timeline, stage, field=None):
    """The stage's duration in ms (summed where it ran twice), or the
    named Server-Timing field of it; None where the request has none."""
    hits = [s for s in timeline["stages"] if s["name"] == stage]
    if field is not None:
        hits = [s for s in hits if field in s.get("server", {})]
        return sum(s["server"][field] for s in hits) if hits else None
    return sum(s["end"] - s["start"] for s in hits) * 1e3 if hits else None


def read(ctx, stage, q=50, field=None):
    found = timelines(ctx)
    if found is None:
        return None
    values = [v for v in (stage_ms(t, stage, field) for t in found)
              if v is not None]
    return stats.percentile(values, q)
