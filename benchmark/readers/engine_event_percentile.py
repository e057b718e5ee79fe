"""A percentile of the engine's own per-request observations inside the
window, read from its flight recorder's events: kind 3 is admit (value:
queue wait, ms), kind 6 is first token (value: engine time to first
token, ms). These are the observations its histograms are fed with,
exact instead of bucketed."""
from benchmark.harness import stats

KINDS = {"admit": 3, "first_token": 6}


def values(ctx, kind):
    return [e["a"] for e in ctx["engine"]["events"]
            if e["kind"] == KINDS[kind]
            and stats.in_window(e["t"], ctx["seconds"])]


def read(ctx, kind, q):
    return stats.percentile(values(ctx, kind), q)
