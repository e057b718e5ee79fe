"""The share of (expert layer, held expert, step) triples in which the
expert took a pair, over the window: `hit` over `of`, summed over the
engine's `moe_load` flight events (kind 19, one a landed decode block of
a model with sparse experts) whose `aux` carries `hit=<n> of=<m>`. It is
what the routing DID, where an entry's `experts_hit` is what uniform
routing would: which of a layer's held weights a step reads is decided by
the router, not by the shape. An engine whose events carry no such `aux`
(every program from before it) gives None."""
from benchmark.harness import stats

MOE_LOAD = 19


def hit_share(events, keep):
    """hit / of over the `moe_load` events `keep(e)` holds, or None."""
    hit = of = 0
    for e in events:
        if e["kind"] != MOE_LOAD or not keep(e):
            continue
        aux = dict(kv.split("=", 1) for kv in (e.get("aux") or "").split())
        if "hit" in aux and "of" in aux:
            hit += int(aux["hit"])
            of += int(aux["of"])
    return hit / of if of else None


def read(ctx):
    return hit_share(ctx["engine"]["events"],
                     lambda e: stats.in_window(e["t"], ctx["seconds"]))
