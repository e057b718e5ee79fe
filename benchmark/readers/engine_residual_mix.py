"""The median, over the window, of one field of the engine's
`residual_mix` flight events (kind 26, one a landed decode block of a
model whose residual is several streams mixed around every branch, from
the mask the host dispatched the block with): a = the branches mixed a
step of the block (live slots x 2 x layers); b = the stream's bytes a
token. An engine that writes no such event (one stream and one add, as
every program from before them) gives None."""
from benchmark.harness import stats

RESIDUAL_MIX = 26


def read(ctx, field):
    values = [e[field] for e in ctx["engine"]["events"]
              if e["kind"] == RESIDUAL_MIX
              and stats.in_window(e["t"], ctx["seconds"])]
    return stats.percentile(values, 50) if values else None
