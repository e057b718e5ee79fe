"""The median, over the window, of one field of the engine's
`sparse_select` flight events (kind 22, one a landed decode block of a
model with learned sparse attention, from the lengths the host dispatched
it with): a = index keys scored a live slot, step and layer; b = rows
attended over keys scored. An engine that writes no such event (no
indexer) gives None."""
from benchmark.harness import stats

SPARSE_SELECT = 22


def read(ctx, field):
    values = [e[field] for e in ctx["engine"]["events"]
              if e["kind"] == SPARSE_SELECT
              and stats.in_window(e["t"], ctx["seconds"])]
    return stats.percentile(values, 50) if values else None
