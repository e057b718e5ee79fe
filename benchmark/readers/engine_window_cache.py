"""The median, over the window, of one field of the engine's
`window_cache` flight events (kind 23, one a landed decode block of a
model with window layers beside global ones, from the lengths and tables
the host dispatched it with): a = cached tokens the block's attention
calls see over layers x context of its live slots (what one kind of cache
row would have seen); b = pages x rows the live slots hold in both pools
over what one page table for every row would hold. An engine that writes
no such event (no window rows, as every program from before them) gives
None."""
from benchmark.harness import stats

WINDOW_CACHE = 23


def read(ctx, field):
    values = [e[field] for e in ctx["engine"]["events"]
              if e["kind"] == WINDOW_CACHE
              and stats.in_window(e["t"], ctx["seconds"])]
    return stats.percentile(values, 50) if values else None
