"""The median, over the window, of one field of the engine's
`state_cache` flight events (kind 24, one a landed decode block of a
model that carries recurrent state beside a latent row, from the lengths
the host dispatched it with): a = the live slots' mean context over the
block's steps, in tokens (what the latent layers' kernel walks a slot and
call); b = a live sequence's latent-row bytes over its state + tail +
latent-row bytes (the share of a sequence's memory that grows with it).
An engine that writes no such event (no state beside a latent row, as
every program from before them) gives None."""
from benchmark.harness import stats

STATE_CACHE = 24


def read(ctx, field):
    values = [e[field] for e in ctx["engine"]["events"]
              if e["kind"] == STATE_CACHE
              and stats.in_window(e["t"], ctx["seconds"])]
    return stats.percentile(values, 50) if values else None
