"""What the hop from the chain server to the engine costs a request's
first token, subtracted request by request: the chain's `llm_first_piece`
stage (POST issued -> first non-empty piece parsed) less what the engine
server accounts for inside it, the surface's work before submit (`b` of
the flight recorder's `submit` event) plus the engine's own submit ->
first token (`a` of `first_token`). What is left is HTTP both ways, SSE
framing and `iter_lines`.

A chain timeline joins its engine request through the `x-request-id` the
connector sends: the engine records it as `aux` of `submit`. A request
that does not join is left out; `joined()` says how many did."""
from benchmark.harness import stats
from benchmark.readers import chain_stage_percentile, engine_interval_percentile


def joined(ctx):
    """[(timeline, submit event, first_token event)] of the window's
    chain requests that join an engine request; None with no timelines."""
    found = chain_stage_percentile.timelines(ctx)
    if found is None:
        return None
    submits = {evs[0]["aux"]: evs[0] for evs in
               engine_interval_percentile.by_rid(ctx, "submit").values()
               if evs[0]["aux"]}
    firsts = engine_interval_percentile.by_rid(ctx, "first_token")
    out = []
    for t in found:
        sub = submits.get(t["rid"])
        first = firsts.get(sub["rid"]) if sub else None
        if first:
            out.append((t, sub, first[0]))
    return out


def read(ctx, q=50):
    values = []
    for timeline, sub, first in joined(ctx) or ():
        stage = chain_stage_percentile.stage_ms(timeline, "llm_first_piece")
        if stage is not None:
            values.append(stage - (sub["b"] + first["a"]))
    return stats.percentile(values, q)
