"""What stands between the client and the engine: the median time to
first token the clients saw less the median the engine recorded
(submit -> first token) over the same window. In a completions cell
that is the OpenAI surface; in a chain cell it is everything before the
LLM call (embed, search, prompt assembly) plus both surfaces."""
from benchmark.harness import stats
from benchmark.readers import engine_event_percentile


def read(ctx, q=50):
    client = stats.percentile(
        stats.ttft_ms(ctx["records"], ctx["seconds"]), q)
    engine = stats.percentile(
        engine_event_percentile.values(ctx, "first_token"), q)
    if client is None or engine is None:
        return None
    return client - engine
