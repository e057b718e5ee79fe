"""Time to first token, due time -> first token frame at the client, as
a percentile over the requests due inside the window."""
from benchmark.harness import stats


def read(ctx, q):
    return stats.percentile(stats.ttft_ms(ctx["records"], ctx["seconds"]), q)
