"""A percentile of all token gaps of all streams, pooled; a gap counts
when its later token arrived inside the window."""
from benchmark.harness import stats


def read(ctx, q):
    return stats.percentile(
        stats.pooled_gaps_ms(ctx["records"], ctx["seconds"]), q)
