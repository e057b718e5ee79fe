"""How late the generator sent the requests due in the window."""
from benchmark.harness import stats


def read(ctx, q):
    return stats.percentile(stats.lag_ms(ctx["records"], ctx["seconds"]), q)
