"""% of requests due in the window that met the traffic file's limits
on time to first token and mean token gap; failed requests miss."""
from benchmark.harness import stats


def read(ctx):
    slo = ctx["traffic"].get("slo")
    if not slo:
        return None
    return stats.slo_share(ctx["records"], ctx["seconds"],
                           float(slo["ttft_ms"]), float(slo["mean_gap_ms"]))
