"""The median, over the window, of one field of one kind of the engine's
flight-recorder events that carry no request (one a landed decode
block): `moe_load` is kind 19 (a: token-expert pairs computed on the
held experts per step and expert layer; b: the pairs of the busiest held
expert of any one layer over the mean). `per_config_key` divides by a
number of the configuration file (the experts held: pairs a held expert
takes). An engine that writes no such event (no sparse experts) gives
None."""
from benchmark.harness import stats

KINDS = {"moe_load": 19}


def read(ctx, event, field, per_config_key=None):
    values = [e[field] for e in ctx["engine"]["events"]
              if e["kind"] == KINDS[event]
              and stats.in_window(e["t"], ctx["seconds"])]
    if not values:
        return None
    median = stats.percentile(values, 50)
    if per_config_key is not None:
        median /= float(ctx["config"][per_config_key])
    return median
