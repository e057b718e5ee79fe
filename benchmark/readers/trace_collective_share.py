"""Device time of collective operations over busy time, chip 0."""
import re

COLLECTIVE = re.compile(r"all-reduce|all_reduce|all-gather|all_gather|"
                        r"reduce-scatter|reduce_scatter|collective-permute|"
                        r"all-to-all")


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["busy_s"]:
        return None
    t = sum(s for k, s in tr["ops"].items() if COLLECTIVE.search(k))
    return 100.0 * t / tr["busy_s"]
