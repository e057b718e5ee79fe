"""Tokens that reached the clients inside the window, over its length."""
from benchmark.harness import stats


def read(ctx):
    n = stats.tokens_in_window(ctx["records"], ctx["seconds"])
    return n / ctx["seconds"] if n else None
