"""A count the harness took during set-up (e.g. programs that missed
the persistent compile cache)."""


def read(ctx, name):
    return ctx["counters"].get(name)
