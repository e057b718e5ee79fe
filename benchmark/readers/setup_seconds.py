"""A phase of set-up by the harness's clock. `total_s` runs from process
start to the opening of the window: load, weights, warm-up, the
reference check, ingest and the ramp."""


def read(ctx, phase):
    return ctx["phases"].get(phase)
