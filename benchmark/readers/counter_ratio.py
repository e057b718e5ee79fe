"""The change of one engine counter over the change of another across
the window (e.g. busy slot-steps over decode steps: mean occupancy)."""


def read(ctx, num, den):
    a, b = ctx["engine"]["open"], ctx["engine"]["close"]
    d = b[den] - a[den]
    return (b[num] - a[num]) / d if d else None
