"""Mean choose-lane tick in the window: requests over batches of every
choose lane (``/stats`` before and after; predict lanes are named
``job@machine``)."""
from bench.metrics import lane_delta


def read(ctx):
    req, bat = lane_delta(ctx, lambda name: "@" not in name)
    return req / bat if bat else None
