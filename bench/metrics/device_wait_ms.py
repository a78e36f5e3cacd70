"""Time a choose-lane tick waits on the device for its results in the
window (the choose lanes' ``engine.sync`` total on ``/stats``, over their
``lane.tick`` count)."""
from bench.metrics import _spans as S


def read(ctx):
    t = S.window(ctx, S.choose_lanes)
    n = S.count(t, "lane.tick")
    return 1e3 * S.total(t, "engine.sync") / n if n > 0 else None
