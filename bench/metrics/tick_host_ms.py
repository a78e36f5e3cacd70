"""Host time of a choose-lane tick in the window: pack, service lookup,
grid dispatches, selection and envelopes, everything of the tick but its
waits on the device (the choose lanes' ``lane.tick`` less their
``engine.sync`` totals on ``/stats``, over the ticks)."""
from bench.metrics import _spans as S


def read(ctx):
    t = S.window(ctx, S.choose_lanes)
    n = S.count(t, "lane.tick")
    if n <= 0:
        return None
    return 1e3 * (S.total(t, "lane.tick") - S.total(t, "engine.sync")) / n
