"""Mean wait of a choose request on its lane in the window, from enqueue
to the start of its tick (the choose lanes' ``lane.wait`` interval on
``/stats``)."""
from bench.metrics import _spans as S


def read(ctx):
    t = S.window(ctx, S.choose_lanes)
    n = S.count(t, "lane.wait")
    return 1e3 * S.total(t, "lane.wait") / n if n > 0 else None
