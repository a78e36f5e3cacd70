"""Mean time an API request spends in the edge's own phases in the
window: head and body read, decode, encode and write (the process's
``edge.read``, ``edge.decode``, ``edge.encode`` and ``edge.write`` span
totals from ``/stats``) over the requests decoded."""
from bench.metrics import _spans as S

PHASES = ("edge.read", "edge.decode", "edge.encode", "edge.write")


def read(ctx):
    t = S.window(ctx, S.process)
    n = S.count(t, "edge.decode")
    if n <= 0:
        return None
    return 1e3 * sum(S.total(t, p) for p in PHASES) / n
