"""Share of the time with an API request in the edge during which the
device runs nothing: 1 - device busy time (trace) / seconds with at
least one request in the edge (the process's ``edge.in_flight`` interval
on ``/stats``).  Untraced runs read None."""
from bench.metrics import _spans as S


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["devices"]:
        return None
    held = S.total(S.window(ctx, S.process), "edge.in_flight")
    return 1.0 - tr["busy_s"] / held if held > 0 else None
