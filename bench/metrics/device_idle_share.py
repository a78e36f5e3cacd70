"""1 - device busy time / window, from the profiler trace (the union of
the device's operation intervals; ``bench/trace_reduce.py``)."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["devices"] or not tr["window_s"]:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
