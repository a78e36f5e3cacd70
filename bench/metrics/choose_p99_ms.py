"""p99 of every choose due in the window, from when it was due to its
answer; one that failed counts as over any limit."""
from bench.metrics import latencies, percentile


def read(ctx):
    p = percentile(latencies(ctx, ("choose",)), 99)
    return None if p is None else 1e3 * p
