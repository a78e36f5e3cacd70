"""One reader per metric, found by the metric's name in BENCHMARK.json.

``read(ctx)`` returns the metric's value, or None where the run holds
nothing to read it from (the harness then leaves the metric out).  The
context (``bench/run.py``) carries the traffic with every request's
times and answers, ``/stats`` before and after the window, the server's
window readings (``window``), its trace reduction (``trace``, traced runs
only), the model the hub serves for each (job, machine) (``selected``),
the configuration and the mix.
"""
from __future__ import annotations

import math


def latencies(ctx, ops) -> list:
    """Seconds from due to answer of every request of ``ops`` due in the
    window; a request that failed or never came reads infinite."""
    return [r.done - r.due if r.ok else math.inf
            for r in ctx.traffic.all() if r.op in ops]


def percentile(values, p: float):
    """Nearest-rank percentile; None without values."""
    v = sorted(values)
    if not v:
        return None
    return v[min(len(v) - 1, max(0, math.ceil(p / 100.0 * len(v)) - 1))]


def lane_delta(ctx, want) -> tuple:
    """(requests, batches) the lanes ``want(name)`` served in the window."""
    before = {ln.lane: ln for ln in ctx.stats_before.lanes}
    req = bat = 0
    for ln in ctx.stats_after.lanes:
        if not want(ln.lane):
            continue
        b = before.get(ln.lane)
        req += ln.requests - (b.requests if b else 0)
        bat += ln.batches - (b.batches if b else 0)
    return req, bat
