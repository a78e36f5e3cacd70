"""Seconds set-up spends lowering (jaxpr to MLIR) and compiling
executables, a compile-cache read included: the process's
``engine.lower`` and ``engine.compile`` totals on ``/stats`` before the
window."""
from bench.metrics import _spans as S


def read(ctx):
    t = S.process(ctx.stats_before)
    if "engine.lower" not in t and "engine.compile" not in t:
        return None
    return S.total(t, "engine.lower") + S.total(t, "engine.compile")
