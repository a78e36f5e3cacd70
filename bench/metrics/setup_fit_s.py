"""Seconds set-up spends fitting, its compiles left out: the process's
``engine.cv`` and ``engine.fit`` self time on ``/stats`` before the
window (the LOO-CV selection and the final fit of every predictor)."""
from bench.metrics import _spans as S


def read(ctx):
    t = S.process(ctx.stats_before)
    if "engine.cv" not in t and "engine.fit" not in t:
        return None
    return S.own(t, "engine.cv") + S.own(t, "engine.fit")
