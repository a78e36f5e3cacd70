"""Faults planted under the timed path, for ``bench/test_faults.py`` and
the fault readings in PERF.md: each must turn a run's ``correct``
false.  Benchmark runs never plant one.

``runtime``  every runtime the engine predicts comes out 3% high (an
             answer altered where it is produced)
``tree``     the boosted trees' runtimes (GBM, OGB) come out 3% high:
             on a TPU the GBM's are the Pallas kernel's answers
``rule``     the choose rule leaves the deadline out: every choose gets
             the cheapest configuration
``half``     a choose tick scores only the first half of its contexts and
             answers the rest with those answers (half of the batch left
             out)
"""
from __future__ import annotations

FAULTS = ("runtime", "tree", "rule", "half")


def plant(name: str) -> None:
    if name in ("runtime", "tree"):
        from repro.core import engine
        predict = engine.predict
        hit = None if name == "runtime" else ("gbm", "ogb")

        def high(spec, params, X, aux):
            out = predict(spec, params, X, aux)
            return out * 1.03 if hit is None or spec.name in hit else out

        engine.predict = high
    elif name == "rule":
        from repro.core.service import ConfigurationService
        select = ConfigurationService._select

        def cheapest(cf, bf, of, t_max, C):
            return select(cf, bf, of, None, C)

        ConfigurationService._select = staticmethod(cheapest)
    elif name == "half":
        import numpy as np
        from repro.core.service import ConfigurationService
        choose = ConfigurationService.choose_cluster_batch

        def half(self, contexts, t_max=None, zones=None, options=None):
            contexts = np.atleast_2d(np.asarray(contexts, np.float64))
            C = len(contexts)
            k = max(1, C // 2)
            tm = None if t_max is None else np.broadcast_to(
                np.asarray(t_max, np.float64), (C,))
            out = choose(self, contexts[:k], None if tm is None else tm[:k],
                         zones, options)
            return [out[i % k] for i in range(C)]

        ConfigurationService.choose_cluster_batch = half
    else:
        raise ValueError(f"unknown fault {name!r} (known: {FAULTS})")
