"""The float64 reference against the hub on the CPU: the same rows give
the same selected model and calibration, the closed-form models'
predictions to float32 rounding, and the boosted trees' to within the
spread their greedy split search shows (PERF.md)."""
from __future__ import annotations

import numpy as np
import pytest

from bench import ref


@pytest.mark.parametrize("job", ["sort", "grep"])
def test_reference_matches_the_hub(job):
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import JobRepo
    from repro.workloads import spark_emul as W
    seed = 2**31 + 3
    d = W.generate_job_data(job, seed)
    repo = JobRepo(job, job, d.schema, RuntimeDataStore(d, seed=seed),
                   predictor_kw=dict(pad_rows=True))
    rng = np.random.default_rng(seed)
    for m in d.machines:
        v = repo.store.data.machine_view(m)
        got = repo.predictor_for(m, seed=seed)
        want = ref.fit_state(v.X, v.y, seed)
        assert got.selected == want.best
        Xq = np.asarray(v.X)[rng.integers(0, len(v), 32)]
        p = want.predict(got.selected, Xq)
        gap = np.max(np.abs(got.predict(Xq) - p) / np.abs(p))
        assert gap < (3e-2 if got.selected in ("gbm", "ogb") else 1e-5)
        for name in ("ernest", "bom"):
            assert got.cv_mape[name] == pytest.approx(want.cv_mape[name],
                                                      rel=1e-4)
        assert got.sigma == pytest.approx(want.sigma[got.selected],
                                          rel=0.2)


def test_choose_regret_reads_the_margin_of_a_near_tie():
    t = np.array([[100.0, 60.0], [90.0, 55.0]])           # [M=2, S=2]
    margin = np.zeros(2)
    prices = np.array([[1.0], [1.0]])
    rates = np.zeros(1)
    S = (1, 2)
    # no deadline: the cheapest is machine 1 at s=1 (cost 90)
    assert ref.choose_regret(t, margin, prices, rates, 0.0, S, np.nan,
                             2) == 0.0
    assert ref.choose_regret(t, margin, prices, rates, 0.0, S, np.nan,
                             0) == pytest.approx(0.1)
    # deadline 91: (1, s=1) meets it by 1/91 and saves 10% over (0, s=1)
    # served (0, s=1) breaks it by 9/91; (1, s=1) is the rule's choice
    assert ref.choose_regret(t, margin, prices, rates, 0.0, S, 91.0,
                             2) == 0.0
    assert ref.choose_regret(t, margin, prices, rates, 0.0, S, 91.0,
                             0) == pytest.approx(9 / 91)
