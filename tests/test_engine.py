"""Prediction-engine contracts: no retracing across repeated fits/predicts,
batched choose_batch parity with scalar choose_scaleout, version-keyed hub
fit caching, and Pallas GBM-kernel routing parity."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.configurator import Configurator
from repro.core.models.api import FittedModel, ModelSpec, get_model
from repro.core.predictor import C3OPredictor
from repro.workloads import spark_emul as W

SCALEOUTS = [2, 3, 4, 6, 8, 12, 16]
PRICES = {m.name: m.price for m in W.MACHINES.values()}


class _FakePredictor:
    """Deterministic predictor: t(s) = a/s + b*s + c, known error stats."""

    def __init__(self, a=1000.0, b=5.0, c=50.0, mu=0.0, sigma=10.0):
        self.a, self.b, self.c = a, b, c
        self.mu, self.sigma = mu, sigma

    def predict(self, X):
        s = np.asarray(X)[:, 0]
        return self.a / s + self.b * s + self.c

    def predict_with_error(self, X):
        return self.predict(X), self.mu, self.sigma


# --------------------------------------------------------------------------
# compilation-count regression
# --------------------------------------------------------------------------

def _probe_spec(calls):
    """A ModelSpec whose fit/predict bump a Python counter when traced —
    a retrace is visible as a second increment for identical shapes."""

    def fit(X, y, w, aux):
        calls["fit"] += 1
        return {"m": (w * y).sum() / jnp.maximum(w.sum(), 1e-9)}

    def predict(params, X, aux):
        calls["predict"] += 1
        return jnp.full((X.shape[0],), params["m"])

    return ModelSpec("_trace_probe", lambda X: {}, fit, predict)


def test_no_retrace_across_repeated_fitted_models():
    calls = {"fit": 0, "predict": 0}
    spec = _probe_spec(calls)
    rng = np.random.default_rng(0)
    X = rng.uniform(1, 10, (12, 2))
    y = rng.uniform(50, 100, 12)
    for _ in range(4):
        fm = FittedModel(spec, X, y)
        fm.predict(X[:5])
    # one trace per (spec, shape), no matter how many instances/calls
    assert calls["fit"] == 1
    assert calls["predict"] == 1
    fm.predict(X[:7])                    # new shape -> exactly one more trace
    assert calls["predict"] == 2


def test_no_retrace_across_repeated_cv_selection():
    calls = {"fit": 0, "predict": 0}
    spec = _probe_spec(calls)
    rng = np.random.default_rng(1)
    X = rng.uniform(1, 10, (10, 2))
    y = rng.uniform(50, 100, 10)
    folds = np.arange(10)
    for seed in range(3):
        engine.cv_select([spec], X, y + seed, folds)
    assert calls["fit"] == 1             # vmapped LOO traces the body once
    assert calls["predict"] == 1


# --------------------------------------------------------------------------
# choose_batch parity with scalar choose_scaleout
# --------------------------------------------------------------------------

def _assert_same_choice(a, b):
    assert a.scale_out == b.scale_out
    assert a.machine_type == b.machine_type
    assert a.bottleneck == b.bottleneck
    np.testing.assert_allclose(a.predicted_runtime_s, b.predicted_runtime_s)
    np.testing.assert_allclose(a.runtime_bound_s, b.runtime_bound_s)
    np.testing.assert_allclose(a.cost_usd, b.cost_usd)


@pytest.mark.parametrize("bottleneck", [None, lambda ctx, s: s <= 4])
def test_choose_batch_matches_scalar_fake_predictor(bottleneck):
    conf = Configurator(_FakePredictor(sigma=20.0), "m5.xlarge", PRICES,
                        SCALEOUTS, confidence=0.9, bottleneck_fn=bottleneck)
    rng = np.random.default_rng(2)
    contexts = rng.uniform(10, 20, (16, 1))
    for t_max in (None, 250.0, 400.0, 1e9):
        batched = conf.choose_batch(contexts, t_max=t_max)
        assert len(batched) == len(contexts)
        for ctx, ch in zip(contexts, batched):
            _assert_same_choice(ch, conf.choose_scaleout(ctx, t_max=t_max))


def test_choose_batch_matches_scalar_real_predictor():
    d = W.generate_job_data("grep").filter_machine("m5.xlarge")
    pred = C3OPredictor(max_cv_folds=15).fit(d.X, d.y)
    conf = Configurator(pred, "m5.xlarge", PRICES, SCALEOUTS)
    rng = np.random.default_rng(3)
    contexts = np.stack([rng.uniform(10, 20, 12),
                         rng.choice([.002, .02, .08], 12)], axis=1)
    t_maxes = rng.uniform(150, 600, 12)
    batched = conf.choose_batch(contexts, t_max=t_maxes)
    for ctx, tm, ch in zip(contexts, t_maxes, batched):
        _assert_same_choice(ch, conf.choose_scaleout(ctx, t_max=float(tm)))
    # no-deadline menu path too
    for ctx, ch in zip(contexts[:4], conf.choose_batch(contexts[:4])):
        _assert_same_choice(ch, conf.choose_scaleout(ctx))


# --------------------------------------------------------------------------
# hub fit cache / datastore versioning
# --------------------------------------------------------------------------

def test_predictor_for_refits_only_on_accepted_contribution():
    from repro.core.datastore import RuntimeDataStore
    from repro.core.features import RuntimeData
    from repro.core.hub import JobRepo

    data = W.generate_job_data("grep")
    store = RuntimeDataStore(data, seed=0)
    repo = JobRepo("grep", "grep", data.schema, store)
    p1 = repo.predictor_for("m5.xlarge")
    assert repo.predictor_for("m5.xlarge") is p1          # cache hit
    assert repo.predictor_for("m5.xlarge", seed=1) is not p1

    d = data.filter_machine("m5.xlarge")
    good = RuntimeData(data.schema, np.asarray(["m5.xlarge"] * 3),
                       d.X[:3], d.y[:3] * 1.01)
    report = repo.contribute(good)
    assert report.accepted
    assert store.version == 1
    assert repo.predictor_for("m5.xlarge") is not p1      # data changed


# --------------------------------------------------------------------------
# Pallas GBM ensemble routing
# --------------------------------------------------------------------------

def test_gbm_kernel_routing_matches_jnp_path(monkeypatch):
    """On a TPU backend ``engine.predict`` sends GBM predictions through
    the Pallas kernel.  The test steers that choice by standing in for
    the backend check and runs the kernel in interpret mode."""
    rng = np.random.default_rng(4)
    X = rng.uniform(1, 10, (24, 2))
    y = 20 + 5 * X[:, 1] / X[:, 0] + rng.normal(0, 0.5, 24)
    fm = FittedModel(get_model("gbm"), X, y)
    Xq = rng.uniform(1, 10, (40, 2))
    ref = fm.predict(Xq)                        # CPU backend: jnp path
    kernel = engine._gbm_kernel_executable
    calls = []

    def interpreted():
        calls.append(1)
        return kernel(interpret=True)

    monkeypatch.setattr(engine, "_on_tpu", lambda: True)
    monkeypatch.setattr(engine, "_gbm_kernel_executable", interpreted)
    out = fm.predict(Xq)
    assert calls, "GBM prediction on a TPU backend must take the kernel"
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
