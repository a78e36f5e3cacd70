"""Fused prediction engine: the single compiled entry point for fit, model
selection (LOO-CV), and candidate-grid scoring.

DESIGN
======
The C3O compute hot-spot is evaluating the runtime predictor over every
candidate configuration (machine types x scale-outs x contexts) and
re-predicting during leave-one-out model selection (paper §IV-§VI).  The seed
implementation paid three avoidable costs on that path:

  1. retracing — ``jax.jit(spec.fit)`` / ``jax.jit(spec.predict)`` built a
     *fresh* jit wrapper (with an empty executable cache) on every
     ``FittedModel`` construction and every ``predict`` call;
  2. host round-trips — model selection pulled each model's fold predictions
     to the host before the next model was even dispatched, serializing the
     device pipeline and computing MAPE/residual statistics in numpy;
  3. per-row Python loops — the configurator scored candidates one context at
     a time, and machine-type selection re-built the scale-out grid per
     machine.

This module removes all three.  Everything routes through process-wide
executable caches:

Cache keys
----------
``fit_executable(spec)`` / ``predict_executable(spec)`` / ``cv_executable(spec)``
    LRU-cached per ``ModelSpec`` (frozen dataclass: equality is
    (name, make_aux, fit, predict) identity).  Each cached wrapper is a
    single ``jax.jit`` object, so XLA keeps **one executable per
    (ModelSpec, input shape/dtype)** — repeated fits/predicts on the same
    data shape never retrace, across any number of ``FittedModel`` or
    ``C3OPredictor`` instances.

``val_executable(spec)``
    Fused fit + masked holdout (MAPE, MAE) for contribution validation
    (``RuntimeDataStore``) and the evaluation replay plane's per-model
    error trajectories (``holdout_errors``): inputs are zero-padded to
    power-of-two row buckets, so evaluating against a store that grows row
    by row keeps hitting the same compiled executable.

``cv_executable_sharded(spec, n_devices)``
    LOO-CV with the fold axis partitioned over a one-dimensional "cv" mesh
    (``shard_map``; fold-weight buffers donated off-CPU).  ``cv_select``
    routes here when the host has multiple devices (or ``C3O_CV_SHARD=on``)
    and falls back to the numerically-reference single-device path
    otherwise.

``_gbm_kernel_executable()``
    The Pallas boosted-ensemble inference kernel
    (``repro.kernels.gbm_predict``), jitted once.  On a TPU backend every
    batched prediction of a GBM-selected predictor runs through it; on
    any other backend GBM predicts through the cached jnp executable.
    The choice follows the backend alone: there is no switch.

``JobRepo.predictor_for`` (see ``repro.core.hub``)
    fitted predictors cached per
    ``(machine_type, seed, datastore version, model list)`` — ``contribute``
    bumps the datastore version only when data is actually accepted, so hub
    traffic triggers a refit only when the data changed.

Fused multi-model CV
--------------------
``cv_select`` builds the fold-weight matrix ``W = 1 - onehot(folds)`` once,
dispatches every model's vmapped LOO refit+predict **and** its on-device
MAPE/residual reduction back-to-back (no host sync between models), then
performs a single host pull at the end.  The device pipeline therefore
overlaps model k's compute with model k+1's dispatch.

Grid-scored configuration
-------------------------
``score_grid`` evaluates a (scale-out x context-batch) grid in one predictor
call.  ``machine_grid_costs`` scores it for every machine type in one device
program (``grid_executable``, cached per tuple of the machines' ModelSpecs):
one host-to-device copy of the rows, one dispatch, one transfer back.
Predictors that expose no fitted model fall back to a dispatch per machine,
all before the first sync.  ``Configurator.choose_batch`` turns the
scored grid into per-context choices with vectorized numpy selection —
semantics identical, choice-for-choice, to the scalar ``choose_scaleout``.
"""
from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.models.api import ModelSpec

# --------------------------------------------------------------------------
# Executable caches (one jit wrapper per ModelSpec; XLA then caches one
# executable per input shape under each wrapper).
# --------------------------------------------------------------------------


def _full_precision(fn):
    """``fn`` traced with f32 matmuls at full precision.  A TPU's default
    runs them as one bf16 pass, which costs the normal equations and
    linear predictions of the model pool about three significant digits;
    every engine executable pins it so a chip answers like the CPU."""

    @functools.wraps(fn)
    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return traced


@functools.lru_cache(maxsize=None)
def fit_executable(spec: ModelSpec):
    """Cached jitted ``spec.fit``: (X, y, w, aux) -> params."""
    return jax.jit(_full_precision(spec.fit))


@functools.lru_cache(maxsize=None)
def predict_executable(spec: ModelSpec):
    """Cached jitted ``spec.predict``: (params, X, aux) -> yhat."""
    return jax.jit(_full_precision(spec.predict))


@functools.lru_cache(maxsize=None)
def val_executable(spec: ModelSpec):
    """Cached jitted fused fit + holdout-error for one model.

    (X_tr, y_tr, w, X_te, y_te, valid, aux) -> (MAPE, MAE) on the valid
    rows of the held-out split; the contribution validator and the
    evaluation replay plane dispatch every pool model through this (one
    executable per spec, shared process-wide) instead of constructing a
    throwaway CV predictor per call.  ``w`` and ``valid`` are 0/1 masks so
    callers can pad both splits to bucketed shapes — XLA then keeps one
    executable per bucket, not one per exact store size.
    """

    def _val(X_tr, y_tr, w, X_te, y_te, valid, aux):
        params = spec.fit(X_tr, y_tr, w, aux)
        pred = spec.predict(params, X_te, aux)
        pred = jnp.nan_to_num(pred, nan=1e12, posinf=1e12, neginf=-1e12)
        err = jnp.abs(pred - y_te)
        cnt = jnp.maximum(valid.sum(), 1.0)
        ape = err / jnp.maximum(jnp.abs(y_te), 1e-9)
        return (ape * valid).sum() / cnt, (err * valid).sum() / cnt

    return jax.jit(_full_precision(_val))


def _bucket(n: int, lo: int = 32) -> int:
    """Next power-of-two shape bucket >= n (stable executables while the
    collaborative store grows row by row)."""
    b = lo
    while b < n:
        b *= 2
    return b


def bucket_rows(n: int, lo: int = 32) -> int:
    """Public row-bucketing policy (``C3OPredictor(pad_rows=True)`` and the
    evaluation replay plane pad training batches to this)."""
    return _bucket(n, lo)


def holdout_errors(specs: Sequence[ModelSpec], X_tr: np.ndarray,
                   y_tr: np.ndarray, X_te: np.ndarray,
                   y_te: np.ndarray,
                   row_weight: Optional[np.ndarray] = None
                   ) -> Dict[str, Tuple[float, float]]:
    """Held-out (MAPE, MAE) per model, one fused dispatch per model and a
    single host sync at the end — the batched primitive behind both
    contribution validation and the evaluation replay plane's per-model
    error trajectories.

    Inputs are zero-padded to power-of-two row buckets with 0-weight /
    invalid masks (every pool model fits weighted, so w=0 rows are inert):
    repeated evaluations against a growing store hit the SAME compiled
    executable instead of retracing per store size.

    ``row_weight`` (fractional, [n_tr]) scales each training row's weight
    in the fit — the trust plane's reputation-derived weights; None keeps
    every real row at 1.0 (the exact historical path).
    """
    X_tr64 = np.asarray(X_tr, np.float64)
    n_tr, n_te = len(y_tr), len(y_te)
    b_tr, b_te = _bucket(n_tr), _bucket(n_te)
    Xp = np.zeros((b_tr, X_tr64.shape[1]), np.float64)
    Xp[:n_tr] = X_tr64
    yp = np.ones(b_tr, np.float32)
    yp[:n_tr] = y_tr
    w = np.zeros(b_tr, np.float32)
    w[:n_tr] = 1.0 if row_weight is None else row_weight
    Xq = np.zeros((b_te, Xp.shape[1]), np.float64)
    Xq[:n_te] = np.asarray(X_te, np.float64)
    yq = np.ones(b_te, np.float32)
    yq[:n_te] = y_te
    valid = np.zeros(b_te, np.float32)
    valid[:n_te] = 1.0
    Xtr, ytr = jnp.asarray(Xp, jnp.float32), jnp.asarray(yp)
    Xte, yte = jnp.asarray(Xq, jnp.float32), jnp.asarray(yq)
    wj, vj = jnp.asarray(w), jnp.asarray(valid)
    pending = [(spec.name, val_executable(spec)(Xtr, ytr, wj, Xte, yte, vj,
                                                spec.make_aux(Xp)))
               for spec in specs]
    return {name: (float(mape), float(mae))
            for name, (mape, mae) in pending}              # single sync pass


def holdout_mape(specs: Sequence[ModelSpec], X_tr: np.ndarray,
                 y_tr: np.ndarray, X_te: np.ndarray,
                 y_te: np.ndarray,
                 row_weight: Optional[np.ndarray] = None) -> float:
    """Best (lowest) held-out MAPE over the model pool (§III-C.b
    contribution validation consumes exactly this scalar)."""
    errs = holdout_errors(specs, X_tr, y_tr, X_te, y_te,
                          row_weight=row_weight)
    return min(mape for mape, _ in errs.values())


@functools.lru_cache(maxsize=None)
def cv_executable(spec: ModelSpec):
    """Cached jitted fused LOO-CV for one model.

    (X, y, W, fold_idx, valid, aux) -> (mape, resid_mu, resid_sigma); all
    folds are one vmapped weighted refit and the MAPE/residual reductions
    happen on-device, so selection needs a single scalar pull per model.
    ``valid`` is a 0/1 mask over the fold axis: callers may pad the fold
    list (and, via 0-weight rows in ``W``, the data rows) to bucketed
    shapes so a store growing row by row keeps hitting one executable.
    """

    def _cv(X, y, W, fold_idx, valid, aux):
        def one_fold(w, i):
            params = spec.fit(X, y, w, aux)
            return spec.predict(params, X[i][None, :], aux)[0]

        pred = jax.vmap(one_fold)(W, fold_idx)
        pred = jnp.nan_to_num(pred, nan=1e12, posinf=1e12, neginf=-1e12)
        y_f = y[fold_idx]
        ape = jnp.abs(pred - y_f) / jnp.maximum(jnp.abs(y_f), 1e-9)
        resid = pred - y_f
        cnt = jnp.maximum(valid.sum(), 1.0)
        mape = (ape * valid).sum() / cnt
        mu = (resid * valid).sum() / cnt
        sigma = jnp.sqrt(jnp.maximum(
            (resid * resid * valid).sum() / cnt - mu * mu, 0.0))
        return mape, mu, sigma

    return jax.jit(_full_precision(_cv))


# --------------------------------------------------------------------------
# Device-sharded CV (fold axis partitioned over the mesh)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _cv_mesh(n_devices: int):
    """One-dimensional "cv" mesh over the first ``n_devices`` devices."""
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n_devices]), ("cv",))


@functools.lru_cache(maxsize=None)
def cv_executable_sharded(spec: ModelSpec, n_devices: int):
    """Cached jitted LOO-CV for one model, folds sharded over the mesh.

    The (model pool x folds) work grid is partitioned across devices: fold
    shards run data-parallel under ``shard_map`` (each device refits its
    slice of the fold-weight matrix) while the pool dimension pipelines
    dispatches exactly like the single-device path.  Inputs are the padded
    fold arrays (F_pad divisible by the device count) plus a 0/1 validity
    mask; MAPE/residual moments reduce via ``psum`` so every device holds
    the replicated scalars and the host pulls once per model.  The
    fold-weight buffer is donated — at F_pad x n floats it is the dominant
    allocation and is dead after the refits.
    """
    from jax.sharding import PartitionSpec as P

    mesh = _cv_mesh(n_devices)

    def _shard(X, y, W, fold_idx, valid, aux):
        # local shards: W [F_pad/dev, n], fold_idx/valid [F_pad/dev]
        def one_fold(w, i):
            params = spec.fit(X, y, w, aux)
            return spec.predict(params, X[i][None, :], aux)[0]

        pred = jax.vmap(one_fold)(W, fold_idx)
        pred = jnp.nan_to_num(pred, nan=1e12, posinf=1e12, neginf=-1e12)
        y_f = y[fold_idx]
        ape = jnp.abs(pred - y_f) / jnp.maximum(jnp.abs(y_f), 1e-9)
        resid = pred - y_f
        cnt = jax.lax.psum((valid).sum(), "cv")
        ape_s = jax.lax.psum((ape * valid).sum(), "cv")
        r_s = jax.lax.psum((resid * valid).sum(), "cv")
        r2_s = jax.lax.psum((resid * resid * valid).sum(), "cv")
        mape = ape_s / cnt
        mu = r_s / cnt
        sigma = jnp.sqrt(jnp.maximum(r2_s / cnt - mu * mu, 0.0))
        return mape, mu, sigma

    fn = jax.shard_map(_shard, mesh=mesh,
                       in_specs=(P(), P(), P("cv"), P("cv"), P("cv"), P()),
                       out_specs=(P(), P(), P()), check_vma=False)
    # donating on CPU only triggers "donation not implemented" warnings
    donate = () if jax.default_backend() == "cpu" else (2,)
    return jax.jit(_full_precision(fn), donate_argnums=donate)


def _cv_shard_devices() -> int:
    """How many devices the sharded CV path should span (0 = stay on the
    single-device path).  ``C3O_CV_SHARD``: ``auto`` shards when the host
    has more than one device, ``on`` forces the shard_map path (even over a
    1-device mesh — the parity tests use this), ``off`` disables it."""
    mode = os.environ.get("C3O_CV_SHARD", "auto").lower()
    if mode == "off":
        return 0
    n = len(jax.devices())
    if mode == "on":
        return n
    return n if n > 1 else 0


def cache_stats() -> Dict[str, int]:
    """Executable-cache occupancy (introspection for tests/benchmarks)."""
    return {"fit": fit_executable.cache_info().currsize,
            "predict": predict_executable.cache_info().currsize,
            "cv": cv_executable.cache_info().currsize,
            "cv_sharded": cv_executable_sharded.cache_info().currsize,
            "val": val_executable.cache_info().currsize}


def cache_clear() -> None:
    """Drop all cached executables (tests emulate a fresh process with this:
    after a warm-start restore, a zero fit/cv occupancy proves no refit)."""
    fit_executable.cache_clear()
    predict_executable.cache_clear()
    cv_executable.cache_clear()
    cv_executable_sharded.cache_clear()
    val_executable.cache_clear()
    grid_executable.cache_clear()


# --------------------------------------------------------------------------
# Prediction dispatch (with Pallas GBM ensemble routing)
# --------------------------------------------------------------------------

def _gbm_kernel(interpret: bool = False):
    """The kernel path as a plain function of (X, feat, thr, leaf, f0,
    y_scale): jitted alone by ``_gbm_kernel_executable``, traced into the
    grid program by ``grid_executable``."""
    from repro.kernels.gbm_predict import gbm_predict as pallas_gbm

    def run(X, feat, thr, leaf, f0, y_scale):
        raw = pallas_gbm(X, feat, thr, leaf, f0, 1.0, interpret=interpret)
        # same normalization contract as models.gbm.gbm_predict: y_scale==0
        # is the log-target sentinel
        return jnp.where(y_scale == 0.0,
                         jnp.exp(jnp.clip(raw, -30.0, 30.0)),
                         raw * jnp.maximum(y_scale, 1e-12))

    return run


@functools.lru_cache(maxsize=2)
def _gbm_kernel_executable(interpret: bool = False):
    """The jitted kernel path; ``interpret=True`` only where a test asks
    for the interpreted kernel directly."""
    return jax.jit(_gbm_kernel(interpret))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def predict(spec: ModelSpec, params, X, aux) -> jnp.ndarray:
    """Batched prediction through the cached executable for ``spec``.

    On a TPU backend GBM predictors run the Pallas ensemble kernel;
    everything else uses the cached jnp executable.  The enqueue is the
    ``engine.dispatch`` span (a new row count lowers inside it).
    """
    with trace.span("engine.dispatch", model=spec.name, rows=len(X)):
        Xj = jnp.asarray(X, jnp.float32)
        from repro.core.models.gbm import GBM_SPEC
        # identity, not name: a maintainer model re-registered as "gbm"
        # has foreign params
        if spec is GBM_SPEC and _on_tpu():
            return _gbm_kernel_executable()(
                Xj, params.feat, params.thr, params.leaf, params.f0,
                params.y_scale)
        return predict_executable(spec)(params, Xj, aux)


@functools.lru_cache(maxsize=None)
def grid_executable(specs: Tuple[ModelSpec, ...], kernel: bool = False,
                    interpret: bool = False):
    """Cached jitted scoring of several fitted models on the same rows:
    (params tuple, aux tuple, X [R, d]) -> [len(specs), R].

    Each model computes what ``predict`` computes for it alone: its
    ``spec.predict`` at full precision, or with ``kernel`` (a TPU
    backend) the GBM kernel for ``GBM_SPEC``.  The kernel runs under the
    name scope ``run``, so its custom calls are named ``run.<n>`` in a
    device trace, as the standalone executable's are.  Keyed on the spec
    tuple, so XLA keeps one executable per (specs, row count)."""
    from repro.core.models.gbm import GBM_SPEC
    run = _gbm_kernel(interpret) if kernel else None

    def grid(params, aux, X):
        out = []
        for spec, p, a in zip(specs, params, aux):
            if run is not None and spec is GBM_SPEC:
                with jax.named_scope("run"):
                    out.append(run(X, p.feat, p.thr, p.leaf, p.f0,
                                   p.y_scale))
            else:
                out.append(spec.predict(p, X, a))
        return jnp.stack(out)

    return jax.jit(_full_precision(grid))


def predict_grid(models: Sequence, X) -> jax.Array:
    """Every fitted model's predictions on the same rows, [len(models),
    len(X)], in one device program: one host-to-device copy of ``X`` and
    one enqueue, the ``engine.dispatch`` span (``model=`` joins the
    models' names).  ``models`` expose ``spec``, ``params`` and ``aux``
    (``FittedModel``)."""
    specs = tuple(m.spec for m in models)
    with trace.span("engine.dispatch",
                    model="+".join(s.name for s in specs), rows=len(X)):
        Xj = jnp.asarray(X, jnp.float32)
        return grid_executable(specs, _on_tpu())(
            tuple(m.params for m in models), tuple(m.aux for m in models),
            Xj)


def to_host(result) -> np.ndarray:
    """A device result as a float64 host array: the wait on the device,
    timed as the ``engine.sync`` span."""
    with trace.span("engine.sync"):
        return np.asarray(result, np.float64)


# --------------------------------------------------------------------------
# Fused multi-model cross-validation / selection
# --------------------------------------------------------------------------

def cv_select(specs: Sequence[ModelSpec], X: np.ndarray, y: np.ndarray,
              folds: np.ndarray, *, sharded: Optional[bool] = None,
              row_weight: Optional[np.ndarray] = None
              ) -> Tuple[str, Dict[str, float], float, float]:
    """LOO-CV every model in one pipelined batch; returns
    (selected name, {name: mape}, resid mu, resid sigma of the selected).

    All models are dispatched before any host synchronization: the shared
    fold-weight matrix lives on device once, and each model's executable
    reduces MAPE/residual statistics on-device, so the only host traffic is
    a few scalars per model at the end.

    ``row_weight`` (0/1 per row of ``X``) marks padding rows as inert:
    every fold's weight vector is multiplied by it, so callers (the
    replay plane's ``C3OPredictor(pad_rows=True)``) can zero-pad the data
    to power-of-two row buckets and the fold list to power-of-two fold
    buckets (masked via ``valid``) — selection against a store growing row
    by row then reuses one compiled executable per bucket instead of
    retracing per exact store size.  Folds must index real rows.

    With more than one device (or ``C3O_CV_SHARD=on``) the fold axis is
    partitioned over a "cv" mesh via shard_map — see
    ``cv_executable_sharded`` — with fold-weight buffers donated.  The
    single-device path is the numerical reference; the sharded path matches
    it to float tolerance (same selected model, allclose mape/mu/sigma).
    ``sharded`` overrides the environment policy when not None.
    """
    X64 = np.asarray(X, np.float64)
    Xj = jnp.asarray(X64, jnp.float32)
    yj = jnp.asarray(y, jnp.float32)
    folds = np.asarray(folds)
    rw = (None if row_weight is None
          else jnp.asarray(np.asarray(row_weight, np.float32)))
    n_dev = _cv_shard_devices() if sharded is None else \
        (len(jax.devices()) if sharded else 0)
    F = len(folds)
    # bucket the fold axis whenever rows are padded (the caller is asking
    # for shape stability); the sharded path additionally pads to a
    # device-count multiple
    F_pad = _bucket(F, 8) if rw is not None else F
    if n_dev:
        F_pad += (-F_pad) % n_dev
    folds_p = np.concatenate([folds, np.zeros(F_pad - F, folds.dtype)])
    valid = jnp.asarray(np.concatenate([np.ones(F, np.float32),
                                        np.zeros(F_pad - F, np.float32)]))
    fold_j = jnp.asarray(folds_p)

    def weights():
        W = 1.0 - jax.nn.one_hot(fold_j, len(yj))          # [F_pad, n]
        return W if rw is None else W * rw[None, :]

    pending = []
    if n_dev:
        # off-CPU the executable donates its fold-weight buffer, so each
        # spec needs a fresh [F_pad, n] matrix; on CPU donation is disabled
        # and one shared W serves every spec
        donating = jax.default_backend() != "cpu"
        W_shared = None if donating else weights()
        for spec in specs:
            aux = spec.make_aux(X64)
            W = weights() if donating else W_shared
            pending.append((spec.name, cv_executable_sharded(spec, n_dev)(
                Xj, yj, W, fold_j, valid, aux)))
    else:
        W = weights()                                      # [F_pad, n] shared
        for spec in specs:
            aux = spec.make_aux(X64)
            pending.append((spec.name,
                            cv_executable(spec)(Xj, yj, W, fold_j, valid,
                                                aux)))
    mapes: Dict[str, float] = {}
    stats: Dict[str, Tuple[float, float]] = {}
    for name, (mape, mu, sigma) in pending:                 # single sync pass
        mapes[name] = float(mape)
        stats[name] = (float(mu), float(sigma))
    best = min(mapes, key=mapes.get)        # ties: first in model order
    mu, sigma = stats[best]
    return best, mapes, mu, sigma + 1e-12


# --------------------------------------------------------------------------
# Grid-scored configuration
# --------------------------------------------------------------------------

def grid_rows(scaleouts: Sequence[int], contexts: np.ndarray) -> np.ndarray:
    """[S*C, 1+k] feature rows for the (scale-out x context) grid,
    scale-out-major (row s*C + c pairs scaleouts[s] with contexts[c])."""
    contexts = np.atleast_2d(np.asarray(contexts, np.float64))
    S = np.asarray(scaleouts, np.float64)
    C, k = contexts.shape
    rows = np.empty((len(S), C, k + 1), np.float64)
    rows[..., 0] = S[:, None]
    rows[..., 1:] = contexts[None, :, :]
    return rows.reshape(-1, k + 1)


def _predict_rows(predictor, rows: np.ndarray):
    """Prefer the device-level (non-syncing) predict when available so
    multi-predictor sweeps pipeline their dispatches."""
    dev = getattr(predictor, "predict_device", None)
    return dev(rows) if dev is not None else predictor.predict(rows)


def score_grid(predictor, scaleouts: Sequence[int], contexts: np.ndarray
               ) -> Tuple[np.ndarray, float, float]:
    """Runtime predictions for the whole (scale-out x context) grid in ONE
    predictor call: -> (t [C, S], mu, sigma)."""
    contexts = np.atleast_2d(np.asarray(contexts, np.float64))
    rows = grid_rows(scaleouts, contexts)
    t, mu, sigma = predictor.predict_with_error(rows)
    t = np.asarray(t, np.float64).reshape(len(scaleouts), len(contexts)).T
    return t, mu, sigma


def machine_grid_runtimes(predictors: Dict[str, object],
                          scaleouts: Sequence[int],
                          contexts: np.ndarray
                          ) -> Tuple[List[str], np.ndarray]:
    """Runtime predictions for the (machine x scale-out x context) grid.

    When every predictor exposes its fitted model (``fitted``, as a
    fitted ``C3OPredictor`` does), the whole grid is one device program
    (``predict_grid``): the rows are copied once, every machine's model
    scores them, and one transfer brings the [M, S*C] result back.
    Otherwise (predictors with only ``predict``) each machine's grid is
    dispatched on its own, all before the first host sync.  Returns
    (machine names, t [M, C, S]) with runtimes clamped at >= 0 (a
    negative runtime would make a negative cost win every
    cheapest-choice selection downstream)."""
    contexts = np.atleast_2d(np.asarray(contexts, np.float64))
    rows = grid_rows(scaleouts, contexts)
    names = list(predictors)
    models = [getattr(p, "fitted", None) for p in predictors.values()]
    if all(m is not None for m in models):
        t = to_host(predict_grid(models, rows))
    else:
        pending = [_predict_rows(p, rows) for p in predictors.values()]
        t = np.stack([to_host(p) for p in pending])
    t = t.reshape(len(names), len(scaleouts), len(contexts))
    return names, np.maximum(t.transpose(0, 2, 1), 0.0)


def machine_grid_costs(predictors: Dict[str, object],
                       prices: Dict[str, float],
                       scaleouts: Sequence[int],
                       contexts: np.ndarray
                       ) -> Tuple[List[str], np.ndarray, np.ndarray]:
    """Score the full (machine x scale-out x context) grid through
    ``machine_grid_runtimes``; returns (machine names, t [M, C, S],
    cost [M, C, S])."""
    names, t = machine_grid_runtimes(predictors, scaleouts, contexts)
    S = np.asarray(scaleouts, np.float64)
    cost = np.stack([prices[m] for m in names])[:, None, None] \
        * (t / 3600.0) * S[None, None, :]
    return names, t, cost


def placement_grid_costs(predictors: Dict[str, object], book,
                         scaleouts: Sequence[int], contexts: np.ndarray,
                         zones=None, options=None):
    """Score the (machine x placement x context x scale-out) grid.

    The placement axis is pure broadcasting over the SAME fused runtime
    dispatch as ``machine_grid_costs`` — predicted runtime does not
    depend on where the cluster is bought, so a Z-zone book adds a numpy
    axis, not a prediction loop.  ``book`` is a
    ``repro.core.market.PriceBook``; returns

        (names, placements, t [M, C, S],
         et [M, P, C, S], naive [M, P, C, S], adjusted [M, P, C, S])

    where ``et`` is the interruption-adjusted expected completion time,
    ``naive`` the listed-price cost (price x t x nodes) and ``adjusted``
    the interruption-adjusted expected cost (price x E[t] x nodes)."""
    from repro.core.market import expected_completion_time_s
    names, t = machine_grid_runtimes(predictors, scaleouts, contexts)
    placements = book.resolve(zones, options)
    prices = book.price_matrix(names, placements)           # [M, P]
    rates = book.rates(placements)                          # [P]
    S = np.asarray(scaleouts, np.float64)
    et = expected_completion_time_s(t[:, None, :, :],
                                    rates[None, :, None, None],
                                    book.restart_overhead_s)
    # same op order as machine_grid_costs so a flat (single-placement,
    # rate-0) book reproduces the legacy cost bit-for-bit
    p4 = prices[:, :, None, None]
    naive = p4 * (t[:, None, :, :] / 3600.0) * S[None, None, None, :]
    adjusted = p4 * (et / 3600.0) * S[None, None, None, :]
    return names, placements, t, et, naive, adjusted
