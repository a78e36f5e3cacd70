"""The lower-precision control: the engine run one step down the
precision ladder from what the configuration states (float32, matrix
products at ``highest``).

- Matrix products go to ``high`` (three bf16 passes) or the given
  precision: ``repro.core.engine`` traces every fit, CV, validation and
  predict executable inside ``jax.default_matmul_precision("highest")``
  through its ``_full_precision`` wrapper, and the control swaps that
  wrapper before the first executable is built.
- The boosted trees have no matrix product; their other float32
  arithmetic goes to bfloat16: the features and residuals a tree is fit
  to, and the features, thresholds and leaves it predicts with, are
  rounded to bfloat16 (``lax.reduce_precision``, which the TPU compiler
  keeps, where it drops an f32 -> bf16 -> f32 convert pair).

Only the control runs call this (``bench/test_control.py`` and the
readings in PERF.md); benchmark runs never do.
"""
from __future__ import annotations

import functools


def _bf16(x):
    from jax import lax
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def lower_engine_precision(precision: str = "high") -> None:
    import jax
    from repro.core import engine
    from repro.core.models import gbm, optimistic

    def wrapper(fn):
        @functools.wraps(fn)
        def traced(*args):
            with jax.default_matmul_precision(precision):
                return fn(*args)
        return traced

    engine._full_precision = wrapper

    fit_tree = gbm._fit_tree

    def fit_tree_bf16(X, r, w, orders, depth):
        return fit_tree(_bf16(X), _bf16(r), w, orders, depth)

    gbm._fit_tree = fit_tree_bf16

    predict = gbm.gbm_predict

    def predict_bf16(params, X):
        return predict(params._replace(thr=_bf16(params.thr),
                                       leaf=_bf16(params.leaf)), _bf16(X))

    gbm.gbm_predict = optimistic.gbm_predict = predict_bf16

    kernel = engine._gbm_kernel_executable

    @functools.lru_cache(maxsize=2)
    def kernel_bf16(interpret: bool = False):
        run = kernel(interpret)

        def bf16_run(X, feat, thr, leaf, f0, y_scale):
            return run(_bf16(X), feat, _bf16(thr), _bf16(leaf), f0, y_scale)

        return jax.jit(bf16_run)

    engine._gbm_kernel_executable = kernel_bf16
