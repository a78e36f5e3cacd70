"""Roofline methodology validation: the analytic cost model vs XLA's
cost_analysis on configurations where cost_analysis is trustworthy
(no scans), plus a regression test documenting the scan undercount that
motivates the methodology (EXPERIMENTS.md §Dry-run)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.configs.base import ShapeConfig
from repro.launch import specs as SP
from repro.launch.analytic import analytic_cost
from repro.train import train_step as TS


def _hlo_flops(cfg, shape):
    batch = {"tokens": jax.ShapeDtypeStruct(
        (shape.global_batch, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct(
        (shape.global_batch, shape.seq_len), jnp.int32)}
    state = SP.abstract_state(cfg)
    comp = jax.jit(TS.make_train_step(cfg)).lower(state, batch).compile()
    return comp.cost_analysis().get("flops", 0.0)


def test_scan_undercount_regression():
    """cost_analysis counts a scan body once — the bug class that makes the
    naive roofline wrong and the analytic model necessary."""
    def make(K):
        def f(x):
            return jax.lax.scan(lambda c, _: (c @ c, None), x, None,
                                length=K)[0]
        return f
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    f1 = jax.jit(make(1)).lower(x).compile().cost_analysis()["flops"]
    f8 = jax.jit(make(8)).lower(x).compile().cost_analysis()["flops"]
    # trip count ignored (only loop-bookkeeping flops differ)
    assert f8 < f1 * 1.01


@pytest.mark.parametrize("remat", ["none"])
def test_analytic_flops_close_to_hlo_unrolled(remat):
    cfg = smoke_config("deepseek-7b", scan_layers=False, n_layers=4,
                       remat=remat, attention_impl="reference",
                       grad_accum=1)
    shape = ShapeConfig("t", 64, 4, "train")
    hlo = _hlo_flops(cfg, shape)
    ana = analytic_cost(cfg, shape, {"data": 1, "model": 1}).flops
    assert 0.8 < ana / hlo < 1.25, f"analytic {ana:.3e} vs hlo {hlo:.3e}"


def test_analytic_flops_moe_unrolled():
    cfg = smoke_config("olmoe-1b-7b", scan_layers=False, n_layers=2,
                       remat="none", attention_impl="reference",
                       grad_accum=1, moe_impl="dense")
    shape = ShapeConfig("t", 32, 4, "train")
    hlo = _hlo_flops(cfg, shape)
    ana = analytic_cost(cfg, shape, {"data": 1, "model": 1}).flops
    # dense one-hot dispatch adds dispatch-einsum flops the analytic EP
    # model does not charge; require same order of magnitude + lower bound
    assert ana <= hlo * 1.3
    assert ana > hlo * 0.2


def test_analytic_scales_linearly_in_depth_and_tokens():
    cfg = smoke_config("deepseek-7b")
    s1 = ShapeConfig("a", 64, 4, "train")
    s2 = ShapeConfig("b", 64, 8, "train")
    mesh = {"data": 1, "model": 1}
    c1 = analytic_cost(cfg, s1, mesh).flops
    c2 = analytic_cost(cfg, s2, mesh).flops
    assert abs(c2 / c1 - 2.0) < 0.05
    cfg2 = dataclasses.replace(cfg, n_layers=cfg.n_layers * 2)
    c3 = analytic_cost(cfg2, s1, mesh).flops
    assert c3 > c1 * 1.5


def test_collective_model_tp_vs_dp():
    """Pure TP has all-reduces, no FSDP gathers; pure DP the reverse."""
    cfg = smoke_config("deepseek-7b")
    shape = ShapeConfig("t", 128, 16, "train")
    tp = analytic_cost(cfg, shape, {"data": 1, "model": 16})
    dp = analytic_cost(cfg, shape, {"data": 16, "model": 1})
    assert tp.coll.get("all-reduce", 0) > 0
    assert tp.coll.get("all-gather", 0) == 0
    assert dp.coll.get("all-gather", 0) > 0
