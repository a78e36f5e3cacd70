"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept
(unaligned blocks, layouts Mosaic cannot tile), so these tests compile the
engine's device programs against a described ``v5e:2x2`` topology:

- the Pallas GBM kernel, as the engine serves it, at 24 to 4096 rows;
- the machine grid program (``grid_executable``) with GBM-selected
  machines, whose kernel calls keep the ``run.<n>`` names a device trace
  finds the kernel by;
- ``cv_executable`` for every pool model on one chip at a real store size;
- ``cv_executable_sharded`` over the four described chips, with its
  fold-weight buffer donated as on a TPU host.

Nothing runs, so nothing here says anything about results or times (one
CPU case runs the grid program's kernel in interpret mode).  The
topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: a program
compiled for a chip that is not attached cannot be read back from it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.models.api import get_model
from repro.workloads import spark_emul as W

POOL = ("ernest", "gbm", "bom", "ogb")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        engine.cv_executable_sharded.cache_clear()
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def store():
    """A hub store after a dozen contributions: pagerank's paper dataset
    plus twelve users' rows (1002 rows, padded to the 1024-row bucket)
    and the predictor's 30 LOO folds (padded to 32)."""
    from repro.core.predictor import C3OPredictor
    d = W.generate_job_data("pagerank", 0)
    for user in range(1, 13):
        d = d.append(W.generate_user_data("pagerank", user, 0))
    return C3OPredictor(pad_rows=True).cv_inputs(d.X, d.y)


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("rows", [24, 256, 1792, 4096])
def test_gbm_kernel_compiles_for_v5e(one_chip, rows):
    T, n_int = 200, 7                   # the pool GBM: 200 trees, depth 3
    args = _abstract((np.zeros((rows, 4), np.float32),
                      np.zeros((T, n_int), np.int32),
                      np.zeros((T, n_int), np.float32),
                      np.zeros((T, n_int + 1), np.float32),
                      np.float32(0), np.float32(0)), one_chip)
    compiled = engine._gbm_kernel_executable().lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model", POOL)
def test_cv_executable_compiles_for_v5e(one_chip, store, model):
    X, y, folds, w = store
    spec = get_model(model)
    F = engine.bucket_rows(len(folds), 8)
    args = _abstract((X.astype(np.float32), y.astype(np.float32),
                      np.zeros((F, len(y)), np.float32),
                      np.zeros(F, folds.dtype), np.zeros(F, np.float32),
                      spec.make_aux(X)), one_chip)
    engine.cv_executable(spec).lower(*args).compile()


@pytest.mark.parametrize("model", POOL)
def test_cv_executable_sharded_compiles_for_v5e_2x2(topo, store, model,
                                                   monkeypatch):
    X, y, folds, w = store
    mesh = Mesh(np.asarray(topo.devices), ("cv",))
    monkeypatch.setattr(engine, "_cv_mesh", lambda n: mesh)
    # build the executable as a TPU host does: fold weights donated
    monkeypatch.setattr(engine.jax, "default_backend", lambda: "tpu")
    engine.cv_executable_sharded.cache_clear()
    spec = get_model(model)
    F = engine.bucket_rows(len(folds), 8)
    rep, fold = NamedSharding(mesh, P()), NamedSharding(mesh, P("cv"))
    args = (*_abstract((X.astype(np.float32), y.astype(np.float32)), rep),
            *_abstract((np.zeros((F, len(y)), np.float32),
                        np.zeros(F, folds.dtype), np.zeros(F, np.float32)),
                       fold),
            _abstract(spec.make_aux(X), rep))
    lowered = engine.cv_executable_sharded(spec, 4).lower(*args)
    assert lowered.args_info[0][2].donated
    assert "all-reduce" in lowered.compile().as_text()


#: a compiled Pallas call: its HLO instruction name
_KERNEL_CALL = re.compile(r"%?([\w.\-]+) = .*custom_call_target="
                          r"\"tpu_custom_call\"")


def _kernel_names(compiled) -> list:
    return [m.group(1) for m in map(_KERNEL_CALL.search,
                                    compiled.as_text().splitlines()) if m]


@pytest.mark.parametrize("models", [("gbm", "gbm", "ernest"),
                                    ("ernest", "gbm", "bom", "ogb")])
def test_grid_program_compiles_for_v5e_with_run_kernel_names(
        one_chip, store, models):
    """The trace reduction finds the GBM kernel by instruction names
    that start with ``run.``: inside the grid program as alone."""
    X, y, folds, w = store
    specs = tuple(get_model(m) for m in models)
    aux = tuple(spec.make_aux(X) for spec in specs)
    shapes = tuple(jax.eval_shape(spec.fit, X.astype(np.float32),
                                  y.astype(np.float32),
                                  w.astype(np.float32), a)
                   for spec, a in zip(specs, aux))
    params = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    rows = np.zeros((len(W.MACHINES) * 7 * 4, X.shape[1]), np.float32)
    args = _abstract((params, aux, rows), one_chip)
    names = _kernel_names(engine.grid_executable(specs, kernel=True)
                          .lower(*args).compile())
    assert len(names) == models.count("gbm")
    assert all(n.startswith("run.") for n in names), names
    alone = _abstract((rows, *(params[models.index("gbm")][1:4]),
                       np.float32(0), np.float32(0)), one_chip)
    names = _kernel_names(engine._gbm_kernel_executable()
                          .lower(*alone).compile())
    assert names == ["run.1"]


def test_grid_program_runs_the_interpreted_kernel_like_the_executable():
    """CPU: the kernel traced into the grid program (interpret mode)
    answers as the standalone kernel executable, next to a jnp model."""
    from repro.core.models.api import FittedModel
    from repro.core.models.gbm import GBMParams
    rng = np.random.default_rng(3)
    d = W.generate_job_data("grep").filter_machine("m5.xlarge")
    ernest = FittedModel(get_model("ernest"), d.X, d.y)
    T, n_int, k = 32, 7, d.X.shape[1]

    def gbm(y_scale):
        return GBMParams(np.float32(rng.normal()),
                         rng.integers(0, k, (T, n_int)).astype(np.int32),
                         rng.uniform(0, 20, (T, n_int)).astype(np.float32),
                         rng.normal(0, 0.1, (T, n_int + 1))
                         .astype(np.float32), np.float32(y_scale))

    spec = get_model("gbm")
    params = (gbm(0.0), ernest.params, gbm(3.0))
    X = engine.grid_rows([2, 4, 8], d.X[:5, 1:]).astype(np.float32)
    got = engine.grid_executable((spec, ernest.spec, spec), kernel=True,
                                 interpret=True)(
        params, ({}, ernest.aux, {}), X)
    kernel = engine._gbm_kernel_executable(interpret=True)
    for i in (0, 2):
        np.testing.assert_array_equal(got[i], kernel(X, *params[i][1:4],
                                                     params[i].f0,
                                                     params[i].y_scale))
    np.testing.assert_array_equal(got[1], ernest.predict_device(X))
