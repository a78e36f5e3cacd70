"""Start-up contracts of the device entry points, checked on the CPU.

``chip_smoke.py`` must refuse to run without a TPU (and without the repo
beside it), printing no result line; the compile-cache helper must leave
``JAX_COMPILATION_CACHE_DIR`` in charge when it is set and otherwise use
the fixed, git-ignored in-checkout directory; importing the serving
modules must initialise no JAX backend, so a load generator can run as a
second process beside an edge that holds the chip.  The smoke's GBM split
check must pass the engine's own fit and catch a split that is not the
best, and its reference comparison must hold each family of answers to its
own limit.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"), **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    out = _run([str(ROOT / "chip_smoke.py")], ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    full = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=dict(full, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    script = ("import jax, jax.numpy as jnp\n"
              "from repro.compile_cache import enable_compile_cache\n"
              "print(enable_compile_cache())\n"
              "jax.config.update("
              "'jax_persistent_cache_min_compile_time_secs', 0)\n"
              "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    out = _run(["-c", script], tmp_path,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())


def test_compile_cache_defaults_to_the_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_importing_the_serving_path_initialises_no_backend():
    script = ("import repro.core, repro.api, repro.compile_cache\n"
              "import repro.serve.edge, repro.serve.loadgen\n"
              "from jax._src import xla_bridge\n"
              "print(len(xla_bridge._backends))\n")
    out = _run(["-c", script], ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "0"


def _chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


def test_reference_compare_holds_each_family_to_its_limit():
    """The golden against itself passes; an Ernest number 2e-3 off, a GBM
    prediction 2% off, or a changed choice that is not fragile fails; a
    GBM prediction 0.5% off passes."""
    cs = _chip_smoke()
    golden = json.loads(cs.GOLDEN.read_text())
    ans = golden["answers"]
    log = cs.Log("test")
    assert cs.compare(ans, golden, log) == []
    ernest = next(k for k, f in ans["fits"].items()
                  if f["selected"] == "ernest")
    gbm = next(k for k, f in ans["fits"].items() if f["selected"] == "gbm")

    def moved(edit):
        alt = copy.deepcopy(ans)
        edit(alt)
        return cs.compare(alt, golden, log)

    def scale(key, factor):
        return lambda a: a["predict_1row"].update(
            {key: a["predict_1row"][key] * factor})

    assert moved(scale(ernest, 1 + 2e-3))
    assert moved(scale(gbm, 1.02))
    assert moved(scale(gbm, 1.005)) == []
    fragile = set(golden["fragile"])
    i = next(i for i in range(len(ans["burst"]))
             if f"burst {i} choose" not in fragile)
    bad = moved(lambda a: a["burst"][i].__setitem__(1, -1))
    assert bad and bad[0].startswith(f"burst {i} choose")


def test_split_check_tells_the_best_split_from_a_worse_one():
    """``chip_smoke``'s float64 replay of a GBM fit: the engine's own fit
    stays under the limit; moving one root threshold to another cut of
    the same feature shows up as a shortfall far above it."""
    chip_smoke = _chip_smoke()
    from repro.core import engine
    from repro.core.models.gbm import GBM_SPEC
    from repro.core.predictor import C3OPredictor
    from repro.workloads import spark_emul as W

    d = W.generate_job_data("kmeans", 0).machine_view("c5.xlarge")
    X, y, _, w = C3OPredictor(pad_rows=True).cv_inputs(d.X, d.y)
    params = engine.fit_executable(GBM_SPEC)(
        np.float32(X), np.float32(y), np.float32(w), GBM_SPEC.make_aux(X))
    yn = chip_smoke.log_targets(y)
    assert chip_smoke.split_shortfall(params, X, yn, w) \
        <= chip_smoke.SPLIT_SHORTFALL
    f = int(params.feat[0, 0])
    cuts = np.unique(X[w > 0, f])
    t = float(params.thr[0, 0])
    other = 0.5 * (cuts[0] + cuts[1]) if t > cuts[1] else \
        0.5 * (cuts[-2] + cuts[-1])
    moved = params._replace(thr=params.thr.at[0, 0].set(other))
    assert chip_smoke.split_shortfall(moved, X, yn, w) \
        > 10 * chip_smoke.SPLIT_SHORTFALL
