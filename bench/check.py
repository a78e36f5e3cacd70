"""What decides ``correct``: every answer the window received, held
against the float64 reference (``bench/ref.py``) fitted on the same
rows.

The hub names the model it serves for a (job, machine) in each predict
answer, and the harness asks it for every pair once the window has
closed (``selected``).  Each answer is held against the reference's fit
of the model the hub served it with, and ``select_gap`` judges whether
that model may be served at all.  Numbers (``.plain``: answers of an
Ernest- or BOM-served machine, ``.tree``: GBM or OGB):

``predict_gap.<f>``  |served runtime - reference runtime| / reference,
                     per single-row predict
``choose_gap.<f>``   the same for the chosen machine and scale-out of a
                     choose answer
``calib_gap.<f>``    |served mu or sigma - reference's| / reference
                     sigma, per predict
``select_gap``       reference CV MAPE of a served model minus the
                     pool's least, per (job, machine)
``choose_regret``    the least relative change to the reference grid
                     under which the choose rule picks the served choice
                     (``ref.choose_regret``), per choose
``failed``           answers that never came or were not ok

Each number is the largest over the run's answers.  The trees' runtime
gaps are also read at their 90th percentile (``predict_gap.tree.p90``,
``choose_gap.tree.p90``): the greedy split search turns any rounding
difference into another, equally good ensemble that lies up to a few
percent off at single points, so their largest gap separates nothing
(PERF.md).  The configuration's ``limits`` name the numbers compared;
the others are printed beside them.
"""
from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass
from functools import cached_property
from typing import Dict, List, Tuple

import numpy as np

from bench import data as D
from bench import ref
from bench.metrics import percentile

#: worker processes for the reference fits (numpy, one thread each)
WORKERS = max(1, min(8, (os.cpu_count() or 2) - 1))
THREAD_VARS = ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS")
#: numbers also read at a percentile over the answers
PERCENTILE = {"predict_gap.tree": 90, "choose_gap.tree": 90}


@dataclass
class Store:
    """The reference's copy of one job's shared rows, in store order."""
    machine: np.ndarray
    X: np.ndarray
    y: np.ndarray

    @classmethod
    def of(cls, d) -> "Store":
        """From a ``RuntimeData`` (the seed's rows)."""
        return cls(np.asarray(d.machine_type).astype(str),
                   np.asarray(d.X, np.float64), np.asarray(d.y, np.float64))

    @cached_property
    def order(self) -> List[str]:
        """Machines in first-appearance order, as the hub lists them."""
        return list(dict.fromkeys(self.machine.tolist()))

    def view(self, m):
        k = self.machine == m
        return self.X[k], self.y[k]


def describe(req) -> str:
    return (f"{req.op} {req.job} {req.machine} ctx={req.context} "
            f"t_max={req.t_max} due={req.due:.3f} sent={req.sent:.3f} "
            f"done={req.done:.3f} -> {req.result}")


def family(model: str) -> str:
    """``plain`` for the closed-form pool models, ``tree`` for the
    boosted ones."""
    return "tree" if model in ("gbm", "ogb") else "plain"


def _fit(args):
    X, y, seed = args
    return ref.fit_state(X, y, seed)


def _pool(fn, args) -> list:
    """``fn`` over ``args`` in worker processes (numpy, one BLAS thread
    each); started and stopped here."""
    if len(args) < 2:
        return [fn(a) for a in args]
    import multiprocessing as mp
    saved = {k: os.environ.get(k) for k in THREAD_VARS}
    os.environ.update({k: "1" for k in THREAD_VARS})
    try:
        with ProcessPoolExecutor(min(WORKERS, len(args)),
                                 mp_context=mp.get_context("spawn")) as pool:
            return list(pool.map(fn, args))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class Checker:
    def __init__(self, cfg: dict, seed: int, traffic, selected: dict):
        self.cfg, self.seed, self.t = cfg, seed, traffic
        self.selected = selected                 # (job, machine) -> model
        self.limits = cfg["limits"]
        self.readings: Dict[str, float] = {}
        self.worst: Dict[str, str] = {}          # number -> its answer
        self.values: Dict[str, list] = {}        # number -> every reading
        self.stores = {j: Store.of(d) for j, d in traffic.stores.items()}
        self.fits: Dict[Tuple[str, str], ref.Fitted] = {}

    def note(self, name: str, value: float, what=None) -> None:
        self.values.setdefault(name, []).append(float(value))
        if float(value) >= self.readings.get(name, 0.0):
            self.readings[name] = float(value)
            if what is not None:
                self.worst[name] = what()

    def fit_all(self, jobs) -> None:
        keys = [(j, m) for j in jobs for m in self.stores[j].order]
        args = [self.stores[j].view(m) + (self.seed,) for j, m in keys]
        self.fits.update(zip(keys, _pool(_fit, args)))

    # ----------------------------------------------------------- answers
    def check_selection(self) -> None:
        for (job, m), sel in self.selected.items():
            f = self.fits[(job, m)]
            self.note("select_gap", f.cv_mape[sel] - min(f.cv_mape.values()),
                      lambda: f"{job} {m} serves {sel}, reference CV MAPE "
                              f"{f.cv_mape}")

    def check_predicts(self, reads) -> None:
        """Single-row predicts, scored in one reference call per
        (job, machine, served model)."""
        groups: Dict[tuple, list] = {}
        for r in reads:
            key = (r.job, r.machine, r.result.selected_model)
            groups.setdefault(key, []).append(r)
        for (job, m, sel), rs in groups.items():
            f = self.fits[(job, m)]
            p = f.predict(sel, np.asarray([r.context for r in rs]))
            fam = family(sel)
            for r, want in zip(rs, p):
                res = r.result
                self.note(f"predict_gap.{fam}",
                          abs(res.runtimes_s[0] - want) / max(abs(want),
                                                              1e-9),
                          lambda: describe(r))
                self.note(f"calib_gap.{fam}",
                          max(abs(res.mu - f.mu[sel]),
                              abs(res.sigma - f.sigma[sel]))
                          / max(f.sigma[sel], 1e-9), lambda: describe(r))

    def check_chooses(self, reads) -> None:
        """Choose answers: the reference grid of every machine's served
        model, for all of a job's answers in one call per machine."""
        S = list(self.cfg["scaleouts"])
        by_job: Dict[str, list] = {}
        for r in reads:
            by_job.setdefault(r.job, []).append(r)
        for job, rs in by_job.items():
            order = self.stores[job].order
            places, prices, rates, overhead = D.market_tables(
                self.cfg, self.seed, order)
            rows = np.concatenate([ref.grid_rows(S, r.context) for r in rs])
            sel = [self.selected[(job, m)] for m in order]
            grid = np.stack([np.maximum(
                self.fits[(job, m)].predict(s, rows), 0.0)
                .reshape(len(rs), len(S)) for m, s in zip(order, sel)], 1)
            margin = np.array([self.fits[(job, m)].margin(s)
                               for m, s in zip(order, sel)])
            for a, r in enumerate(rs):
                res = r.result
                m_i = order.index(res.machine_type)
                p_i = places.index((res.zone, res.purchase_option))
                s_i = S.index(res.scale_out)
                want = grid[a, m_i, s_i]
                self.note(f"choose_gap.{family(sel[m_i])}",
                          abs(res.predicted_runtime_s - want)
                          / max(want, 1e-9), lambda: describe(r))
                k = (m_i * len(places) + p_i) * len(S) + s_i
                self.note("choose_regret", ref.choose_regret(
                    grid[a], margin, prices, rates, overhead, S, r.t_max,
                    k), lambda: describe(r))

    def run(self) -> Dict[str, Tuple[float, float]]:
        """-> {number: (reading, limit)} for the numbers the configuration
        limits, over every answer of the run; every number is read
        (``readings``) and printed all the same."""
        reads = [r for r in self.t.reads if r.ok]
        self.fit_all(sorted({r.job for r in reads}
                            | {j for j, _ in self.selected}))
        self.check_selection()
        self.check_predicts([r for r in reads if r.op == "predict"])
        self.check_chooses([r for r in reads if r.op == "choose"])
        for name, p in PERCENTILE.items():
            if name in self.values:
                self.readings[f"{name}.p{p}"] = percentile(
                    self.values[name], p)
        failed = sum(1 for r in self.t.all() if not r.ok)
        out = {k: (v, self.limits[k]) for k, v in self.readings.items()
               if k in self.limits}
        out["failed"] = (float(failed), 0.0)
        return out
