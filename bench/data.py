"""Inputs of a run, made from the seed: the deployment's stored rows, its
price book, and the lane tick sizes set-up warms.  Both sides use this
module: the server builds the hub from these rows, and the reference
fits the same rows.

The rows come from ``repro.workloads.spark_emul``, the emulation of the
paper's Spark jobs that the configuration names as its data source: they
are the hub's input, as a prompt is a model's, not anything it computed.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _emul():
    from repro.workloads import spark_emul
    return spark_emul


def job_data(cfg: dict, seed: int) -> Dict:
    """job -> RuntimeData: the shared store each job repo starts from."""
    W = _emul()
    out = {}
    for job, rows in cfg["jobs"].items():
        d = W.generate_job_data(job, seed)
        if len(d) != rows:
            raise ValueError(f"{job}: the emulation gives {len(d)} rows, "
                             f"the configuration states {rows}")
        out[job] = d
    return out


def list_prices(cfg: dict) -> Dict[str, float]:
    return {m: float(p) for m, p in cfg["machines"].items()}


def price_book(cfg: dict, seed: int):
    """The deployment's market book, or None for list prices."""
    if not cfg.get("market"):
        return None
    mk = cfg["market"]
    return _emul().generate_price_book(
        seed, n_ticks=mk["ticks"], zones=tuple(mk["zones"]),
        machines=tuple(cfg["machines"]),
        restart_overhead_s=mk["restart_overhead_s"])


def market_tables(cfg: dict, seed: int, machines) -> tuple:
    """(placements [(zone, option)], prices [M, P], rates [P], restart
    overhead s) at the book's first tick; one on-demand placement at list
    prices without a market."""
    book = price_book(cfg, seed)
    if book is None:
        prices = list_prices(cfg)
        return ([("", "")], np.array([[prices[m]] for m in machines]),
                np.zeros(1), 0.0)
    places = book.resolve(None, None)
    return ([(p.zone, p.option) for p in places],
            book.price_matrix(list(machines), places), book.rates(places),
            book.restart_overhead_s)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """Exact counts of ``n`` draws over ``k`` ranks in Zipf(s) shares
    (largest remainder), so every seed gets the same mix."""
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    c = np.floor(p * n).astype(int)
    rest = np.argsort(-(p * n - c), kind="stable")[:n - c.sum()]
    c[rest] += 1
    return c


def exact_split(n: int, shares: Dict[str, float]) -> List[str]:
    names = list(shares)
    p = np.asarray([shares[k] for k in names], float)
    p /= p.sum()
    c = np.floor(p * n).astype(int)
    rest = np.argsort(-(p * n - c), kind="stable")[:n - c.sum()]
    c[rest] += 1
    return [nm for nm, k in zip(names, c) for _ in range(k)]


def warm_ticks(mix: dict) -> dict:
    """Lane tick sizes set-up runs through each lane kind the mix reads
    (``warm_ticks``); a tick of another size lowers its executables when
    it first comes, as it would in a deployment."""
    ops = mix.get("ops", {})
    return {op: list(mix["warm_ticks"]) if ops.get(op) else []
            for op in ("choose", "predict")}


def sample_rows(data, n: int, rng, jitter: float = 0.10) -> np.ndarray:
    """``n`` feature rows around the job's stored ones: stored scale-out,
    context jittered by up to ``jitter``."""
    X = np.asarray(data.X, np.float64)[rng.integers(0, len(data), n)]
    X[:, 1:] *= rng.uniform(1.0 - jitter, 1.0 + jitter,
                            (n, X.shape[1] - 1))
    return X


def deadline(rng, mix: dict) -> float:
    lo, hi = mix["deadline_s"]
    return math.nan if rng.random() < mix["no_deadline_share"] \
        else float(rng.uniform(lo, hi))
