"""Bring-up smoke test: the C3O hub's fit -> choose -> serve path on a TPU.

Builds the hub from the paper's five emulated Spark jobs (``spark_emul``,
all three machine types, the default model pool and LOO-CV fold cap),
starts the serving edge in this process on a localhost port, and drives it
over the socket with the wire codec:

  search; a single-row predict for every (job, machine) and a choose with a
  deadline for every job; one 4096-row predict; one burst of chooses that a
  single lane tick scores at more than 1024 grid rows per machine; an
  accepted contribution and the choose that refits on it; GET /stats.

It fails unless every envelope is ok, /stats counts no error, a served
predictor selected GBM, the Pallas GBM kernel served it (and agrees with
the jnp GBM path on the chip at 1..4096 rows), every GBM the chip fitted
chose the best split at each node, and the answers agree with the CPU
reference in ``tests/goldens/chip_smoke_cpu.json``.

    python chip_smoke.py                                  # one TPU chip
    python chip_smoke.py --chips 4                        # sharded LOO-CV
    JAX_PLATFORMS=cpu python chip_smoke.py --reference    # rewrite golden

``--chips 4`` runs one phase only: ``engine.cv_select`` sharded over the
four chips against the single-chip path, for every (job, machine).  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failure exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
GOLDEN = ROOT / "tests" / "goldens" / "chip_smoke_cpu.json"
JOBS = ("sort", "grep", "sgd", "kmeans", "pagerank")
SCALEOUTS = (2, 3, 4, 6, 8, 12, 16)
BIG_PREDICT_ROWS = 4096
BURST = 256                # 256 contexts x 7 scale-outs = 1792 rows/machine
BURST_TICK_S = 0.2         # lane accumulation window, so the burst shares
#                            one tick
KERNEL_ROWS = (1, 7, 255, 256, 1000, 1792, 4096)
# chip vs CPU reference: f32 on two backends whose reduction orders and
# transcendentals differ (set before the first chip run)
RTOL, ATOL_MAPE, ATOL_S = 1e-3, 1e-5, 1e-3
# numbers out of a GBM fit: its greedy split search turns any f32
# difference into another, equally good ensemble, so they are held to an
# envelope: absolute CV MAPE, a fraction of the fit's CV sigma (mu and
# sigma), a relative deviation (predictions, bounds, costs).  Each limit
# is the geometric mean of the largest reading of one-ulp-nudged CPU runs
# and the smallest of a bf16 split search (PERF.md gives both)
GBM_ATOL_MAPE, GBM_RTOL_SIGMA, GBM_RTOL_PRED = 2e-3, 0.12, 1.3e-2
#: the limit of each family's reading (see ``readings``)
LIMITS = {"mape": 1.0, "s": 1.0, "gbm mape": GBM_ATOL_MAPE,
          "gbm resid": GBM_RTOL_SIGMA, "gbm pred": GBM_RTOL_PRED}
# a GBM split's gain shortfall against the best split at its node, as a
# fraction of the node's sum of squares (split_shortfall; PERF.md)
SPLIT_SHORTFALL = 5e-5
# kernel vs jnp GBM path on the same chip: same f32 adds in the same order
KERNEL_RTOL = 1e-6
# sharded vs single-chip LOO-CV (tests/test_dataplane.py parity bounds)
SHARD_RTOL, SHARD_ATOL_MAPE, SHARD_ATOL_S = 2e-5, 1e-6, 1e-5
NEAR_TIE = 1e-4            # CV MAPE gap under which a model flip is a tie
NUDGES = 64                # --reference reruns that find fragile decisions


class SmokeError(Exception):
    """A phase failed; the message says which and why."""


class Log:
    """Per-phase wall times, each line labelled with the device."""

    def __init__(self, label: str):
        self.label = label

    def line(self, msg: str) -> None:
        print(f"[{self.label}] {msg}", flush=True)

    def phase(self, name: str, t0: float, extra: str = "") -> None:
        self.line(f"phase={name} wall_s={time.perf_counter() - t0!r}"
                  + (f" {extra}" if extra else ""))


# --------------------------------------------------------------------------
# hub and traffic (all built from the seed)
# --------------------------------------------------------------------------

def build_gateway(seed: int, nudge=None):
    """The five-job hub; ``nudge`` (a numpy Generator) moves every stored
    runtime by at most one f32 ulp, for the reference's sensitivity runs."""
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import Hub, JobRepo
    from repro.workloads import spark_emul as W
    hub = Hub()
    for job in JOBS:
        d = W.generate_job_data(job, seed)
        if nudge is not None:
            y = d.runtime.astype(np.float32)
            step = nudge.integers(-1, 2, len(y))
            d.runtime[:] = np.where(step == 0, y, np.nextafter(
                y, np.where(step > 0, np.inf, -np.inf).astype(np.float32)))
        hub.publish(JobRepo(job, job, d.schema,
                            RuntimeDataStore(d, seed=seed),
                            predictor_kw=dict(pad_rows=True)))
    prices = {m.name: m.price for m in W.MACHINES.values()}
    return hub.gateway(prices, SCALEOUTS, seed=seed)


def sample_rows(data, n: int, rng) -> np.ndarray:
    """``n`` feature rows around the job's stored ones: stored scale-out,
    context jittered by up to 10%."""
    X = np.asarray(data.X, np.float64)[rng.integers(0, len(data), n)]
    X[:, 1:] *= rng.uniform(0.9, 1.1, (n, X.shape[1] - 1))
    return X


def choice_row(c, t_max: float) -> list:
    return [c.machine_type, int(c.scale_out), float(c.runtime_bound_s),
            float(c.cost_usd), t_max]


# --------------------------------------------------------------------------
# serving phase: edge + socket client in this one process
# --------------------------------------------------------------------------

async def _call(port: int, method: str, path: str, body=None):
    from repro.api import codec
    from repro.serve.loadgen import _request
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        raw = b"" if body is None else codec.encode(body).encode("ascii")
        _, payload = await _request(reader, writer, method, path, raw)
    finally:
        writer.close()
        await writer.wait_closed()
    resp = codec.decode(payload.decode("utf-8"))
    if not resp.ok:
        raise SmokeError(f"{method} {path} answered {resp.error_code}: "
                         f"{resp.detail}")
    return resp.result


async def _burst(port: int, reqs) -> list:
    """Send every request at once over its own pre-opened connection."""
    from repro.api import codec
    from repro.serve.loadgen import _request
    conns = [await asyncio.open_connection("127.0.0.1", port) for _ in reqs]
    try:
        outs = await asyncio.gather(*(
            _request(r, w, "POST", "/v1/choose",
                     codec.encode(q).encode("ascii"))
            for (r, w), q in zip(conns, reqs)))
    finally:
        for _, w in conns:
            w.close()
            await w.wait_closed()
    results = []
    for _, payload in outs:
        resp = codec.decode(payload.decode("utf-8"))
        if not resp.ok:
            raise SmokeError(f"burst choose answered {resp.error_code}: "
                             f"{resp.detail}")
        results.append(resp.result)
    return results


def _lane(stats, name: str):
    for lane in stats.lanes:
        if lane.lane == name:
            return lane
    return None


async def serve_phase(gw, seed: int, log: Log) -> dict:
    from repro.api.types import (ChooseRequest, ContributeRequest,
                                 PredictRequest, SearchRequest)
    from repro.serve.edge import serve_edge
    from repro.workloads import spark_emul as W

    rng = np.random.default_rng(seed)
    out: dict = {}
    _, server = await serve_edge(gw, "127.0.0.1", 0, max_batch=BURST,
                                 tick_s=BURST_TICK_S)
    port = server.port
    try:
        t0 = time.perf_counter()
        found = await _call(port, "POST", "/v1/search", SearchRequest())
        if tuple(j.job for j in found.jobs) != tuple(sorted(JOBS)):
            raise SmokeError(f"search listed {[j.job for j in found.jobs]}")
        log.phase("search", t0)

        # single-row predicts: the first one per (job, machine) fits
        # that machine's predictor (cold compiles land here)
        t0 = time.perf_counter()
        out["predict_1row"], out["fits"] = {}, {}
        for job in JOBS:
            repo = gw.hub.get(job)
            data = repo.store.data
            for m in data.machines:
                i = int(np.flatnonzero(data.machine_type == m)[0])
                row = tuple(float(v) for v in data.X[i])
                res = await _call(port, "POST", "/v1/predict",
                                  PredictRequest(job, m, (row,)))
                pred = repo.predictor_for(m, seed=gw.seed)     # cached fit
                key = f"{job}/{m}"
                out["predict_1row"][key] = res.runtimes_s[0]
                out["fits"][key] = {
                    "selected": res.selected_model,
                    "cv_mape": dict(sorted(pred.cv_mape.items())),
                    "mu": res.mu, "sigma": res.sigma}
        log.phase("predict_1row_with_fits", t0,
                  f"predictors={len(out['fits'])}")

        t0 = time.perf_counter()
        out["choose"] = {}
        for job in JOBS:
            data = gw.hub.get(job).store.data
            t_max = float(1.5 * data.y[0])
            res = await _call(port, "POST", "/v1/choose", ChooseRequest(
                job, tuple(float(v) for v in data.X[0, 1:]), t_max=t_max))
            out["choose"][job] = choice_row(res, t_max)
        log.phase("choose_deadline", t0, f"jobs={len(JOBS)}")

        gbm = [k for k, f in out["fits"].items() if f["selected"] == "gbm"]
        if not gbm:
            raise SmokeError("no served predictor selected gbm: the kernel "
                             "path was not exercised")
        job, machine = gbm[0].split("/")
        out["gbm_target"] = gbm[0]
        data = gw.hub.get(job).store.data

        t0 = time.perf_counter()
        X = sample_rows(data, BIG_PREDICT_ROWS, rng)
        res = await _call(port, "POST", "/v1/predict", PredictRequest(
            job, machine, tuple(tuple(float(v) for v in r) for r in X)))
        if len(res.runtimes_s) != BIG_PREDICT_ROWS \
                or not np.all(np.isfinite(res.runtimes_s)):
            raise SmokeError("4096-row predict: wrong length or non-finite")
        out["predict_4096"] = list(res.runtimes_s)
        log.phase("predict_4096", t0, f"job={job} machine={machine}")

        t0 = time.perf_counter()
        before = _lane(await _call(port, "GET", "/stats"), job)
        ctx = sample_rows(data, BURST, rng)[:, 1:]
        t_max = np.where(rng.random(BURST) < 0.25, math.nan,
                         rng.uniform(100.0, 2000.0, BURST))
        res = await _burst(port, [
            ChooseRequest(job, tuple(float(v) for v in c), t_max=float(t))
            for c, t in zip(ctx, t_max)])
        after = _lane(await _call(port, "GET", "/stats"), job)
        ticks = after.batches - before.batches
        served = after.requests - before.requests
        rows = math.ceil(served / max(ticks, 1)) * len(SCALEOUTS)
        if served != BURST or rows <= 1024:
            raise SmokeError(f"burst of {BURST} chooses took {ticks} lane "
                             f"ticks for {served} requests: no tick is "
                             "known to score > 1024 grid rows per machine")
        out["burst"] = [choice_row(c, float(t)) for c, t in zip(res, t_max)]
        log.phase("choose_burst", t0, f"job={job} lane_ticks={ticks} "
                  f"grid_rows_per_machine_per_tick>={rows}")

        t0 = time.perf_counter()
        new = W.generate_user_data(job, 1, seed)
        rep = await _call(port, "POST", "/v1/contribute", ContributeRequest(
            job, tuple(str(m) for m in new.machine_type),
            tuple(tuple(float(v) for v in r) for r in new.X),
            tuple(float(v) for v in new.y), contributor_id="chip-smoke"))
        if not rep.accepted:
            raise SmokeError(f"contribution rejected: {rep.reason}")
        t_max = float(1.5 * data.y[0])
        res = await _call(port, "POST", "/v1/choose", ChooseRequest(
            job, tuple(float(v) for v in data.X[0, 1:]), t_max=t_max))
        repo = gw.hub.get(job)
        out["refit"] = {
            "store_rows": rep.store_rows, "choose": choice_row(res, t_max),
            "fits": {m: {"selected": p.selected,
                         "cv_mape": dict(sorted(p.cv_mape.items()))}
                     for m in repo.store.data.machines
                     for p in [repo.predictor_for(m, seed=gw.seed)]}}
        log.phase("contribute_and_refit_choose", t0,
                  f"store_rows={rep.store_rows}")

        stats = await _call(port, "GET", "/stats")
        if stats.errors != 0:
            raise SmokeError(f"/stats reports errors={stats.errors}")
        log.line(f"stats requests={stats.requests} errors={stats.errors} "
                 f"p50_ms={stats.p50_ms!r} p99_ms={stats.p99_ms!r}")
    finally:
        await server.stop()
    return out


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------

def check_kernel(gw, target: str, seed: int, log: Log) -> None:
    """The Pallas kernel served on the chip, and agrees with the jnp GBM
    path on the chip itself."""
    from repro.core import engine
    from repro.core.models.gbm import GBM_SPEC
    if engine._gbm_kernel_executable.cache_info().currsize == 0:
        raise SmokeError("the GBM kernel executable was never requested")
    kernel = engine._gbm_kernel_executable()
    job, machine = target.split("/")
    fitted = gw.hub.get(job).predictor_for(machine, seed=gw.seed)._fitted
    if fitted.spec is not GBM_SPEC:
        raise SmokeError(f"{target} no longer selects gbm after the refit")
    p = fitted.params
    data = gw.hub.get(job).store.data
    rng = np.random.default_rng(seed + 1)
    args = (p.feat, p.thr, p.leaf, p.f0, p.y_scale)
    text = kernel.lower(np.zeros((8, data.X.shape[1]), np.float32),
                        *args).as_text()
    if "tpu_custom_call" not in text:
        raise SmokeError("the GBM kernel did not lower to a Mosaic call")
    log.line(f"gbm kernel: compiled shapes={kernel._cache_size()} "
             "lowering=tpu_custom_call")
    for n in KERNEL_ROWS:
        X = sample_rows(data, n, rng)
        got = np.asarray(engine.predict(GBM_SPEC, p, X, fitted.aux))
        ref = np.asarray(engine.predict_executable(GBM_SPEC)(
            p, np.asarray(X, np.float32), fitted.aux))
        err = float(np.max(np.abs(got - ref) / np.abs(ref)))
        log.line(f"gbm kernel vs jnp rows={n} max_rel_diff={err!r}")
        if not np.allclose(got, ref, rtol=KERNEL_RTOL, atol=0.0):
            raise SmokeError(f"kernel disagrees with jnp GBM at {n} rows "
                             f"(max rel diff {err!r})")


def log_targets(y) -> np.ndarray:
    """The GBM fit's log targets as the default device computes them.  A
    TPU's f32 log differs from numpy's by up to about 1e-4 on these
    runtimes, which is enough to move the best split of a late tree, so
    the split replay starts from the device's own targets."""
    import jax
    import jax.numpy as jnp
    return np.asarray(jax.jit(lambda v: jnp.log(jnp.maximum(v, 1e-6)))(
        np.asarray(y, np.float32)))


def split_shortfall(params, X, yn, w) -> float:
    """Replay a fitted GBM along its own trees from its log targets ``yn``
    (``log_targets``) and return the largest gain shortfall of a split it
    chose against the best split at that node, as a fraction of the
    node's weighted sum of squared residuals.  Residuals follow the fit in
    f32; gains are taken in float64.  A sound f32 split search stays at
    rounding level; one that compares gains at reduced precision does
    not."""
    X = np.asarray(X, np.float32).astype(np.float64)
    w = np.asarray(w, np.float32).astype(np.float64)
    yn = np.asarray(yn, np.float32)
    feat, thr, leaf = (np.asarray(a) for a in
                       (params.feat, params.thr, params.leaf))
    n, d = X.shape
    depth = int(np.log2(feat.shape[1] + 1))
    order = np.argsort(X, axis=0)
    xs = np.take_along_axis(X, order, 0)                   # [n, d]
    step = np.vstack([xs[1:] > xs[:-1], np.zeros((1, d), bool)])
    pred = np.full(n, params.f0, np.float32)
    worst = 0.0
    for t in range(feat.shape[0]):
        r = (yn - pred).astype(np.float64)
        node = np.zeros(n, np.int64)
        for level in range(depth):
            M, base = 2 ** level, 2 ** level - 1
            oh = (node[:, None] == np.arange(M)) * w[:, None]   # [n, M]
            ws, wr = oh[order], (oh * r[:, None])[order]        # [n, d, M]
            cw, cwr = np.cumsum(ws, 0), np.cumsum(wr, 0)
            tw, twr = cw[-1], cwr[-1]
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = (cwr ** 2 / cw + (twr - cwr) ** 2 / (tw - cw)
                        - twr ** 2 / tw)
            ok = (cw > 1e-9) & (tw - cw > 1e-9) & step[:, :, None]
            best = np.where(ok, gain, -np.inf).max(axis=(0, 1))    # [M]
            f_c, t_c = feat[t, base:base + M], thr[t, base:base + M]
            left = (X[:, f_c] <= t_c) * oh                           # [n, M]
            lw, lr_ = left.sum(0), (left * r[:, None]).sum(0)
            with np.errstate(divide="ignore", invalid="ignore"):
                chosen = np.where(
                    np.isinf(t_c), 0.0, lr_ ** 2 / lw
                    + (twr[0] - lr_) ** 2 / (tw[0] - lw)
                    - twr[0] ** 2 / tw[0])
            ss = (oh * r[:, None] ** 2).sum(0)
            has = np.isfinite(best) & (ss > 0)
            if has.any():
                worst = max(worst, float(np.max(
                    (best[has] - chosen[has]) / ss[has])))
            node = 2 * node + (X[np.arange(n), feat[t, base + node]]
                               > thr[t, base + node])
        pred = pred + leaf[t, node]
    return worst


def check_gbm_splits(gw, log: Log) -> None:
    """Every GBM the chip fitted chose the best split at every node, to
    f32 rounding (SPLIT_SHORTFALL), checked on the host in float64."""
    from repro.core.models.gbm import GBM_SPEC
    worst, n_fits = (0.0, ""), 0
    for job in JOBS:
        repo = gw.hub.get(job)
        for m in repo.store.data.machines:
            pred = repo.predictor_for(m, seed=gw.seed)
            if pred._fitted.spec is not GBM_SPEC:
                continue
            d = repo.store.data.machine_view(m)
            X, y, _, w = pred.cv_inputs(d.X, d.y, repo.store.row_weights(d))
            x = split_shortfall(pred._fitted.params, X, log_targets(y),
                                w)
            n_fits += 1
            worst = max(worst, (x, f"{job}/{m}"))
    log.line(f"gbm split check: fits={n_fits} max_shortfall={worst[0]!r} "
             f"at {worst[1]} limit={SPLIT_SHORTFALL!r}")
    if worst[0] > SPLIT_SHORTFALL:
        raise SmokeError(f"the GBM fit at {worst[1]} chose a split "
                         f"{worst[0]!r} of its node's sum of squares short "
                         "of the best")


def choices(ans: dict) -> dict:
    """Every choose answer: name -> (row, model the chosen machine's
    predictor selected); a row is (machine, scale-out, bound, cost,
    t_max)."""
    job = ans["gbm_target"].split("/")[0]
    fits = {k: f["selected"] for k, f in ans["fits"].items()}
    out = {f"{j} choose": (row, fits[f"{j}/{row[0]}"])
           for j, row in ans["choose"].items()}
    out.update({f"burst {i} choose": (row, fits[f"{job}/{row[0]}"])
                for i, row in enumerate(ans["burst"])})
    row = ans["refit"]["choose"]
    out["refit choose"] = (row, ans["refit"]["fits"][row[0]]["selected"])
    return out


def decisions(ans: dict) -> dict:
    """Every discrete answer: selected models and chosen configurations."""
    d = {f"{k} selected": f["selected"] for k, f in ans["fits"].items()}
    d.update({k: row[:2] for k, (row, _) in choices(ans).items()})
    d.update({f"refit {m} selected": f["selected"]
              for m, f in ans["refit"]["fits"].items()})
    d["gbm target"] = ans["gbm_target"]
    d["refit store rows"] = ans["refit"]["store_rows"]
    return d


def numbers(ans: dict) -> dict:
    """Every continuous answer: name -> (values, family, scale, decision).

    A number whose value comes out of a GBM fit is in a ``gbm`` family;
    ``scale`` is what its deviation is measured against; ``decision`` is
    the discrete answer it rests on."""
    out = {}
    for key, f in ans["fits"].items():
        g = f["selected"] == "gbm"
        for m, v in f["cv_mape"].items():
            out[f"{key} cv_mape {m}"] = (
                [v], "gbm mape" if m == "gbm" else "mape", 1.0, "")
        for stat in ("mu", "sigma"):
            out[f"{key} {stat}"] = ([f[stat]], "gbm resid" if g else "s",
                                    f["sigma"], f"{key} selected")
        out[f"{key} 1-row predict"] = ([ans["predict_1row"][key]],
                                       "gbm pred" if g else "s", None,
                                       f"{key} selected")
    for k, (row, sel) in choices(ans).items():
        for name, v in ((" runtime bound", row[2]), (" cost", row[3])):
            out[k + name] = ([v], "gbm pred" if sel == "gbm" else "s",
                             None, k)
    for m, f in ans["refit"]["fits"].items():
        for k, v in f["cv_mape"].items():
            out[f"refit {m} cv_mape {k}"] = (
                [v], "gbm mape" if k == "gbm" else "mape", 1.0, "")
    out["4096-row predict"] = (ans["predict_4096"], "gbm pred", None,
                               "gbm target")
    return {k: (np.asarray(v, np.float64), fam, sc, dec)
            for k, (v, fam, sc, dec) in out.items()}


def readings(got: dict, ref: dict) -> dict:
    """How far each number of ``got`` lies from ``ref``, in the units its
    family's limit (LIMITS) is stated in: a multiple of the plain
    tolerance, an absolute MAPE, a fraction of the fit's sigma, or a
    relative deviation.  Numbers resting on a flipped decision are left
    out; the flip is judged on its own."""
    got_d, ref_d = decisions(got), decisions(ref)
    got_n = numbers(got)
    out = {}
    for k, (r, fam, scale, dec) in numbers(ref).items():
        if dec and got_d[dec] != ref_d[dec]:
            continue
        g = got_n[k][0]
        if g.shape != r.shape:
            out[k] = (fam, math.inf)
            continue
        err = np.abs(g - r)
        if fam == "mape":
            err = err / (ATOL_MAPE + RTOL * np.abs(r))
        elif fam == "s":
            err = err / (ATOL_S + RTOL * np.abs(r))
        elif fam == "gbm resid":
            err = err / scale
        elif fam == "gbm pred":
            err = err / np.abs(r)
        out[k] = (fam, float(err.max()))
    return out


def compare(got: dict, golden: dict, log: Log) -> list:
    """Chip answers against the CPU reference; returns the mismatches.

    Decisions must be equal.  Two kinds of flip are reported on their own
    line instead: a model flip between two models whose CV MAPEs lie
    within NEAR_TIE, and a decision in the golden's ``fragile`` list (one
    that a one-ulp nudge of the stored runtimes flips on the CPU too).
    Numbers resting on a flipped decision are not compared; every other
    number must lie within its family's limit (LIMITS)."""
    ref, fragile = golden["answers"], set(golden["fragile"])
    bad = []
    got_d, ref_d = decisions(got), decisions(ref)
    for k, r in ref_d.items():
        if got_d[k] == r:
            continue
        if k in fragile:
            log.line(f"fragile decision flip at {k}: chip={got_d[k]} "
                     f"cpu={r} (a one-ulp nudge flips it on the CPU too)")
            continue
        if k.endswith("selected"):
            fit = (ref["refit"]["fits"][k.split()[1]] if k.startswith("refit")
                   else ref["fits"][k.split()[0]])
            gap = abs(fit["cv_mape"][got_d[k]] - fit["cv_mape"][r])
            if gap <= NEAR_TIE:
                log.line(f"model near-tie flip at {k}: chip={got_d[k]} "
                         f"cpu={r} cpu_mape_gap={gap!r}")
                continue
        bad.append(f"{k}: {got_d[k]} vs {r}")
    worst: dict = {}
    for k, (fam, x) in readings(got, ref).items():
        if x > worst.get(fam, (-1.0, ""))[0]:
            worst[fam] = (x, k)
        if x > LIMITS[fam]:
            bad.append(f"{k}: {fam} reading {x!r} over its limit "
                       f"{LIMITS[fam]!r}")
    for fam, (x, k) in sorted(worst.items()):
        log.line(f"reference family={fam!r} max_reading={x!r} "
                 f"limit={LIMITS[fam]!r} at {k!r}")
    return bad


# --------------------------------------------------------------------------
# --chips 4: sharded vs single-chip LOO-CV
# --------------------------------------------------------------------------

def sharded_phase(seed: int, log: Log) -> None:
    from repro.core import engine
    from repro.core.models.api import get_model
    from repro.core.predictor import C3OPredictor
    gw = build_gateway(seed)
    bad = []
    for job in JOBS:
        repo = gw.hub.get(job)
        specs = [get_model(n) for n in repo.model_names]
        for m in repo.store.data.machines:
            d = repo.store.data.machine_view(m)
            pred = C3OPredictor(model_names=tuple(repo.model_names),
                                seed=gw.seed, **repo.predictor_kw)
            X, y, folds, w = pred.cv_inputs(d.X, d.y,
                                            repo.store.row_weights(d))
            t0 = time.perf_counter()
            one = engine.cv_select(specs, X, y, folds, sharded=False,
                                   row_weight=w)
            t1 = time.perf_counter()
            four = engine.cv_select(specs, X, y, folds, sharded=True,
                                    row_weight=w)
            t2 = time.perf_counter()
            ok = one[0] == four[0] and all(
                np.isclose(four[1][k], one[1][k], rtol=SHARD_RTOL,
                           atol=SHARD_ATOL_MAPE) for k in one[1]) \
                and np.allclose(four[2:], one[2:], rtol=SHARD_RTOL,
                                atol=SHARD_ATOL_S)
            diff = max(abs(four[1][k] - one[1][k]) for k in one[1])
            log.line(f"cv {job}/{m}: one_chip={one[0]} four_chips={four[0]}"
                     f" max_mape_diff={diff!r} mu=({one[2]!r},{four[2]!r})"
                     f" sigma=({one[3]!r},{four[3]!r}) one_chip_wall_s="
                     f"{t1 - t0!r} four_chip_wall_s={t2 - t1!r} "
                     f"agree={ok}")
            if not ok:
                bad.append(f"{job}/{m}")
    if bad:
        raise SmokeError(f"sharded CV disagrees at {bad}")


def write_reference(got: dict, seed: int, log: Log) -> None:
    """Rewrite the golden: the answers, and the decisions that one of
    NUDGES reruns with every stored runtime nudged by one f32 ulp flips
    (``fragile``).  Each rerun's family readings are printed too:
    they are what the GBM limits are set from."""
    import jax
    fragile: set = set()
    for k in range(NUDGES):
        gw = build_gateway(seed, nudge=np.random.default_rng([seed, k]))
        alt = asyncio.run(serve_phase(gw, seed, log))
        flips = [d for d, v in decisions(alt).items()
                 if v != decisions(got)[d]]
        fragile.update(flips)
        worst: dict = {}
        for key, (fam, x) in readings(alt, got).items():
            worst[fam] = max(worst.get(fam, (-1.0, "")), (x, key))
        log.line(f"nudge {k}: decision flips={flips} " + " ".join(
            f"{fam!r}={x!r}@{key!r}" for fam, (x, key) in sorted(
                worst.items())))
    GOLDEN.write_text(json.dumps(
        {"answers": got, "fragile": sorted(fragile), "nudges": NUDGES,
         "jax": jax.__version__}, indent=1, sort_keys=True) + "\n")
    log.line(f"wrote {GOLDEN.relative_to(ROOT)} fragile={sorted(fragile)}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reference", action="store_true",
                    help="run on the CPU and rewrite the golden")
    ap.add_argument("--answers", help="also write this run's answers here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: FAIL: the repo's src/ tree is not beside this "
              "script", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    want = "cpu" if args.reference else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: FAIL: no TPU found (JAX platform "
              f"{dev.platform!r})" if want == "tpu" else
              "chip_smoke: FAIL: --reference runs on the CPU "
              "(JAX_PLATFORMS=cpu)", file=sys.stderr)
        return 2
    if args.reference and args.chips != 1:
        print("chip_smoke: FAIL: --reference is a one-device CPU run",
              file=sys.stderr)
        return 2
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: FAIL: --chips 4 needs 4 chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    log = Log(f"{dev.platform}:{dev.device_kind}x{len(devices)}")
    log.line(f"jax={jax.__version__} compile_cache={cache}")
    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_phase(args.seed, log)
        else:
            t0 = time.perf_counter()
            gw = build_gateway(args.seed)
            log.phase("build_hub", t0)
            got = asyncio.run(serve_phase(gw, args.seed, log))
            for key, f in got["fits"].items():
                m = sorted(f["cv_mape"].values())
                if len(m) > 1 and m[1] - m[0] <= NEAR_TIE:
                    log.line(f"near-tie at {key}: best two CV MAPEs "
                             f"{m[0]!r} {m[1]!r}")
            if args.answers:
                Path(args.answers).write_text(json.dumps(got) + "\n")
            check_gbm_splits(gw, log)
            if args.reference:
                write_reference(got, args.seed, log)
            else:
                check_kernel(gw, got["gbm_target"], args.seed, log)
                bad = compare(got, json.loads(GOLDEN.read_text()), log)
                for b in bad:
                    log.line(f"reference mismatch: {b}")
                if bad:
                    raise SmokeError(f"{len(bad)} answer(s) differ from "
                                     "the CPU reference")
                log.line("reference: selected models and chosen "
                         "configurations equal the CPU golden; every number "
                         "within tolerance")
    except SmokeError as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log.phase("total", t_start)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
