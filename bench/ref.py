"""Plain float64 reference of the C3O hub's answers, in numpy alone.

It imports nothing of the system under test.  From the same stored rows
(``bench/data.py`` makes them from the seed) it fits the model pool the
hub selects from, leave-one-out cross-validates it, picks the model with
the least CV MAPE, scores the (machine x [placement x] scale-out) grid,
and applies the choose rule.  Every fit is vectorised over its folds:
``W`` is an ``[F, n]`` weight matrix and a model is ``F`` models.

The pool follows the published model descriptions as the hub states them
(paper section V; Ernest, NSDI'16):

  ernest  t = th0 + th1 z/s + th2 log s + th3 s, th >= 0, by 400 projected
          gradient steps on column-normalised normal equations
  gbm     200 exact-greedy depth-3 regression trees on log runtime, lr 0.1
  bom     group-mean log runtime, cubic log-space scale-out model, ridge
          context model
  ogb     the same factorisation with boosted trees for both parts
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import numpy as np

MODELS = ("ernest", "gbm", "bom", "ogb")
NEG = -1e30
MIN_RATIO = 0.05
#: sqrt(2) * erfinv(2 * 0.95 - 1): the one-sided 95% normal quantile
Z95 = 1.6448536269514722


# --------------------------------------------------------------------------
# models, vectorised over F weight vectors
# --------------------------------------------------------------------------

def _route(feat, thr, X):
    """feat/thr [F, n_int], X [n, d] -> leaf index [F, n]."""
    F, n_int = feat.shape
    n = X.shape[0]
    idx = np.zeros((F, n), np.int64)
    rows = np.arange(n)[None, :]
    for _ in range(int(math.log2(n_int + 1))):
        f = np.take_along_axis(feat, idx, 1)
        t = np.take_along_axis(thr, idx, 1)
        idx = 2 * idx + 1 + (X[rows, f] > t)
    return idx - n_int


def _fit_tree(X, r, W, orders, depth):
    """One weighted least-squares tree per fold on residuals r [F, n].

    Every feature's candidate splits are scored at once: the best split
    of a node is the first maximum of the gain in feature-major order
    (the first feature that reaches it, then its first position)."""
    F, n = r.shape
    d = X.shape[1]
    n_int = 2 ** depth - 1
    feat = np.zeros((F, n_int), np.int64)
    thr = np.full((F, n_int), np.inf)
    node = np.zeros((F, n), np.int64)
    rows = np.arange(n)[None, :]
    x_s = np.take_along_axis(X, orders.T, 0).T                   # [d, n]
    x_next = np.concatenate([x_s[:, 1:], x_s[:, -1:]], 1)
    step = (x_next > x_s)[None, :, :, None]
    w_s, r_s = W[:, orders], r[:, orders]                        # [F, d, n]
    for level in range(depth):
        M = 2 ** level
        oh = node[:, orders][..., None] == np.arange(M)       # [F, d, n, M]
        ws = w_s[..., None] * oh
        cw = np.cumsum(ws, 2)
        cwr = np.cumsum(ws * r_s[..., None], 2)
        tw, twr = cw[:, :, -1:], cwr[:, :, -1:]
        rw, rr = tw - cw, twr - cwr
        gain = (cwr ** 2 / np.maximum(cw, 1e-12)
                + rr ** 2 / np.maximum(rw, 1e-12)
                - twr ** 2 / np.maximum(tw, 1e-12))
        gain = np.where((cw > 1e-9) & (rw > 1e-9) & step, gain, NEG)
        flat = gain.reshape(F, d * n, M)
        k = flat.argmax(1)                                       # [F, M]
        best_gain = np.take_along_axis(flat, k[:, None, :], 1)[:, 0]
        best_feat, pos = k // n, k % n
        best_thr = 0.5 * (x_s[best_feat, pos] + x_next[best_feat, pos])
        base = 2 ** level - 1
        lvl_thr = np.where(best_gain > NEG / 2, best_thr, np.inf)
        feat[:, base:base + M] = best_feat
        thr[:, base:base + M] = lvl_thr
        f_cur = np.take_along_axis(best_feat, node, 1)
        t_cur = np.take_along_axis(lvl_thr, node, 1)
        node = 2 * node + (X[rows, f_cur] > t_cur)
    leaf_idx = _route(feat, thr, X)
    oh = leaf_idx[:, :, None] == np.arange(2 ** depth)           # [F, n, L]
    sw = (W[:, :, None] * oh).sum(1)
    swr = (W[:, :, None] * oh * r[:, :, None]).sum(1)
    return feat, thr, swr / np.maximum(sw, 1e-12), leaf_idx


@dataclass
class GBM:
    f0: np.ndarray            # [F]
    feat: np.ndarray          # [F, T, n_int]
    thr: np.ndarray           # [F, T, n_int]
    leaf: np.ndarray          # [F, T, n_leaves], learning rate applied
    log: bool
    y_scale: np.ndarray       # [F]

    def predict(self, X) -> np.ndarray:
        out = np.repeat(self.f0[:, None], len(X), 1)
        for t in range(self.feat.shape[1]):
            idx = _route(self.feat[:, t], self.thr[:, t], X)
            out = out + np.take_along_axis(self.leaf[:, t], idx, 1)
        if self.log:
            return np.exp(np.clip(out, -30.0, 30.0))
        return out * np.maximum(self.y_scale, 1e-12)[:, None]


def gbm_fit(X, y, W, orders, n_trees, depth, lr, log_target=True) -> GBM:
    """y [n] or [F, n]; W [F, n]."""
    F, n = W.shape
    y = np.broadcast_to(y, (F, n))
    wsum = np.maximum(W.sum(1), 1e-12)
    if log_target:
        yn = np.log(np.maximum(y, 1e-6))
        y_scale = np.zeros(F)
    else:
        y_scale = np.maximum((W * np.abs(y)).sum(1) / wsum, 1e-12)
        yn = y / y_scale[:, None]
    f0 = (W * yn).sum(1) / wsum
    pred = np.repeat(f0[:, None], n, 1)
    feats, thrs, leaves = [], [], []
    for _ in range(n_trees):
        feat, thr, leaf, idx = _fit_tree(X, yn - pred, W, orders, depth)
        pred = pred + lr * np.take_along_axis(leaf, idx, 1)
        feats.append(feat)
        thrs.append(thr)
        leaves.append(lr * leaf)
    return GBM(f0, np.stack(feats, 1), np.stack(thrs, 1),
               np.stack(leaves, 1), log_target, y_scale)


def _orders(X) -> np.ndarray:
    return np.argsort(X, axis=0, kind="stable").T


@dataclass
class Ernest:
    theta: np.ndarray         # [F, 4]
    scale: np.ndarray         # [F]

    @staticmethod
    def basis(X):
        s = np.maximum(X[:, 0], 1.0)
        z = X[:, 1] if X.shape[1] > 1 else np.ones_like(s)
        return np.stack([np.ones_like(s), z / s, np.log(s), s], 1)

    def predict(self, X) -> np.ndarray:
        return (self.basis(X) @ self.theta.T).T * self.scale[:, None]


def ernest_fit(X, y, W, iters: int = 400) -> Ernest:
    A = Ernest.basis(X)                                          # [n, 4]
    scale = np.maximum((W * np.abs(y)).sum(1)
                       / np.maximum(W.sum(1), 1e-12), 1e-12)
    yn = y[None, :] / scale[:, None]
    cn = np.maximum(np.sqrt(np.einsum("fn,ni->fi", W, A ** 2)), 1e-12)
    An = A[None] / cn[:, None, :]                                # [F, n, 4]
    G = np.einsum("fni,fn,fnj->fij", An, W, An)
    b = np.einsum("fni,fn,fn->fi", An, W, yn)
    L = np.linalg.norm(G, ord=2, axis=(1, 2)) + 1e-6
    th = np.maximum(b / np.maximum(np.einsum("fii->fi", G), 1e-9), 0.0)
    for _ in range(iters):
        g = np.einsum("fij,fj->fi", G, th) - b
        th = np.maximum(th - g / L[:, None], 0.0)
    return Ernest(th / cn, scale)


@dataclass
class Ridge:
    beta: np.ndarray          # [F, k + 1], bias last
    mu: np.ndarray            # [F, k]
    sd: np.ndarray            # [F, k]

    def predict(self, X) -> np.ndarray:
        """X [n, k] or [F, n, k] -> [F, n]."""
        Xn = (X - self.mu[:, None, :]) / self.sd[:, None, :]
        A = np.concatenate([Xn, np.ones(Xn.shape[:2] + (1,))], 2)
        return np.einsum("fnk,fk->fn", A, self.beta)


def ridge_fit(X, y, W, lam=1e-4) -> Ridge:
    """X [n, k] or [F, n, k]; y [n] or [F, n]; W [F, n]."""
    F, n = W.shape
    X = np.broadcast_to(X, (F,) + X.shape[-2:])
    y = np.broadcast_to(y, (F, n))
    wsum = np.maximum(W.sum(1), 1e-12)
    mu = (W[:, :, None] * X).sum(1) / wsum[:, None]
    var = (W[:, :, None] * (X - mu[:, None]) ** 2).sum(1) / wsum[:, None]
    sd = np.sqrt(np.maximum(var, 1e-12))
    Xn = (X - mu[:, None]) / sd[:, None]
    A = np.concatenate([Xn, np.ones((F, n, 1))], 2)
    Aw = A * W[:, :, None]
    G = np.einsum("fni,fnj->fij", A, Aw) + lam * np.eye(A.shape[2])
    b = np.einsum("fni,fn->fi", Aw, y)
    return Ridge(np.linalg.solve(G, b[:, :, None])[:, :, 0], mu, sd)


def _poly(s):
    s = np.maximum(s, 1e-6)
    return np.stack([s, s ** 2, s ** 3], -1)


@dataclass
class Optimistic:
    kind: str                 # "bom" or "ogb"
    ssm: object
    g1: np.ndarray            # [F]
    ibm: object

    def _ssm(self, s) -> np.ndarray:
        if self.kind == "bom":
            return np.exp(np.clip(self.ssm.predict(_poly(s)), -4.0, 4.0))
        return self.ssm.predict(s[:, None])

    def predict(self, X) -> np.ndarray:
        s, ctx = _split(X)
        g = np.maximum(self._ssm(s), MIN_RATIO) / self.g1[:, None]
        return self.ibm.predict(ctx) * g


def _split(X):
    return X[:, 0], (X[:, 1:] if X.shape[1] > 1
                     else np.zeros((len(X), 1)))


def optimistic_fit(kind: str, X, y, W) -> Optimistic:
    s, ctx = _split(X)
    _, gid = np.unique(np.round(ctx, 9), axis=0, return_inverse=True)
    onehot = (gid.reshape(-1)[:, None]
              == np.arange(gid.max() + 1)).astype(np.float64)   # [n, G]
    logt = np.log(np.maximum(y, 1e-6))
    wg = W[:, :, None] * onehot                                  # [F, n, G]
    cnt = wg.sum(1)
    beta = (wg * logt[None, :, None]).sum(1) / np.maximum(cnt, 1e-12)
    base = np.exp(beta @ onehot.T)                               # [F, n]
    ratio = y / np.maximum(base, 1e-9)
    w_ssm = W * ((cnt >= 1.5).astype(np.float64) @ onehot.T)
    if kind == "bom":
        ssm = ridge_fit(_poly(s), np.log(np.maximum(ratio, 1e-3)), w_ssm,
                        lam=3e-3)
    else:
        ssm = gbm_fit(s[:, None], ratio, w_ssm, _orders(s[:, None]),
                      n_trees=50, depth=2, lr=0.15)
    m = Optimistic(kind, ssm, None, None)
    if kind == "bom":
        g_raw = np.exp(np.clip(ssm.predict(_poly(s)), -4.0, 4.0))
        g1 = np.exp(np.clip(ssm.predict(_poly(np.ones(1))), -4.0, 4.0))[:, 0]
    else:
        g_raw = ssm.predict(s[:, None])
        g1 = ssm.predict(np.ones((1, 1)))[:, 0]
    g_raw = np.maximum(g_raw, MIN_RATIO)
    m.g1 = np.maximum(g1, MIN_RATIO)
    t1 = y / (g_raw / m.g1[:, None])
    if kind == "bom":
        m.ibm = ridge_fit(ctx, t1, W)
    else:
        m.ibm = gbm_fit(ctx, t1, W, _orders(ctx), n_trees=100, depth=3,
                        lr=0.1)
    return m


def fit_model(name: str, X, y, W):
    if name == "ernest":
        return ernest_fit(X, y, W)
    if name == "gbm":
        return gbm_fit(X, y, W, _orders(X), n_trees=200, depth=3, lr=0.1)
    return optimistic_fit(name, X, y, W)


# --------------------------------------------------------------------------
# selection, calibration, grid choice, validation
# --------------------------------------------------------------------------

@dataclass
class Fitted:
    """One (job, machine) at one store state: every pool model's CV
    readings and its fit on all rows."""
    cv_mape: Dict[str, float]
    mu: Dict[str, float]
    sigma: Dict[str, float]
    models: Dict[str, object]

    @property
    def best(self) -> str:
        return min(MODELS, key=lambda m: self.cv_mape[m])

    def predict(self, model: str, X) -> np.ndarray:
        return self.models[model].predict(np.asarray(X, np.float64))[0]

    def margin(self, model: str) -> float:
        return self.mu[model] + Z95 * (self.sigma[model] + 1e-12)


def cv_folds(n: int, seed: int, max_folds: int = 30) -> np.ndarray:
    if n <= max_folds:
        return np.arange(n)
    return np.random.default_rng(seed).choice(n, max_folds, replace=False)


def _clean(p):
    return np.nan_to_num(p, nan=1e12, posinf=1e12, neginf=-1e12)


def fit_state(X, y, seed: int) -> Fitted:
    """LOO-CV every pool model, then fit each on all rows."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    folds = cv_folds(len(y), seed)
    W = 1.0 - (folds[:, None] == np.arange(len(y)))
    cv, mu, sigma, models = {}, {}, {}, {}
    for name in MODELS:
        m = fit_model(name, X, y, W)
        pred = _clean(np.take_along_axis(
            m.predict(X), folds[:, None], 1)[:, 0])
        yf = y[folds]
        resid = pred - yf
        cv[name] = float(np.mean(np.abs(resid) / np.maximum(np.abs(yf),
                                                            1e-9)))
        mu[name] = float(resid.mean())
        sigma[name] = float(np.sqrt(max((resid ** 2).mean()
                                        - mu[name] ** 2, 0.0)))
        models[name] = fit_model(name, X, y, np.ones((1, len(y))))
    return Fitted(cv, mu, sigma, models)


def grid_rows(scaleouts, context) -> np.ndarray:
    """[S, 1 + k] rows of one context over the scale-out grid."""
    c = np.asarray(context, np.float64)
    return np.concatenate([np.asarray(scaleouts, np.float64)[:, None],
                           np.broadcast_to(c, (len(scaleouts), len(c)))], 1)


def expected_time(t, rate, overhead_s):
    """E[wall seconds] of a t-second run under Poisson interruptions at
    ``rate`` per hour, each costing ``overhead_s`` to restart:
    (exp(rate t) - 1) (1 / rate + overhead), in hours; t when rate = 0.
    The rate is held at 50 / t hours, so a huge rate stays finite."""
    t = np.asarray(t, np.float64)
    lam = np.asarray(rate, np.float64)
    t_h = t / 3600.0
    safe = np.minimum(np.where(lam > 0, lam, 1.0),
                      np.where(t_h > 0, 50.0 / np.where(t_h > 0, t_h, 1.0),
                               np.inf))
    e_h = np.expm1(safe * t_h) * (1.0 / safe + overhead_s / 3600.0)
    return np.where(lam > 0, e_h * 3600.0, t)


def choose_regret(t, margin, prices, rates, overhead_s, scaleouts,
                  t_max, k_served) -> float:
    """The least relative change to the reference's grid under which the
    choose rule picks the served choice, as a share.

    With a deadline the served choice must meet it (its bound over the
    deadline reads as its share of the deadline) and no choice may be
    both cheaper (by a share of the served cost) and inside the deadline
    (by a share of the deadline): each such choice reads as the smaller
    of its two margins.  Without a deadline it reads as the cheapest
    choice's saving over the served cost.  When no choice meets the
    deadline, the rule takes the fastest bound, and the served bound's
    excess over it counts instead, if that reads less.  A near tie, or a
    bound near the deadline, therefore reads small.

    t [M, S] runtimes, margin [M], prices [M, P], rates [P]; the flat
    index runs machine, then placement, then scale-out."""
    S = np.asarray(scaleouts, np.float64)
    et = expected_time(t[:, None, :], rates[None, :, None], overhead_s)
    cost = (prices[:, :, None] * (et / 3600.0) * S).reshape(-1)
    bound = (et + margin[:, None, None]).reshape(-1)
    c, b = cost[k_served], bound[k_served]
    saving = (c - cost) / c
    if t_max is None or math.isnan(t_max):
        return max(float(saving.max()), 0.0)
    inside = (t_max - bound) / t_max
    better = np.minimum(saving, inside)
    rule = max(float(better.max()), (b - t_max) / t_max, 0.0)
    if (bound <= t_max).any():
        return rule
    return min(rule, (b - bound.min()) / b)


