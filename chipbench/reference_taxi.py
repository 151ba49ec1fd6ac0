"""The plain reference of the ``nyc_taxi_duration`` configuration: a trip-time
regression through the regression selector's default zoo, written
straightforwardly in float64 numpy.

Imports nothing of ``transmogrifai_tpu`` and takes nothing the program made.
From ``chipbench.reference`` by import: SanityChecker's variance, label- and
feature-correlation rules, the holdout and fold splits, the quantile bins,
the documented key walk of a forest's rounds and the level-wise histogram
tree. Here:

- the vectorizers of the configuration's types, as ``transmogrify`` lays
  them out at its defaults: an Integral column's value and null indicator;
  a DateTime's (sin, cos) of its HourOfDay phase, taken from the epoch
  milliseconds in float64, and null indicator; a PickList's top 20 values
  of support 10 or more (most frequent first, ties in text order), OTHER
  and null; a Geolocation's latitude, longitude, accuracy and null;
- SanityChecker on the rows it reads: a sample of 1,000,000 drawn by
  ``default_rng(42)`` where the table holds more;
- least squares with the elastic net at every grid point, SOLVED: columns
  and target standardized by the fold's training rows (a column constant
  among them gets weight 0), ``np.linalg.solve`` of ``Q + reg I`` for a
  pure-L2 point, cyclic coordinate descent to 1e-13 for an elastic-net one;
- squared-loss trees: a forest's trees grow on the label less the fold's
  mean (Poisson(1) row weights, a 0.7 column mask, ``reg_lambda`` 1e-3), its
  prediction that mean plus the trees' mean; boosting from the fold's mean
  at 0.3, ``reg_lambda`` 1. The histograms sum operands rounded as the
  configuration states (``pipeline.tree_operands``), the leaves come from
  the operands as they are, both in float64;
- the fold's RMSE; the winner (least mean RMSE), its refit on every
  training row, its holdout predictions.

``lowp=True`` is the CONTROL: the feature matrix rounded to bfloat16, the
standardized columns rounded to bfloat16 before the Gram's products, float8
tree operands.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from chipbench import reference as base
from chipbench.reference import (bin_codes, bin_edges, cv_folds, grow_tree,
                                 holdout_split, lowp_round, predict_tree,
                                 tree_keys)

MS_PER_DAY = 86_400_000.0
TOP_K, MIN_SUPPORT = 20, 10            # transmogrify's pivot defaults
SANITY_ROWS, SANITY_SEED = 1_000_000, 42   # SanityChecker's row sample
CD_TOL, CD_MAX_SWEEPS = 1e-13, 100_000
#: transmogrify's order of the blocks it combines, by the type's block
BLOCKS = ("integral", "date", "pivot", "geolocation")
_BLOCK_OF = {"Integral": "integral", "Date": "date", "DateTime": "date",
             "PickList": "pivot", "Geolocation": "geolocation"}
LINEAR, FOREST, BOOSTING = ("least_squares", "forest_regression",
                            "boosting_regression")


# ---------------------------------------------------------------------------
# feature engineering
# ---------------------------------------------------------------------------

@dataclass
class FeFit:
    """What fitting the vectorizers learns: each block's columns in order,
    the pivots' categories, the width."""
    columns: list            # [(block, name, spec)] in the vector's order
    categories: dict         # pivot column -> its kept values
    width: int = 0


def top_values(values: np.ndarray) -> list:
    """A pivot's kept values: support of ``MIN_SUPPORT`` or more, most
    frequent first, ties in text order, at most ``TOP_K``."""
    vals, counts = np.unique(values[np.not_equal(values, None)].astype(str),
                             return_counts=True)
    order = sorted((-c, v) for v, c in zip(vals.tolist(), counts.tolist())
                   if c >= MIN_SUPPORT)
    return [v for _, v in order[:TOP_K]]


def fit_fe(table, dataset: dict) -> FeFit:
    cols = [(_BLOCK_OF[spec["type"]], name, spec)
            for name, spec in dataset["types"].items()]
    cols.sort(key=lambda c: BLOCKS.index(c[0]))       # stable within a block
    cats = {name: top_values(table.cats[name])
            for block, name, _ in cols if block == "pivot"}
    width = sum({"integral": 2, "date": 3, "geolocation": 4}.get(b, 0)
                for b, _, _ in cols) + sum(len(c) + 2 for c in cats.values())
    return FeFit(columns=cols, categories=cats, width=width)


def hour_of_day(ms: np.ndarray) -> np.ndarray:
    """The HourOfDay phase of epoch milliseconds, float64."""
    return np.mod(np.asarray(ms, np.float64), MS_PER_DAY) / MS_PER_DAY \
        * 2.0 * np.pi


def apply_fe(table, fit: FeFit) -> np.ndarray:
    """The un-checked feature matrix, float32 ``[n, fit.width]`` (no nulls
    are generated: every null indicator is 0 and no fill is used)."""
    n = table.n_rows
    out = np.zeros((n, fit.width), np.float32)
    j = 0
    for block, name, spec in fit.columns:
        if block == "integral":
            out[:, j] = table.nums[name]
            j += 2
        elif block == "date":
            theta = hour_of_day(table.nums[name])
            out[:, j], out[:, j + 1] = np.sin(theta), np.cos(theta)
            j += 3
        elif block == "pivot":
            cats = fit.categories[name]
            v = table.cats[name]
            for i, c in enumerate(cats):
                out[:, j + i] = v == c
            other = np.not_equal(v, None) & ~np.isin(v.astype(str), cats)
            out[:, j + len(cats)] = other
            out[:, j + len(cats) + 1] = np.equal(v, None)
            j += len(cats) + 2
        else:
            out[:, j] = table.nums[spec["lat"]]
            out[:, j + 1] = table.nums[spec["lon"]]
            out[:, j + 2] = float(spec["accuracy"])
            j += 4
    return out


def sanity_keep(X: np.ndarray, y: np.ndarray):
    """``chipbench.reference.sanity_keep`` on the rows SanityChecker
    reads."""
    n = X.shape[0]
    if n > SANITY_ROWS:
        idx = np.sort(np.random.default_rng(SANITY_SEED).choice(
            n, size=SANITY_ROWS, replace=False))
        X, y = X[idx], y[idx]
    return base.sanity_keep(X, y)


# ---------------------------------------------------------------------------
# least squares with the elastic net (host, float64)
# ---------------------------------------------------------------------------

def _cd(Q, q, l1, l2, W):
    """Cyclic coordinate descent on ``½ WᵀQW - qᵀW + l2 ½‖W‖² + l1‖W‖₁``
    until no coefficient moves more than ``CD_TOL`` in a sweep."""
    W = W.copy()
    for _ in range(CD_MAX_SWEEPS):
        r = q - Q @ W
        moved = 0.0
        for j in range(q.size):
            z = r[j] + Q[j, j] * W[j]
            new = np.sign(z) * max(abs(z) - l1, 0.0) / (Q[j, j] + l2)
            r -= Q[:, j] * (new - W[j])
            moved = max(moved, abs(new - W[j]))
            W[j] = new
        if moved <= CD_TOL:
            break
    return W


def linreg_fit(X: np.ndarray, y: np.ndarray, grid: list,
               lowp: bool = False) -> list:
    """``[(W [d], b)]`` a grid point, in original feature space."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    n, d = X.shape
    mu, var = X.mean(axis=0), X.var(axis=0)
    live = (X.max(axis=0) > X.min(axis=0)) & (var > 1e-12)
    sd = np.where(live, np.sqrt(np.maximum(var, 1e-300)), 1.0)
    Xs = np.where(live, (X - mu) / sd, 0.0)
    y_mean, y_sd = y.mean(), np.sqrt(max(y.var(), 1e-12))
    ys = (y - y_mean) / y_sd
    if lowp:
        Xs = lowp_round(Xs.astype(np.float32)).astype(np.float64)
    Q = Xs.T @ Xs / n
    q = Xs.T @ ys / n
    Q[~live, :] = 0.0
    Q[:, ~live] = 0.0
    Q[~live, ~live] = 1.0
    q[~live] = 0.0
    out = []
    for point in grid:
        reg = float(point["reg_param"])
        en = float(point.get("elastic_net_param", 0.0))
        l2 = reg * (1.0 - en)
        W = np.linalg.solve(Q + l2 * np.eye(d), q)
        if en > 0:
            W = _cd(Q, q, reg * en, l2, W)
        W_raw = W * y_sd / sd
        out.append((W_raw, y_mean - mu @ W_raw))
    return out


def linreg_predict(params, X: np.ndarray) -> list:
    X = np.asarray(X, np.float64)
    return [X @ W + b for W, b in params]


# ---------------------------------------------------------------------------
# squared-loss trees (host, float64 statistics)
# ---------------------------------------------------------------------------

class TreeRegRef:
    """A squared-loss tree family over its grid (depths differ; a forest's
    trees are shared: a shallower tree of the same rows is a deeper one's
    first levels)."""

    def __init__(self, trainer: str, grid: list, lowp: bool = False,
                 operands: str = "exact"):
        self.forest = trainer == FOREST
        self.grid = [dict(g) for g in grid]
        self.rounds = [int(g.get("num_trees", g.get("num_rounds")))
                       for g in self.grid]
        self.depths = [int(g["max_depth"]) for g in self.grid]
        self.lowp, self.operands = lowp, operands

    def _q(self, x):
        if self.lowp:
            return base._fp8(x)
        if self.operands == "bfloat16":
            return lowp_round(np.asarray(x, np.float32)).astype(np.float64)
        return x

    def fit_predict(self, Xb, y, Xb_eval: list, only=None) -> list:
        """Train every grid point (or ``only`` one) on ``(Xb, y)``; returns
        per grid point its predictions of each matrix of ``Xb_eval``."""
        n, d = Xb.shape
        idx = range(len(self.grid)) if only is None else [only]
        y32 = np.asarray(y, np.float32)
        # the fold's mean as the program holds it (float32)
        mean = np.float32(np.mean(np.asarray(y, np.float64)))
        res = {}
        if self.forest:
            rounds = self.rounds[0]
            depths = sorted({self.depths[i] for i in idx})
            sums = {dp: [np.zeros(e.shape[0]) for e in Xb_eval]
                    for dp in depths}
            for rw, fm in tree_keys(42, rounds, n, d, 0.7, True):
                rw32 = rw.astype(np.float32)
                g = ((mean - y32) * rw32).astype(np.float64)
                trees = grow_tree(Xb, g, rw32.astype(np.float64), fm,
                                  depths, 1e-3, self._q)
                for dp in depths:
                    f, b, leaves, _ = trees[dp]
                    for j, e in enumerate(Xb_eval):
                        sums[dp][j] += predict_tree(e, f, b, leaves)
            for i in idx:
                res[i] = [float(mean) + s / rounds
                          for s in sums[self.depths[i]]]
        else:
            ones = np.ones(d)
            for i in idx:
                margin = np.full(n, mean, np.float32)
                ev = [np.full(e.shape[0], float(mean)) for e in Xb_eval]
                for _ in range(self.rounds[i]):
                    g = (margin - y32).astype(np.float64)
                    tree = grow_tree(Xb, g, np.ones(n), ones,
                                     (self.depths[i],), 1.0, self._q)
                    f, b, leaves, node = tree[self.depths[i]]
                    margin = margin + np.float32(0.3) * leaves[node].astype(
                        np.float32)
                    for j, e in enumerate(Xb_eval):
                        ev[j] += 0.3 * predict_tree(e, f, b, leaves)
                res[i] = ev
        return [res.get(i) for i in range(len(self.grid))]


# ---------------------------------------------------------------------------
# the whole train
# ---------------------------------------------------------------------------

def rmse(pred: np.ndarray, y: np.ndarray) -> float:
    return float(np.sqrt(np.mean((np.asarray(pred, np.float64) - y) ** 2)))


@dataclass
class RefTrain:
    fit: FeFit
    keep: np.ndarray
    unsure: np.ndarray
    cv: dict                  # (family index, grid index) -> mean fold RMSE
    X: np.ndarray = None      # checked matrix, all rows (float32)
    y: np.ndarray = None
    train_idx: np.ndarray = None
    holdout_idx: np.ndarray = None
    edges: np.ndarray = None
    refit_pred: dict = field(default_factory=dict)


def _trees(fam: dict, pcfg: dict, lowp: bool) -> TreeRegRef:
    return TreeRegRef(fam["trainer"], fam["grid"], lowp,
                      pcfg.get("tree_operands", "exact"))


def reference_train(table, config: dict, *, sweep: bool = True,
                    lowp: bool = False) -> RefTrain:
    """Fit the vectorizers and SanityChecker on ``table``, split, and (with
    ``sweep``) run the whole k-fold sweep under the fold RMSE."""
    pcfg = config["pipeline"]
    fit = fit_fe(table, config["dataset"])
    Xfull = apply_fe(table, fit)
    y = np.asarray(table.label, np.float64)
    if lowp:
        Xfull = lowp_round(Xfull)
    keep, unsure = sanity_keep(Xfull, y)
    X = Xfull[:, keep]
    del Xfull
    seed, k = int(pcfg["split_seed"]), int(pcfg["n_folds"])
    tr, ho = holdout_split(table.n_rows, float(pcfg["holdout_fraction"]),
                           seed)
    ref = RefTrain(fit=fit, keep=keep, unsure=unsure, cv={}, X=X, y=y,
                   train_idx=tr, holdout_idx=ho)
    Xt, yt = X[tr], y[tr]
    ref.edges = bin_edges(Xt)
    if not sweep:
        return ref
    folds = cv_folds(tr.size, k, seed)
    Xbt = bin_codes(Xt, ref.edges)
    for ci, fam in enumerate(pcfg["zoo"]):
        G = len(fam["grid"])
        vals = np.zeros((k, G))
        for f, (a, b) in enumerate(folds):
            if fam["trainer"] == LINEAR:
                preds = linreg_predict(
                    linreg_fit(Xt[a], yt[a], fam["grid"], lowp), Xt[b])
            else:
                preds = [p[0] for p in _trees(fam, pcfg, lowp).fit_predict(
                    Xbt[a], yt[a], [Xbt[b]])]
            vals[f] = [rmse(p, yt[b]) for p in preds]
        for g in range(G):
            ref.cv[(ci, g)] = float(vals[:, g].mean())
    return ref


def reference_refit(ref: RefTrain, config: dict, ci: int, gi: int,
                    Xs: list, lowp: bool = False) -> list:
    """Refit zoo entry ``ci``'s grid point ``gi`` on every training row, as
    the selector does with its winner, and predict each checked matrix of
    ``Xs``."""
    pcfg = config["pipeline"]
    fam = pcfg["zoo"][ci]
    Xt, yt = ref.X[ref.train_idx], ref.y[ref.train_idx]
    if fam["trainer"] == LINEAR:
        params = linreg_fit(Xt, yt, [fam["grid"][gi]], lowp)
        return [linreg_predict(params, X)[0] for X in Xs]
    return _trees(fam, pcfg, lowp).fit_predict(
        bin_codes(Xt, ref.edges), yt, [bin_codes(X, ref.edges) for X in Xs],
        only=gi)[gi]
