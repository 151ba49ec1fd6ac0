"""The plain reference of the ``covtype_multi`` configuration: a seven-class
train through the multiclass selector's zoo, written straightforwardly.

Imports nothing of ``transmogrifai_tpu`` and takes nothing the program made.
What is the same for every table of real columns comes from
``chipbench.reference`` by import: the vectorizers (mean fill and null
indicator a real column), SanityChecker's variance, label-correlation and
feature-correlation rules for a numeric label, the holdout and fold splits,
the quantile bins and the documented key walk of a forest's rounds. Here:

- softmax regression: every grid point by 200 full-batch Adam(0.1) steps
  from zero of the mean cross-entropy plus the elastic-net term, on
  standardized columns, float32 at ``highest``; the refit starts from the
  fold-averaged parameters, as the selector's does;
- random forest past two classes: K one-vs-all level-wise histogram trees a
  member on the one-hot targets (gradient ``-t`` and hessian 1 times the
  member's Poisson(1) row weight, ``reg_lambda`` 1e-3, a 0.7 column mask),
  a member's class vote the leaf's weighted class frequency, a row's class
  scores the members' mean votes clipped to [0, 1], its probabilities those
  over their sum. The histograms sum operands rounded as the configuration
  states (``pipeline.tree_operands``); the operands are whole numbers (a
  Poisson count, times 0 or 1), which bfloat16 holds exactly, so the sums
  are counts, taken as integer counts of repeated rows (exact, as float64
  sums of them would be), and the gains are evaluated in float64;
- the sweep's metric: the support-weighted F1 of a fold's argmax, the
  harmonic mean of weighted precision and weighted recall.

``lowp=True`` is the CONTROL: the feature matrix rounded to bfloat16,
bfloat16 products in the descent, float8 tree operands.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from chipbench import reference as base
from chipbench.reference import (N_BINS, apply_fe, bin_codes, bin_edges,
                                 cv_folds, fit_fe, holdout_split, lowp_round,
                                 sanity_keep, tree_keys)

STEPS = 200


def weighted_prf(y: np.ndarray, yhat: np.ndarray, K: int) -> tuple:
    """``(precision, recall, F1, error)`` weighted by class support; F1 is
    the harmonic mean of the weighted precision and recall."""
    conf = np.zeros((K, K))
    np.add.at(conf, (y, yhat), 1.0)
    support, predicted, hit = conf.sum(1), conf.sum(0), np.diag(conf)
    prec = np.divide(hit, predicted, out=np.zeros(K), where=predicted > 0)
    rec = np.divide(hit, support, out=np.zeros(K), where=support > 0)
    n = max(support.sum(), 1e-12)
    p, r = float((prec * support).sum() / n), float((rec * support).sum() / n)
    f1 = 0.0 if p + r == 0 else 2 * p * r / (p + r)
    return p, r, f1, 1.0 - float(hit.sum() / n)


def row_weight(y: np.ndarray, yhat: np.ndarray, K: int) -> float:
    """How many ordinary rows ONE row that changes its predicted class
    weighs in the support-weighted precision of ``yhat``: the largest, over
    the classes predicted for at least one row, of the class's support over
    the number of rows predicted as it. The weighted precision sums
    ``support_c / n * hit_c / predicted_c``, so a row that comes to or goes
    from class ``c`` moves it by ``support_c / predicted_c`` times ``1 / n``
    (and the F1 by about half of that): 1 where a class is predicted as
    often as it occurs, 467 where a class that holds 10,737 of a fold's
    rows is predicted for 23 of them. A class predicted for no row weighs
    nothing until a first row reaches it."""
    support = np.bincount(y, minlength=K).astype(np.float64)
    predicted = np.bincount(yhat, minlength=K).astype(np.float64)
    on = predicted > 0
    return float((support[on] / predicted[on]).max())


# ---------------------------------------------------------------------------
# softmax regression (device, float32 at highest precision)
# ---------------------------------------------------------------------------

def _adam_softmax(Xs, onehot, reg, en, W0, b0, lowp):
    import jax
    import jax.numpy as jnp
    cd = jnp.bfloat16 if lowp else jnp.float32
    Xc = Xs.astype(cd)

    def objective(params):
        W, b = params
        z = (Xc @ W.astype(cd)).astype(jnp.float32) + b
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(z, axis=-1) * onehot,
                                 axis=-1))
        return loss + reg * ((1.0 - en) * 0.5 * jnp.sum(W ** 2)
                             + en * jnp.sum(jnp.abs(W)))

    def step(carry, t):
        p, m, v = carry
        g = jax.grad(objective)(p)
        m = jax.tree_util.tree_map(lambda a, b_: 0.9 * a + 0.1 * b_, m, g)
        v = jax.tree_util.tree_map(
            lambda a, b_: 0.999 * a + 0.001 * b_ * b_, v, g)
        mh = jax.tree_util.tree_map(lambda a: a / (1 - 0.9 ** t), m)
        vh = jax.tree_util.tree_map(lambda a: a / (1 - 0.999 ** t), v)
        p = jax.tree_util.tree_map(
            lambda a, b_, c: a - 0.1 * b_ / (jnp.sqrt(c) + 1e-8), p, mh, vh)
        return (p, m, v), 0.0

    zeros = jax.tree_util.tree_map(jnp.zeros_like, (W0, b0))
    (p, _, _), _ = jax.lax.scan(
        step, ((W0, b0), zeros, zeros),
        jnp.arange(1, STEPS + 1, dtype=jnp.float32))
    return p


def _softmax_grid(Xs, onehot, regs, ens, W0, b0, lowp):
    import jax
    return jax.vmap(lambda r, e, w0, b0_: _adam_softmax(
        Xs, onehot, r, e, w0, b0_, lowp))(regs, ens, W0, b0)


def softmax_fit(X, y, grid: list, K: int, warm=None, lowp: bool = False):
    """``[(W [d, K], b [K])]`` a grid point, in original feature space.
    ``warm``: the same, where the descent starts (else zero)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        mu, sd = base._standardize(jnp, X)
        Xs = (X - mu) / sd
        G, d = len(grid), X.shape[1]
        regs = jnp.asarray([g["reg_param"] for g in grid], jnp.float32)
        ens = jnp.asarray([g.get("elastic_net_param", 0.0) for g in grid],
                          jnp.float32)
        if warm is None:
            W0 = jnp.zeros((G, d, K), jnp.float32)
            b0 = jnp.zeros((G, K), jnp.float32)
        else:       # original space -> this fit's standardized space
            W0 = jnp.stack([W * sd[:, None] for W, _ in warm])
            b0 = jnp.stack([b + mu @ W for W, b in warm])
        onehot = jax.nn.one_hot(y.astype(jnp.int32), K)
        Ws, bs = base._jit(_softmax_grid, ("lowp",))(
            Xs, onehot, regs, ens, W0, b0, lowp=lowp)
        return [(Ws[g] / sd[:, None], bs[g] - (mu / sd) @ Ws[g])
                for g in range(G)]


def softmax_logits(params, X) -> np.ndarray:
    """``[G, n, K]`` class logits."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(jnp.stack([X @ W + b for W, b in params]),
                          np.float64)


def softmax_probs(z: np.ndarray) -> np.ndarray:
    e = np.exp(z - z.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# one-vs-all forest (host, counts)
# ---------------------------------------------------------------------------

class _Rows:
    """The (repeated) training rows of one forest member, laid out for
    counting: per feature the rows whose code is not the feature's most
    frequent one, with those codes. A histogram's count of the most frequent
    code is the node's total less the others, so a 0/1 column costs its
    ones and not its rows."""

    def __init__(self, Xb: np.ndarray, select=None):
        self.d = Xb.shape[1]
        self.mode, self.rows, self.codes = [], [], []
        for f in range(self.d):
            col = Xb[:, f]
            mode = int(np.argmax(np.bincount(col, minlength=N_BINS)))
            rows = np.nonzero(col != mode)[0]
            if select is not None:
                rows = rows[select[rows]]
            self.mode.append(mode)
            self.rows.append(rows)
            self.codes.append(col[rows].astype(np.int64))
        #: a feature of two codes (a 0/1 column): its other code, else None
        self.other = [int(c[0]) if c.size and np.all(c == c[0]) else None
                      for c in self.codes]

    def of(self, select: np.ndarray) -> "_Rows":
        """The same layout over the rows ``select`` (a mask) marks."""
        out = object.__new__(_Rows)
        out.d, out.mode, out.other = self.d, self.mode, self.other
        keep = [select[r] for r in self.rows]
        out.rows = [r[k] for r, k in zip(self.rows, keep)]
        out.codes = [c[k] for c, k in zip(self.codes, keep)]
        return out

    def counts(self, node: np.ndarray, total: np.ndarray) -> np.ndarray:
        """``[nodes, d, B]``: how many of the rows fall in each node and
        bin of each feature; ``node`` is every row's node, ``total`` the
        rows a node."""
        n_nodes = total.size
        out = np.zeros((n_nodes, self.d, N_BINS), np.int64)
        for f in range(self.d):
            at = node[self.rows[f]]
            if self.other[f] is not None:
                cnt = np.bincount(at, minlength=n_nodes)
                out[:, f, self.other[f]] = cnt
                out[:, f, self.mode[f]] = total - cnt
                continue
            cnt = np.bincount(at * N_BINS + self.codes[f],
                              minlength=n_nodes * N_BINS
                              ).reshape(n_nodes, N_BINS)
            out[:, f, :] = cnt
            out[:, f, self.mode[f]] = total - cnt.sum(axis=1)
        return out


def _splits(hg, hh, fmask, lam):
    """``reference._best_splits`` over the nodes that can split at all: one
    with none or all of its rows in the class, or a single row, has no
    positive gain (x^2 / (x + lam) is superadditive) and stays a leaf. The
    masked features are left out before, not after, the search."""
    n_nodes = hg.shape[0]
    G, H = hg[:, 0, :].sum(axis=1), hh[:, 0, :].sum(axis=1)
    live = np.nonzero((G != 0) & (G != -H) & (H >= 2))[0]
    feat = np.full(n_nodes, -1, np.int64)
    bin_ = np.full(n_nodes, N_BINS, np.int64)
    kept = np.nonzero(np.asarray(fmask) > 0)[0]
    for a in range(0, live.size, 256):
        part = live[a:a + 256]
        f, b = base._best_splits(hg[part][:, kept], hh[part][:, kept],
                                 np.ones(kept.size), lam)
        feat[part] = np.where(f < 0, -1, kept[np.clip(f, 0, None)])
        bin_[part] = b
    return feat, bin_


def _route(Xr: np.ndarray, node, feat, bin_):
    """Every row's node a level down: right where its code of the node's
    split feature is over the split bin, left elsewhere (and where the node
    does not split)."""
    f = feat[node]
    right = np.zeros(node.size, bool)
    for j in np.unique(f[f >= 0]):
        m = np.nonzero(f == j)[0]
        right[m] = Xr[m, j] > bin_[node[m]]
    return node * 2 + right


def grow_ova_round(Xb, y, rw, fmask, depths, K: int, lam: float, quantize):
    """One forest member: for each class the level-wise tree on the one-hot
    target. Returns ``{depth: [(feats, bins, leaves) a class]}``. Operands
    are ``quantize``d; they are whole numbers (checked), so a weighted sum
    is a count over rows repeated by their weight."""
    g_all, h_all = quantize(-rw), quantize(rw)
    if not (np.array_equal(h_all, np.rint(h_all))
            and np.array_equal(g_all, -h_all)):
        raise ValueError("one-vs-all forest operands must be whole numbers")
    rep = np.repeat(np.arange(Xb.shape[0]), h_all.astype(np.int64))
    Xr, yr = np.asfortranarray(Xb[rep]), y[rep]
    every = _Rows(Xr)
    out = {dp: [] for dp in depths}
    for c in range(K):
        is_c = yr == c
        of_c = every.of(is_c)
        node = np.zeros(rep.size, np.int64)
        feats, bins = [], []
        for level in range(max(depths)):
            n_nodes = 2 ** level
            total = np.bincount(node, minlength=n_nodes)
            total_c = np.bincount(node[is_c], minlength=n_nodes)
            hh = every.counts(node, total)
            hg = -of_c.counts(node, total_c)
            feat, bin_ = _splits(hg, hh, fmask, lam)
            feats.append(feat)
            bins.append(bin_)
            node = _route(Xr, node, feat, bin_)
            if level + 1 in depths:
                n_leaves = 2 ** (level + 1)
                lh = np.bincount(node, minlength=n_leaves)
                lc = np.bincount(node[is_c], minlength=n_leaves)
                out[level + 1].append((list(feats), list(bins),
                                       lc / (lh + lam)))
    return out


class ForestOvaRef:
    """The one-vs-all forest over its grid (depths differ, trees are
    shared: a shallower tree of the same rows is a deeper one's first
    levels)."""

    def __init__(self, grid: list, K: int, lowp: bool = False,
                 operands: str = "exact"):
        self.grid = [dict(g) for g in grid]
        self.K, self.lowp, self.operands = K, lowp, operands
        self.rounds = int(self.grid[0].get("num_trees",
                                           self.grid[0].get("num_rounds")))
        self.depths = [int(g["max_depth"]) for g in self.grid]

    def _q(self, x):
        if self.lowp:
            return base._fp8(x)
        if self.operands == "bfloat16":
            return lowp_round(x.astype(np.float32)).astype(np.float64)
        return x

    def fit_scores(self, Xb, y, Xb_eval: list, only=None) -> list:
        """Train every grid point (or ``only`` one) on ``(Xb, y)``; returns
        per grid point the ``[n, K]`` class scores of each matrix of
        ``Xb_eval``: the members' mean votes clipped to [0, 1]."""
        n, d = Xb.shape
        idx = range(len(self.grid)) if only is None else [only]
        depths = sorted({self.depths[i] for i in idx})
        sums = {dp: [np.zeros((e.shape[0], self.K)) for e in Xb_eval]
                for dp in depths}
        y = y.astype(np.int64)
        for rw, fm in tree_keys(42, self.rounds, n, d, 0.7, True):
            trees = grow_ova_round(Xb, y, rw, fm, depths, self.K, 1e-3,
                                   self._q)
            for dp in depths:
                for c, (f, b, leaves) in enumerate(trees[dp]):
                    for j, e in enumerate(Xb_eval):
                        sums[dp][j][:, c] += base.predict_tree(e, f, b,
                                                               leaves)
        res = {i: [np.clip(s / self.rounds, 0.0, 1.0)
                   for s in sums[self.depths[i]]] for i in idx}
        return [res.get(i) for i in range(len(self.grid))]


def forest_probs(s: np.ndarray) -> np.ndarray:
    return s / np.maximum(s.sum(axis=-1, keepdims=True), 1e-12)


# ---------------------------------------------------------------------------
# the whole train
# ---------------------------------------------------------------------------

@dataclass
class RefTrain:
    fit: object
    keep: np.ndarray
    unsure: np.ndarray
    cv: dict                  # (family index, grid index) -> mean fold F1
    # the same keys -> the most one validation row weighs in a fold's F1
    row_weight: dict = field(default_factory=dict)
    K: int = 0
    X: object = None          # checked matrix, all rows
    y: np.ndarray = None
    train_idx: np.ndarray = None
    holdout_idx: np.ndarray = None
    fold_params: dict = field(default_factory=dict)
    edges: np.ndarray = None
    refit_probs: dict = field(default_factory=dict)


def _forest(fam: dict, pcfg: dict, K: int, lowp: bool) -> ForestOvaRef:
    return ForestOvaRef(fam["grid"], K, lowp,
                        pcfg.get("tree_operands", "exact"))


def reference_train(table, config: dict, *, sweep: bool = True,
                    lowp: bool = False) -> RefTrain:
    """Fit the vectorizers and SanityChecker on ``table``, split, and (with
    ``sweep``) run the whole k-fold sweep under the fold F1."""
    import jax.numpy as jnp
    pcfg = config["pipeline"]
    fit = fit_fe(table)
    Xfull = apply_fe(table, fit)
    y = np.asarray(table.label, np.float64)
    K = int(config["n_classes"])
    if lowp:
        Xfull = lowp_round(Xfull)
    keep, unsure = sanity_keep(Xfull, y)
    X = Xfull[:, keep]
    del Xfull
    seed, k = int(pcfg["split_seed"]), int(pcfg["n_folds"])
    tr, ho = holdout_split(table.n_rows, float(pcfg["holdout_fraction"]),
                           seed)
    ref = RefTrain(fit=fit, keep=keep, unsure=unsure, cv={}, K=K, X=X, y=y,
                   train_idx=tr, holdout_idx=ho)
    Xt, yt = X[tr], y[tr]
    ref.edges = bin_edges(Xt)
    if not sweep:
        return ref
    folds = cv_folds(tr.size, k, seed)
    Xbt = bin_codes(Xt, ref.edges)
    for ci, fam in enumerate(pcfg["zoo"]):
        G = len(fam["grid"])
        vals, weight = np.zeros((k, G)), np.zeros((k, G))
        for f, (a, b) in enumerate(folds):
            yb = yt[b].astype(np.int64)
            if fam["trainer"] == "logistic":
                params = softmax_fit(jnp.asarray(Xt[a], jnp.float32),
                                     jnp.asarray(yt[a], jnp.float32),
                                     fam["grid"], K, lowp=lowp)
                ref.fold_params.setdefault(ci, []).append(params)
                z = softmax_logits(params, jnp.asarray(Xt[b], jnp.float32))
                scores = [z[g] for g in range(G)]
            else:
                scores = [s[0] for s in _forest(fam, pcfg, K, lowp)
                          .fit_scores(Xbt[a], yt[a], [Xbt[b]])]
            for g, s in enumerate(scores):
                yhat = np.argmax(s, axis=1)
                vals[f, g] = weighted_prf(yb, yhat, K)[2]
                weight[f, g] = row_weight(yb, yhat, K)
        for g in range(G):
            ref.cv[(ci, g)] = float(vals[:, g].mean())
            ref.row_weight[(ci, g)] = float(weight[:, g].max())
    return ref


def reference_refit(ref: RefTrain, config: dict, ci: int, gi: int,
                    Xs: list, lowp: bool = False) -> list:
    """Refit zoo entry ``ci``'s grid point ``gi`` on every training row, as
    the selector does with its winner, and give the ``[n, K]`` class
    probabilities of each checked matrix of ``Xs``."""
    import jax.numpy as jnp
    pcfg = config["pipeline"]
    fam = pcfg["zoo"][ci]
    Xt, yt = ref.X[ref.train_idx], ref.y[ref.train_idx]
    if fam["trainer"] == "logistic":
        fp = ref.fold_params[ci]       # fold-averaged warm start
        warm = [(sum(p[gi][0] for p in fp) / len(fp),
                 sum(p[gi][1] for p in fp) / len(fp))]
        params = softmax_fit(jnp.asarray(Xt, jnp.float32),
                             jnp.asarray(yt, jnp.float32),
                             [fam["grid"][gi]], ref.K, warm=warm, lowp=lowp)
        return [softmax_probs(softmax_logits(
            params, jnp.asarray(X, jnp.float32))[0]) for X in Xs]
    scores = _forest(fam, pcfg, ref.K, lowp).fit_scores(
        bin_codes(Xt, ref.edges), yt,
        [bin_codes(X, ref.edges) for X in Xs], only=gi)[gi]
    return [forest_probs(s) for s in scores]
