"""The plain reference: the same AutoML semantics, written straightforwardly.

Independent of ``transmogrifai_tpu``: it imports nothing of the program and
takes nothing the program made (no weights, edges, vocabularies or kept
columns). It follows the published algorithm of each stage:

- vectorizers: mean fill + null indicator per real column (a configuration
  with text columns brings a reference of its own beside its file);
- SanityChecker: population variance, Pearson correlation with the label,
  feature-feature correlation (the later column of a pair goes), at the
  default thresholds;
- splits: ``default_rng(seed).permutation`` for the holdout and the folds;
- linear families: 15 damped Newton steps for pure-L2 logistic regression,
  200 full-batch Adam(0.1) steps for the L1 points and the hinge loss, on
  standardized columns, from zero;
- tree families: level-wise histogram trees on 64 quantile bins with the
  XGBoost gain; Poisson(1) bootstrap rows and a 0.7 column mask per tree for
  forests, logistic boosting at 0.3 for GBT;
- validation metric: area under the precision-recall curve from 4,096
  score bins, as the sweep defines it; holdout metrics from the exact sort.

Linear algebra runs in float32 at ``highest`` matmul precision, tree
statistics in float64 on the host, from operands in the precision the
configuration states (``pipeline.tree_operands``). ``lowp=True`` is the CONTROL: the same
computation one precision step down (bfloat16 for the float32 linear
algebra, float8 gradients for the bfloat16 histogram operands); it must
come out not correct.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

N_BINS = 64
SWEEP_BINS = 4096
#: a zoo entry's ``trainer`` names its algorithm here, whatever class of the
#: program implements it
LINEAR, TREES = ("logistic", "hinge"), ("forest", "boosting")


# ---------------------------------------------------------------------------
# feature engineering
# ---------------------------------------------------------------------------

@dataclass
class FeFit:
    """What fitting the vectorizers learns from the training table."""
    means: dict
    width: int = 0


def fit_fe(table) -> FeFit:
    if table.cats:
        raise NotImplementedError(
            "this reference vectorizes real columns only; a configuration "
            "with text columns brings its own reference")
    means = {k: float(np.mean(v)) for k, v in table.nums.items()}
    return FeFit(means=means, width=2 * len(table.nums))


def apply_fe(table, fit: FeFit) -> np.ndarray:
    """The un-checked feature matrix, float32 ``[n, fit.width]``: per real
    column its value (no nulls are generated, so the mean fill is unused)
    and its null indicator (0)."""
    out = np.zeros((table.n_rows, fit.width), np.float32)
    for j, v in enumerate(table.nums.values()):
        out[:, 2 * j] = v
    return out


def sanity_keep(X: np.ndarray, y: np.ndarray, margin: float = 0.012):
    """``(keep mask, unsure mask)``: the columns SanityChecker keeps at its
    default thresholds, and those whose deciding statistic lies within
    ``margin`` (relative) of a threshold, which the comparison skips: the
    configuration states matrix products at the chip's default precision,
    whose bfloat16 operands move a correlation by up to 2 x 2^-8."""
    import jax
    import jax.numpy as jnp
    n, d = X.shape
    with jax.default_matmul_precision("highest"):
        Xd = jnp.asarray(X)
        mean = np.asarray(jnp.mean(Xd, axis=0), np.float64)
        Xc = Xd - jnp.asarray(mean, jnp.float32)
        var = np.asarray(jnp.mean(Xc * Xc, axis=0), np.float64)
        yc = jnp.asarray(y - y.mean(), jnp.float32)
        cov = np.asarray(Xc.T @ yc, np.float64) / n
        sd = np.sqrt(np.maximum(var, 1e-12))
        Z = Xc / jnp.asarray(sd, jnp.float32)
        C = np.asarray((Z.T @ Z) / n, np.float64)
    corr = cov / (sd * np.sqrt(max(y.var(), 1e-12)))
    drop = np.zeros(d, bool)
    unsure = np.zeros(d, bool)

    def rule(stat, thr, above=True):
        nonlocal drop, unsure
        hit = stat > thr if above else stat <= thr
        drop |= hit
        unsure |= np.abs(stat - thr) <= margin * max(thr, 1e-12)

    rule(var, 1e-5, above=False)
    rule(np.abs(corr), 0.95)
    A = np.abs(np.tril(C, -1))
    rule(A.max(axis=1), 0.99)
    return ~drop, unsure


# ---------------------------------------------------------------------------
# splits and metrics
# ---------------------------------------------------------------------------

def holdout_split(n: int, fraction: float, seed: int):
    perm = np.random.default_rng(seed).permutation(n)
    n_test = int(round(n * fraction))
    return np.sort(perm[n_test:]), np.sort(perm[:n_test])


def cv_folds(n: int, k: int, seed: int):
    perm = np.random.default_rng(seed).permutation(n)
    n_val = n // k
    rows = np.arange(n)
    out = []
    for f in range(k):
        val = np.sort(perm[f * n_val:(f + 1) * n_val])
        out.append((np.setdiff1d(rows, val), val))
    return out


def binned_aupr(y: np.ndarray, s: np.ndarray) -> float:
    """The sweep's validation metric: average precision over 4,096 equal
    score bins between the least and the greatest score."""
    s = np.asarray(s, np.float32)
    lo, hi = s.min(), s.max()
    b = np.clip(((s - lo) / max(hi - lo, np.float32(1e-12))
                 * np.float32(SWEEP_BINS - 1)).astype(np.int32),
                0, SWEEP_BINS - 1)
    pos = np.bincount(b, weights=y, minlength=SWEEP_BINS)[::-1]
    neg = np.bincount(b, weights=1.0 - y, minlength=SWEEP_BINS)[::-1]
    tp, fp = np.cumsum(pos), np.cumsum(neg)
    tpr = tp / max(tp[-1], 1e-12)
    prec = tp / np.maximum(tp + fp, 1e-12)
    return float(np.sum(prec * np.diff(np.concatenate([[0.0], tpr]))))


def exact_curves(y: np.ndarray, s: np.ndarray) -> tuple[float, float]:
    """``(auROC, auPR)`` from the exact descending sort, one vertex per
    group of tied scores; auPR as the step-wise average precision."""
    order = np.argsort(-s, kind="stable")
    ys, ss = y[order], s[order]
    tp, fp = np.cumsum(ys), np.cumsum(1.0 - ys)
    end = np.concatenate([ss[:-1] != ss[1:], [True]])
    tp, fp = tp[end], fp[end]
    tpr, fpr = tp / max(tp[-1], 1e-12), fp / max(fp[-1], 1e-12)
    prec = tp / np.maximum(tp + fp, 1e-12)
    tpr0, fpr0 = np.concatenate([[0.0], tpr]), np.concatenate([[0.0], fpr])
    auroc = np.sum(np.diff(fpr0) * (tpr0[1:] + tpr0[:-1]) * 0.5)
    return float(auroc), float(np.sum(prec * np.diff(tpr0)))


# ---------------------------------------------------------------------------
# linear families (device, float32 at highest precision)
# ---------------------------------------------------------------------------

def _standardize(jnp, X):
    mu = jnp.mean(X, axis=0)
    var = jnp.mean((X - mu) ** 2, axis=0)
    sd = jnp.sqrt(jnp.maximum(var, 1e-12))
    sd = jnp.where(sd < 1e-6, 1.0, sd)
    return mu, sd


def _newton_lr(Xs, y, reg, lowp):
    import jax
    import jax.numpy as jnp
    n, d = Xs.shape
    lam = reg * 0.5
    cd = jnp.bfloat16 if lowp else jnp.float32
    Xb = jnp.concatenate([Xs, jnp.ones((n, 1), Xs.dtype)], axis=1).astype(cd)
    pen = jnp.ones(d + 1, jnp.float32).at[-1].set(0.0)

    def step(uv, _):
        z = (Xb @ uv.astype(cd)).astype(jnp.float32)
        p = jax.nn.sigmoid(z)
        r = (p - y) / n
        R = jnp.maximum(p * (1.0 - p), 1e-6) / n
        g = (Xb.T @ r.astype(cd)).astype(jnp.float32) + lam * pen * uv
        H = ((Xb * R[:, None].astype(cd)).T @ Xb).astype(jnp.float32)
        H = H + jnp.diag(lam * pen + 1e-4)
        new = uv - jnp.linalg.solve(H, g)
        return jnp.where(jnp.all(jnp.isfinite(new)), new, uv), 0.0

    uv, _ = jax.lax.scan(step, jnp.zeros(d + 1, jnp.float32), None, length=15)
    return uv


def _adam(Xs, y, reg, en, W0, b0, hinge, lowp, steps=200):
    import jax
    import jax.numpy as jnp
    n = Xs.shape[0]
    cd = jnp.bfloat16 if lowp else jnp.float32
    Xc = Xs.astype(cd)

    def objective(params):
        W, b = params
        z = (Xc @ W.astype(cd)).astype(jnp.float32) + b
        if hinge:
            loss = jnp.mean(jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * z[:, 0]))
        else:
            logp = jax.nn.log_softmax(z, axis=-1)
            loss = -jnp.mean(jnp.where(y > 0.5, logp[:, 1], logp[:, 0]))
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
        jnp.arange(1, steps + 1, dtype=jnp.float32))
    return p


def _newton_grid(Xs, y, regs, lowp):
    import jax
    return jax.vmap(lambda r: _newton_lr(Xs, y, r, lowp))(regs)


def _adam_grid(Xs, y, regs, ens, W0, b0, hinge, lowp):
    import jax
    return jax.vmap(lambda r, e, w0, b0_: _adam(Xs, y, r, e, w0, b0_, hinge,
                                                lowp))(regs, ens, W0, b0)


_JITTED: dict = {}


def _jit(fn, static):
    """One jitted program per trainer; the matrices are ARGUMENTS (a closed-
    over matrix would be baked into the program as a constant)."""
    import jax
    if fn not in _JITTED:
        _JITTED[fn] = jax.jit(fn, static_argnames=static)
    return _JITTED[fn]


class LinearRef:
    """One linear family over its grid. ``fit`` trains every grid point on
    ``(X, y)`` and returns the weights in original feature space (which the
    warm refit starts from); ``margins`` scores a matrix."""

    def __init__(self, trainer: str, grid: list, lowp: bool = False):
        self.hinge = trainer == "hinge"
        self.grid = [dict(g) for g in grid]
        self.lowp = lowp

    def _newton(self, g) -> bool:
        return (not self.hinge) and float(g.get("elastic_net_param", 0.0)) == 0

    def fit(self, X, y, warm=None):
        """``[(W [d,C], b [C]) in original space]`` per grid point."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            mu, sd = _standardize(jnp, X)
            Xs = (X - mu) / sd
            out = [None] * len(self.grid)
            newton = [i for i, g in enumerate(self.grid) if self._newton(g)]
            if newton:
                regs = jnp.asarray([self.grid[i]["reg_param"] for i in newton],
                                   jnp.float32)
                uvs = _jit(_newton_grid, ("lowp",))(Xs, y, regs,
                                                    lowp=self.lowp)
                for j, i in enumerate(newton):
                    u, bu = uvs[j, :-1], uvs[j, -1]
                    W = jnp.stack([-u / 2, u / 2], axis=1)
                    out[i] = (W, jnp.stack([-bu / 2, bu / 2]))
            adam = [i for i in range(len(self.grid)) if i not in newton]
            if adam:
                C = 1 if self.hinge else 2
                d = X.shape[1]
                regs = jnp.asarray([self.grid[i]["reg_param"] for i in adam],
                                   jnp.float32)
                ens = jnp.asarray([self.grid[i].get("elastic_net_param", 0.0)
                                   for i in adam], jnp.float32)
                if warm is None:
                    W0 = jnp.zeros((len(adam), d, C), jnp.float32)
                    b0 = jnp.zeros((len(adam), C), jnp.float32)
                else:       # original space -> this fit's standardized space
                    W0 = jnp.stack([warm[i][0] * sd[:, None] for i in adam])
                    b0 = jnp.stack([warm[i][1] + mu @ warm[i][0]
                                    for i in adam])
                Ws, bs = _jit(_adam_grid, ("hinge", "lowp"))(
                    Xs, y, regs, ens, W0, b0, hinge=self.hinge,
                    lowp=self.lowp)
                for j, i in enumerate(adam):
                    out[i] = (Ws[j], bs[j])
            # fold the standardization back: original-space parameters
            return [(W / sd[:, None], b - (mu / sd) @ W) for W, b in out]

    @staticmethod
    def margins(params, X) -> np.ndarray:
        """``[G, n]`` ranking scores: z1 - z0, or the hinge margin."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            out = []
            for W, b in params:
                z = X @ W + b
                out.append(z[:, 0] if z.shape[1] == 1 else z[:, 1] - z[:, 0])
            return np.asarray(jnp.stack(out), np.float64)


# ---------------------------------------------------------------------------
# tree families (host, float64 statistics)
# ---------------------------------------------------------------------------

def bin_edges(X: np.ndarray) -> np.ndarray:
    qs = np.linspace(0.0, 1.0, N_BINS + 1)[1:-1]
    return np.quantile(X, qs, axis=0).T.astype(np.float32)     # [d, B-1]


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    out = np.empty(X.shape, np.uint8)
    for f in range(X.shape[1]):
        out[:, f] = np.searchsorted(edges[f], X[:, f], side="left")
    return out


_POOL = ThreadPoolExecutor(max_workers=8)


def _histograms(Xb, node, g, h, n_nodes):
    n, d = Xb.shape
    base = node.astype(np.int64) * N_BINS
    size = n_nodes * N_BINS

    def one(f):
        idx = base + Xb[:, f]
        return (np.bincount(idx, weights=g, minlength=size),
                np.bincount(idx, weights=h, minlength=size))

    parts = list(_POOL.map(one, range(d)))
    hg = np.stack([p[0] for p in parts], axis=1).reshape(n_nodes, N_BINS, d)
    hh = np.stack([p[1] for p in parts], axis=1).reshape(n_nodes, N_BINS, d)
    return hg.transpose(0, 2, 1), hh.transpose(0, 2, 1)      # [nodes, d, B]


def _best_splits(hg, hh, fmask, lam, gamma=0.0, mcw=1.0):
    n_nodes, d, B = hg.shape
    GL, HL = np.cumsum(hg, axis=2), np.cumsum(hh, axis=2)
    G, H = GL[:, :, -1:], HL[:, :, -1:]
    GR, HR = G - GL, H - HL
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = 0.5 * (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                      - G ** 2 / (H + lam)) - gamma
    gain[(HL < mcw) | (HR < mcw)] = -np.inf
    gain[:, fmask <= 0, :] = -np.inf
    gain[:, :, B - 1] = -np.inf
    flat = gain.reshape(n_nodes, d * B)
    best = np.argmax(flat, axis=1)
    bg = flat[np.arange(n_nodes), best]
    feat, bin_ = best // B, best % B
    no = ~(bg > 0.0)
    return np.where(no, -1, feat), np.where(no, B, bin_)


def _route(Xb, node, feat, bin_):
    f = feat[node]
    x = Xb[np.arange(Xb.shape[0]), np.clip(f, 0, None)]
    left = (f < 0) | (x <= bin_[node])
    return node * 2 + np.where(left, 0, 1)


def grow_tree(Xb, g, h, fmask, depths, lam, quantize=lambda x: x):
    """Level-wise tree to ``max(depths)``; returns ``{depth: (feats, bins,
    leaves)}``: a shallower tree of the same rows is its first levels. The
    histograms that choose the splits sum ``quantize``d operands; the leaf
    values come from the operands as they are."""
    node = np.zeros(Xb.shape[0], np.int64)
    feats, bins, out = [], [], {}
    gq, hq = quantize(g), quantize(h)
    for level in range(max(depths)):
        hg, hh = _histograms(Xb, node, gq, hq, 2 ** level)
        feat, bin_ = _best_splits(hg, hh, fmask, lam)
        feats.append(feat)
        bins.append(bin_)
        node = _route(Xb, node, feat, bin_)
        if level + 1 in depths:
            n_leaves = 2 ** (level + 1)
            lg = np.bincount(node, weights=g, minlength=n_leaves)
            lh = np.bincount(node, weights=h, minlength=n_leaves)
            out[level + 1] = (list(feats), list(bins), -lg / (lh + lam), node)
    return out


def predict_tree(Xb, feats, bins, leaves):
    node = np.zeros(Xb.shape[0], np.int64)
    for feat, bin_ in zip(feats, bins):
        node = _route(Xb, node, feat, bin_)
    return leaves[node]


def lowp_round(x: np.ndarray) -> np.ndarray:
    """The control's feature matrix: float32 values rounded to bfloat16."""
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _fp8(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return x.astype(ml_dtypes.float8_e4m3fn).astype(np.float64)


def tree_keys(seed: int, n_rounds: int, n: int, d: int, colsample: float,
              bootstrap: bool):
    """Per-round (row weights, column mask) from the documented key walk:
    ``PRNGKey(seed)`` split per round, each split into rows and columns."""
    import jax
    out = []
    for key in jax.random.split(jax.random.PRNGKey(seed), n_rounds):
        k_rows, k_cols = jax.random.split(key)
        rw = (np.asarray(jax.random.poisson(k_rows, 1.0, (n,)), np.float64)
              if bootstrap else np.ones(n))
        fm = np.asarray(jax.random.uniform(k_cols, (d,)) < colsample,
                        np.float64)
        out.append((rw, fm if fm.sum() >= 1 else np.ones(d)))
    return out


class TreeRef:
    """One tree family over its grid (depths differ, rounds are shared)."""

    def __init__(self, trainer: str, grid: list, lowp: bool = False,
                 operands: str = "exact"):
        self.forest = trainer == "forest"
        self.operands = operands
        self.grid = [dict(g) for g in grid]
        self.rounds = [int(g.get("num_trees", g.get("num_rounds")))
                       for g in self.grid]
        self.depths = [int(g["max_depth"]) for g in self.grid]
        self.lowp = lowp

    def _q(self, x):
        """The histogram's (gradient, hessian) operands in the precision the
        configuration states (``pipeline.tree_operands``: ``bfloat16`` means
        each float32 value rounded to bfloat16, then summed exactly), or one
        step down for the control."""
        if self.lowp:
            return _fp8(x)
        if self.operands == "bfloat16":
            return lowp_round(x.astype(np.float32)).astype(np.float64)
        return x

    def fit_scores(self, Xb, y, Xb_eval: list, only=None) -> list:
        """Train every grid point (or ``only`` one) on ``(Xb, y)``; returns
        per grid point the ranking scores of each matrix in ``Xb_eval``."""
        n, d = Xb.shape
        idx = range(len(self.grid)) if only is None else [only]
        res = {}
        if self.forest:
            rounds = self.rounds[0]
            keys = tree_keys(42, rounds, n, d, 0.7, True)
            depths = sorted({self.depths[i] for i in idx})
            sums = {dp: [np.zeros(e.shape[0]) for e in Xb_eval]
                    for dp in depths}
            for rw, fm in keys:
                trees = grow_tree(Xb, -y * rw, rw, fm, depths, 1e-3,
                                  self._q)
                for dp in depths:
                    f, b, leaves, _ = trees[dp]
                    for j, e in enumerate(Xb_eval):
                        sums[dp][j] += predict_tree(e, f, b, leaves)
            for i in idx:
                res[i] = [np.clip(s / rounds, 0.0, 1.0) - 0.5
                          for s in sums[self.depths[i]]]
        else:
            p0 = float(np.clip(np.float32(y.mean()), np.float32(1e-6),
                               np.float32(1 - 1e-6)))
            base = np.log(p0 / (1.0 - p0))
            ones = np.ones(d)
            for i in idx:
                margin = np.full(n, base)
                ev = [np.full(e.shape[0], base) for e in Xb_eval]
                for _ in range(self.rounds[i]):
                    p = 1.0 / (1.0 + np.exp(-margin))
                    tree = grow_tree(Xb, p - y, p * (1 - p), ones,
                                     (self.depths[i],), 1.0, self._q)
                    f, b, leaves, node = tree[self.depths[i]]
                    margin = margin + 0.3 * leaves[node]
                    for j, e in enumerate(Xb_eval):
                        ev[j] += 0.3 * predict_tree(e, f, b, leaves)
                res[i] = ev
        return [res.get(i) for i in range(len(self.grid))]


# ---------------------------------------------------------------------------
# the whole train
# ---------------------------------------------------------------------------

@dataclass
class RefTrain:
    fit: FeFit
    keep: np.ndarray
    unsure: np.ndarray
    cv: dict                  # (family index, grid index) -> mean fold metric
    X: object = None          # checked training matrix (device)
    y: np.ndarray = None
    train_idx: np.ndarray = None
    holdout_idx: np.ndarray = None
    fold_params: dict = field(default_factory=dict)
    edges: np.ndarray = None


def reference_train(table, pipeline: dict, *, sweep: bool = True,
                    lowp: bool = False) -> RefTrain:
    """Fit the vectorizers and SanityChecker on ``table``, split, and (with
    ``sweep``) run the whole k-fold sweep."""
    import jax.numpy as jnp
    fit = fit_fe(table)
    Xfull = apply_fe(table, fit)
    y = np.asarray(table.label, np.float64)
    if lowp:
        Xfull = lowp_round(Xfull)
    keep, unsure = sanity_keep(Xfull, y)
    X = Xfull[:, keep]
    del Xfull
    seed, k = int(pipeline["split_seed"]), int(pipeline["n_folds"])
    tr, ho = holdout_split(table.n_rows, float(pipeline["holdout_fraction"]),
                           seed)
    ref = RefTrain(fit=fit, keep=keep, unsure=unsure, cv={}, X=X, y=y,
                   train_idx=tr, holdout_idx=ho)
    Xt, yt = X[tr], y[tr]
    if any(f["trainer"] in TREES for f in pipeline["zoo"]):
        ref.edges = bin_edges(Xt)
    if not sweep:
        return ref
    folds = cv_folds(tr.size, k, seed)
    if ref.edges is not None:
        Xbt = bin_codes(Xt, ref.edges)
    for ci, fam in enumerate(pipeline["zoo"]):
        G = len(fam["grid"])
        vals = np.zeros((k, G))
        if fam["trainer"] in LINEAR:
            lin = LinearRef(fam["trainer"], fam["grid"], lowp)
            ref.fold_params[ci] = []
            for f, (a, b) in enumerate(folds):
                Xa = jnp.asarray(Xt[a], jnp.float32)
                params = lin.fit(Xa, jnp.asarray(yt[a], jnp.float32))
                del Xa
                s = lin.margins(params, jnp.asarray(Xt[b], jnp.float32))
                vals[f] = [binned_aupr(yt[b], s[g]) for g in range(G)]
                ref.fold_params[ci].append(params)
        else:
            trees = TreeRef(fam["trainer"], fam["grid"], lowp,
                            pipeline.get("tree_operands", "exact"))
            for f, (a, b) in enumerate(folds):
                scores = trees.fit_scores(Xbt[a], yt[a], [Xbt[b]])
                vals[f] = [binned_aupr(yt[b], scores[g][0])
                           for g in range(G)]
        for g in range(G):
            ref.cv[(ci, g)] = float(vals[:, g].mean())
    return ref


def reference_refit(ref: RefTrain, pipeline: dict, ci: int, gi: int,
                    Xs: list, lowp: bool = False) -> list:
    """Refit zoo entry ``ci``'s grid point ``gi`` on every training row, as
    the selector does with its winner, and score each checked matrix of
    ``Xs``: the ranking scores (the margin for linear winners and boosting,
    the mean tree probability less a half for forests)."""
    import jax.numpy as jnp
    fam = pipeline["zoo"][ci]
    Xt, yt = ref.X[ref.train_idx], ref.y[ref.train_idx]
    if fam["trainer"] in LINEAR:
        lin = LinearRef(fam["trainer"], [fam["grid"][gi]], lowp)
        warm = None
        if not lin._newton(lin.grid[0]):
            fp = ref.fold_params[ci]       # fold-averaged warm start
            warm = [(sum(p[gi][0] for p in fp) / len(fp),
                     sum(p[gi][1] for p in fp) / len(fp))]
        params = lin.fit(jnp.asarray(Xt, jnp.float32),
                         jnp.asarray(yt, jnp.float32), warm=warm)
        return [lin.margins(params, jnp.asarray(X, jnp.float32))[0]
                for X in Xs]
    trees = TreeRef(fam["trainer"], fam["grid"], lowp,
                    pipeline.get("tree_operands", "exact"))
    return trees.fit_scores(bin_codes(Xt, ref.edges), yt,
                            [bin_codes(X, ref.edges) for X in Xs],
                            only=gi)[gi]


def class1_score(trainer: str, s: np.ndarray) -> np.ndarray:
    """A ranking score as the served score it stands for: the probability
    of class 1 where the family has one, else the margin."""
    if trainer == "hinge":
        return s
    if trainer == "forest":
        return s + 0.5
    return 1.0 / (1.0 + np.exp(-s))
