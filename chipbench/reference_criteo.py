"""The plain reference of the ``criteo_ctr`` configuration.

Independent of ``transmogrifai_tpu``: it imports nothing of the program and
takes nothing the program made (no vocabularies, treatments, kept columns or
weights). From the raw table it follows the published treatment of every
stage:

- a real column: its value and a null indicator (no nulls are generated, so
  the mean fill is unused);
- a string column, by its count of distinct values on the fitted table: up
  to ``max_cardinality`` it pivots (the ``top_k`` most frequent values of
  support ``min_support`` or more, most frequent first and ties in string
  order, then OTHER, then null); above it, it hashes (each lower-cased
  ``[^\\W_]+`` token counted at CRC-32 of its UTF-8 bytes modulo
  ``num_hash_features``, then the string's length, then null); a column
  with no value at all keeps its null indicator only;
- the column order the program states: the real block first, then the
  string columns in table order;
- SanityChecker: population variance at most 1e-5 drops a column, and so
  does a Pearson correlation with the label above 0.95;
- splits, the 4,096-bin auPR of the sweep, the holdout curves and the linear
  trainer are ``chipbench.reference``'s own.

Departures from the source library, each as the configuration's ``assumed``
block states it: the hash is CRC-32 where the library uses MurmurHash3; above
2,048 columns EVERY linear point (pure-L2 logistic regression too) trains by
200 full-batch Adam(0.1) steps from zero on standardized columns, and the
winner's refit is the same descent started from the fold-averaged winning
parameters; above 4,096 columns SanityChecker computes no feature-feature
correlation; its categorical rules (Cramer's V, rule confidence) are left
out, as in ``chipbench.reference``: no column of this data comes near them.

Linear algebra runs in float32 at ``highest`` matmul precision, with the
matrices passed to the jitted trainers as arguments. ``lowp=True`` is the
CONTROL: the feature matrix rounded to bfloat16 and bfloat16 products.
"""

from __future__ import annotations

import re
import zlib
from dataclasses import dataclass, field

import numpy as np

from chipbench import reference
from chipbench.reference import (LinearRef, binned_aupr, cv_folds,
                                 exact_curves, holdout_split, lowp_round)

_TOKEN = re.compile(r"[^\W_]+", re.UNICODE)
MIN_VARIANCE, MAX_LABEL_CORR = 1e-5, 0.95


def standardized(X):
    """``(Xs, mu, sd)``: the columns of a device matrix standardized by
    their population mean and deviation, as ``reference._standardize`` has
    them, in one program that takes ``X``'s own storage for ``Xs`` (the
    caller lets go of ``X``): a 4 GB matrix is never held three times."""
    import jax
    import jax.numpy as jnp

    def run(X):
        mu, sd = reference._standardize(jnp, X)
        return (X - mu) / sd, mu, sd

    if "standardized" not in reference._JITTED:
        donate = () if jax.default_backend() == "cpu" else (0,)
        reference._JITTED["standardized"] = jax.jit(run,
                                                    donate_argnums=donate)
    with jax.default_matmul_precision("highest"):
        return reference._JITTED["standardized"](X)


class AdamRef:
    """One linear family over its grid, every point on the first-order path
    (``reference._adam``: 200 full-batch Adam(0.1) steps), as the
    configuration states for a matrix of more than 2,048 columns."""

    margins = staticmethod(LinearRef.margins)

    def __init__(self, trainer: str, grid: list, lowp: bool = False):
        self.hinge = trainer == "hinge"
        self.grid = [dict(g) for g in grid]
        self.lowp = lowp

    def fit(self, Xs, mu, sd, y, warm=None) -> list:
        """``[(W [d, C], b [C]) in original space]`` per grid point, trained
        on the standardized ``Xs`` from zero, or from ``warm`` (original
        space) mapped into this fit's standardized space."""
        import jax
        import jax.numpy as jnp
        G, C, d = len(self.grid), 1 if self.hinge else 2, Xs.shape[1]
        regs = jnp.asarray([g["reg_param"] for g in self.grid], jnp.float32)
        ens = jnp.asarray([g.get("elastic_net_param", 0.0)
                           for g in self.grid], jnp.float32)
        with jax.default_matmul_precision("highest"):
            if warm is None:
                W0 = jnp.zeros((G, d, C), jnp.float32)
                b0 = jnp.zeros((G, C), jnp.float32)
            else:
                W0 = jnp.stack([W * sd[:, None] for W, _ in warm])
                b0 = jnp.stack([b + mu @ W for W, b in warm])
            Ws, bs = reference._jit(reference._adam_grid, ("hinge", "lowp"))(
                Xs, y, regs, ens, W0, b0, hinge=self.hinge, lowp=self.lowp)
            return [(Ws[g] / sd[:, None], bs[g] - (mu / sd) @ Ws[g])
                    for g in range(G)]


# ---------------------------------------------------------------------------
# feature engineering
# ---------------------------------------------------------------------------

@dataclass
class FeFit:
    """What fitting the vectorizers learns from the training table: per
    string column ``("pivot", [values])``, ``("hash",)`` or ``("ignore",)``;
    and the widths."""
    treatments: dict
    n_hash: int
    n_real: int
    width: int = 0


def _width(treat: tuple, n_hash: int) -> int:
    if treat[0] == "pivot":
        return len(treat[1]) + 2
    return n_hash + 2 if treat[0] == "hash" else 1


def fit_fe(table, fe: dict) -> FeFit:
    """``fe``: ``num_hash_features``, ``top_k``, ``min_support``,
    ``max_cardinality``."""
    treatments = {}
    for name, col in table.cats.items():
        present = np.array([v for v in col if v is not None], dtype=object)
        if present.size == 0:
            treatments[name] = ("ignore",)
            continue
        values, counts = np.unique(present.astype("U"), return_counts=True)
        if values.size > int(fe["max_cardinality"]):
            treatments[name] = ("hash",)
            continue
        ranked = sorted(((-int(c), str(v)) for v, c in zip(values, counts)
                         if c >= int(fe["min_support"])))
        treatments[name] = ("pivot",
                            [v for _, v in ranked[:int(fe["top_k"])]])
    n_hash = int(fe["num_hash_features"])
    fit = FeFit(treatments, n_hash, len(table.nums))
    fit.width = 2 * fit.n_real + sum(_width(t, n_hash)
                                     for t in treatments.values())
    return fit


def _hash_slots(value: str, n_hash: int) -> list:
    return [zlib.crc32(tok.encode("utf-8")) % n_hash
            for tok in _TOKEN.findall(value.lower())]


def apply_fe(table, fit: FeFit) -> np.ndarray:
    """The un-checked feature matrix, float32 ``[n, fit.width]``."""
    n = table.n_rows
    out = np.zeros((n, fit.width), np.float32)
    for j, v in enumerate(table.nums.values()):
        out[:, 2 * j] = v
    off = 2 * fit.n_real
    for name, col in table.cats.items():
        treat = fit.treatments[name]
        null = np.array([v is None for v in col], bool)
        rows = np.nonzero(~null)[0]
        width = _width(treat, fit.n_hash)
        if treat[0] == "pivot":
            slot = {v: i for i, v in enumerate(treat[1])}
            k = len(treat[1])
            out[rows, off + np.array([slot.get(col[r], k) for r in rows],
                                     np.int64)] = 1.0
        elif treat[0] == "hash":
            values, inv = np.unique(col[rows].astype("U"),
                                    return_inverse=True)
            table_ = np.zeros((values.size, fit.n_hash), np.float32)
            for u, v in enumerate(values):
                for s in _hash_slots(str(v), fit.n_hash):
                    table_[u, s] += 1.0
            out[rows, off:off + fit.n_hash] = table_[inv]
            out[rows, off + fit.n_hash] = np.char.str_len(values)[inv]
        out[null, off + width - 1] = 1.0
        off += width
    return out


def sanity_keep(X: np.ndarray, y: np.ndarray, margin: float = 0.012,
                block: int = 512):
    """``(keep mask, unsure mask)`` by the variance and the
    label-correlation rule, in float64 over column blocks; ``unsure`` marks
    a column whose deciding statistic lies within ``margin`` (relative) of
    its threshold, which the comparison skips (the program's moments are
    float32 sums)."""
    n, d = X.shape
    yc = y - y.mean()
    ysd = np.sqrt(max(y.var(), 1e-12))
    var, corr = np.empty(d), np.empty(d)
    for j in range(0, d, block):
        B = X[:, j:j + block].astype(np.float64)
        mean = B.mean(axis=0)
        var[j:j + block] = np.maximum((B * B).mean(axis=0) - mean ** 2, 0.0)
        corr[j:j + block] = (yc @ B) / n
    corr = np.abs(corr / (np.sqrt(np.maximum(var, 1e-12)) * ysd))
    drop = (var <= MIN_VARIANCE) | (corr > MAX_LABEL_CORR)
    unsure = (np.abs(var - MIN_VARIANCE) <= margin * MIN_VARIANCE) | (
        np.abs(corr - MAX_LABEL_CORR) <= margin * MAX_LABEL_CORR)
    return ~drop, unsure


# ---------------------------------------------------------------------------
# the whole train
# ---------------------------------------------------------------------------

@dataclass
class RefTrain:
    fit: FeFit
    keep: np.ndarray
    unsure: np.ndarray
    cv: dict                  # (family index, grid index) -> mean fold metric
    X: np.ndarray = None      # checked matrix, every row (host float32)
    y: np.ndarray = None
    train_idx: np.ndarray = None
    holdout_idx: np.ndarray = None
    fold_params: dict = field(default_factory=dict)
    refit_scores: dict = field(default_factory=dict)  # (ci, gi) -> holdout


def fe_settings(config: dict) -> dict:
    """The vectorizers' settings a configuration states (the pipeline's
    ``transmogrify`` block overrides the hash width, as it does in the
    program)."""
    fe = {k: config[k] for k in ("num_hash_features", "top_k", "min_support",
                                 "max_cardinality")}
    fe.update({k: v for k, v in config["pipeline"].get(
        "transmogrify", {}).items() if k in fe})
    return fe


def reference_train(table, config: dict, *, sweep: bool = True,
                    lowp: bool = False) -> RefTrain:
    """Fit the vectorizers and SanityChecker's two rules on ``table``,
    split, and (with ``sweep``) run the whole k-fold sweep of every grid
    point."""
    import jax.numpy as jnp
    pipeline = config["pipeline"]
    fit = fit_fe(table, fe_settings(config))
    Xfull = apply_fe(table, fit)
    y = np.asarray(table.label, np.float64)
    if lowp:
        Xfull = lowp_round(Xfull)
    keep, unsure = sanity_keep(Xfull, y)
    X = np.ascontiguousarray(Xfull[:, keep])
    del Xfull
    seed, k = int(pipeline["split_seed"]), int(pipeline["n_folds"])
    tr, ho = holdout_split(table.n_rows, float(pipeline["holdout_fraction"]),
                           seed)
    ref = RefTrain(fit=fit, keep=keep, unsure=unsure, cv={}, X=X, y=y,
                   train_idx=tr, holdout_idx=ho)
    if not sweep:
        return ref
    Xt, yt = X[tr], y[tr]
    vals = {ci: np.zeros((k, len(fam["grid"])))
            for ci, fam in enumerate(pipeline["zoo"])}
    for f, (a, b) in enumerate(cv_folds(tr.size, k, seed)):
        # one fold on the device at a time: its standardized training rows
        # (shared by the families) and its validation rows
        Xs, mu, sd = standardized(jnp.asarray(Xt[a], jnp.float32))
        Xb = jnp.asarray(Xt[b], jnp.float32)
        ya = jnp.asarray(yt[a], jnp.float32)
        for ci, fam in enumerate(pipeline["zoo"]):
            lin = AdamRef(fam["trainer"], fam["grid"], lowp)
            params = lin.fit(Xs, mu, sd, ya)
            s = lin.margins(params, Xb)
            vals[ci][f] = [binned_aupr(yt[b], s[g])
                           for g in range(len(fam["grid"]))]
            ref.fold_params.setdefault(ci, []).append(
                [(np.asarray(W), np.asarray(b_)) for W, b_ in params])
        del Xs, Xb
    for ci, v in vals.items():
        for g in range(v.shape[1]):
            ref.cv[(ci, g)] = float(v[:, g].mean())
    return ref


def reference_refit(ref: RefTrain, pipeline: dict, ci: int, gi: int,
                    Xs: list, lowp: bool = False) -> list:
    """Refit zoo entry ``ci``'s grid point ``gi`` on every training row,
    started from the fold-averaged parameters of that point, and score each
    checked matrix of ``Xs``: the margins."""
    import jax.numpy as jnp
    fam = pipeline["zoo"][ci]
    lin = AdamRef(fam["trainer"], [fam["grid"][gi]], lowp)
    fp = ref.fold_params[ci]
    warm = [(jnp.asarray(sum(p[gi][0] for p in fp) / len(fp)),
             jnp.asarray(sum(p[gi][1] for p in fp) / len(fp)))]
    Xt, mu, sd = standardized(jnp.asarray(ref.X[ref.train_idx], jnp.float32))
    params = lin.fit(Xt, mu, sd,
                     jnp.asarray(ref.y[ref.train_idx], jnp.float32),
                     warm=warm)
    del Xt
    return [lin.margins(params, jnp.asarray(X, jnp.float32))[0] for X in Xs]


class1_score = reference.class1_score
__all__ = ["AdamRef", "FeFit", "RefTrain", "apply_fe", "class1_score",
           "exact_curves", "fe_settings", "fit_fe", "lowp_round",
           "reference_refit", "reference_train", "sanity_keep",
           "standardized"]
