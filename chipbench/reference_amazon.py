"""The plain reference of the ``amazon_polarity_text`` configuration.

Independent of ``transmogrifai_tpu``: it imports nothing of the program and
takes nothing the program made (no treatments, entries, kept columns or
weights). From the raw table it follows the published treatment as
``reference_criteo``'s docstring sets it out, on columns of free text:

- a string column, by its count of distinct values on the fitted table: up
  to ``max_cardinality`` it pivots (``reference_criteo``'s ranking), above
  it, it hashes: per string the lower-cased ``[^\\W_]+`` tokens by ``re``,
  each counted at ``zlib.crc32`` of its UTF-8 bytes modulo
  ``num_hash_features``, then the string's length in characters, then the
  null indicator; a column with no value keeps its null indicator only.
  Every row is tokenized by itself (no value is expected twice) and written
  straight into its row of the matrix, a block of rows at a time: no table
  a distinct value is built, which for a million reviews would not fit;
- SanityChecker with ALL of ``chipbench.reference.sanity_keep``'s rules:
  population variance, correlation with the label, and the feature-feature
  correlation, which applies at this width (1,028 columns, under the
  4,096-column cap): of a pair correlated above 0.99 the later column goes;
- splits, the 4,096-bin auPR of the sweep, the holdout curves and the
  linear trainers are ``chipbench.reference``'s own: at this width (under
  2,048 checked columns) the pure-L2 logistic points train by 15 damped
  Newton steps and refit cold, the L1 and hinge points by 200 full-batch
  Adam(0.1) steps from zero and refit from the fold-averaged parameters.

Departures from the source library, each as the configuration's ``assumed``
block states it: the hash is CRC-32 where the library uses MurmurHash3; a
token is a run of letters and digits of any script (the library's analyzer
also drops nothing at its defaults, but segments space-less scripts, which
this data does not hold); SanityChecker's categorical rules are left out,
as in ``chipbench.reference``: no column of this data comes near them.

Linear algebra runs in float32 at ``highest`` matmul precision.
``lowp=True`` is the CONTROL: bfloat16 products in every trainer, over the
exact feature matrix (token counts are bfloat16 numbers anyway, and a text
length past 256 is no fault of a trainer's precision: a control that rounds
the matrix fails ``fe_max_abs`` and shows nothing about the trainers).
"""

from __future__ import annotations

import re
import zlib
from collections import Counter
from dataclasses import dataclass

import numpy as np

from chipbench import reference
from chipbench.reference import (LinearRef, RefTrain, binned_aupr,
                                 class1_score, cv_folds, exact_curves,
                                 holdout_split, reference_refit,
                                 sanity_keep)
from chipbench.reference_criteo import fe_settings

_TOKEN = re.compile(r"[^\W_]+", re.UNICODE)
BLOCK_ROWS = 65_536


@dataclass
class FeFit:
    """What fitting the vectorizers learns from the training table: per
    string column ``("pivot", [values])``, ``("hash",)`` or ``("ignore",)``;
    and the widths."""
    treatments: dict
    n_hash: int
    width: int = 0


def _width(treat: tuple, n_hash: int) -> int:
    if treat[0] == "pivot":
        return len(treat[1]) + 2
    return n_hash + 2 if treat[0] == "hash" else 1


def fit_fe(table, fe: dict) -> FeFit:
    """``fe``: ``num_hash_features``, ``top_k``, ``min_support``,
    ``max_cardinality``. The cardinality rule on every string column."""
    treatments = {}
    for name, col in table.cats.items():
        counts = Counter(v for v in col if v is not None)
        if not counts:
            treatments[name] = ("ignore",)
        elif len(counts) > int(fe["max_cardinality"]):
            treatments[name] = ("hash",)
        else:
            ranked = sorted((-c, v) for v, c in counts.items()
                            if c >= int(fe["min_support"]))
            treatments[name] = ("pivot",
                                [v for _, v in ranked[:int(fe["top_k"])]])
    n_hash = int(fe["num_hash_features"])
    fit = FeFit(treatments, n_hash)
    fit.width = sum(_width(t, n_hash) for t in treatments.values())
    return fit


def _hash_row(out_row: np.ndarray, value: str, n_hash: int) -> None:
    """One string's token counts, then its length, into its row."""
    for tok in _TOKEN.findall(value.lower()):
        out_row[zlib.crc32(tok.encode("utf-8")) % n_hash] += 1.0
    out_row[n_hash] = len(value)


def apply_fe(table, fit: FeFit, rows=None) -> np.ndarray:
    """The un-checked feature matrix of ``table`` (of its ``rows``, where
    given), float32 ``[n, fit.width]``, filled a block of rows at a time."""
    rows = np.arange(table.n_rows) if rows is None else np.asarray(rows)
    out = np.zeros((rows.size, fit.width), np.float32)
    for lo in range(0, rows.size, BLOCK_ROWS):
        block = rows[lo:lo + BLOCK_ROWS]
        off = 0
        for name, col in table.cats.items():
            treat = fit.treatments[name]
            width = _width(treat, fit.n_hash)
            slot = ({v: i for i, v in enumerate(treat[1])}
                    if treat[0] == "pivot" else None)
            for i, v in enumerate(col[block].tolist(), start=lo):
                if v is None:
                    out[i, off + width - 1] = 1.0
                elif treat[0] == "hash":
                    _hash_row(out[i, off:off + width], v, fit.n_hash)
                elif treat[0] == "pivot":
                    out[i, off + slot.get(v, len(slot))] = 1.0
            off += width
    return out


def reference_train(table, config: dict, *, sweep: bool = True,
                    lowp: bool = False) -> RefTrain:
    """Fit the vectorizers and SanityChecker on ``table``, split, and (with
    ``sweep``) run the whole k-fold sweep of every grid point: what
    ``chipbench.reference.reference_train`` does for its linear families,
    from this reference's feature matrix."""
    import jax.numpy as jnp
    pipeline = config["pipeline"]
    fit = fit_fe(table, fe_settings(config))
    Xfull = apply_fe(table, fit)
    y = np.asarray(table.label, np.float64)
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
    folds = cv_folds(tr.size, k, seed)
    for ci, fam in enumerate(pipeline["zoo"]):
        if fam["trainer"] not in reference.LINEAR:
            raise NotImplementedError(
                "this reference trains the linear families only")
        G = len(fam["grid"])
        vals = np.zeros((k, G))
        lin = LinearRef(fam["trainer"], fam["grid"], lowp)
        ref.fold_params[ci] = []
        for f, (a, b) in enumerate(folds):
            Xa = jnp.asarray(Xt[a], jnp.float32)
            params = lin.fit(Xa, jnp.asarray(yt[a], jnp.float32))
            del Xa
            s = lin.margins(params, jnp.asarray(Xt[b], jnp.float32))
            vals[f] = [binned_aupr(yt[b], s[g]) for g in range(G)]
            ref.fold_params[ci].append(params)
        for g in range(G):
            ref.cv[(ci, g)] = float(vals[:, g].mean())
    return ref


__all__ = ["FeFit", "RefTrain", "apply_fe", "class1_score", "exact_curves",
           "fe_settings", "fit_fe", "reference_refit",
           "reference_train", "sanity_keep"]
