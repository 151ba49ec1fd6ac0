"""The comparison that decides ``correct`` for the binary-classification zoo.

A configuration names this module under ``comparison``. ``collect`` copies
out of the program what one timed unit produced (host numbers only, so the
program's state can be freed); ``check`` runs the plain reference on the
same table once the window has closed and returns the numbers compared,
each under a short plain name. Their limits live in the configuration file,
with the readings they were set from in PERF.md.
"""

from __future__ import annotations

import numpy as np

from chipbench import opcount, reference

FE_SAMPLE_ROWS = 20_000
SCORE_SAMPLE_ROWS = 20_000


def _stage(model, cls_name: str):
    for st in model.stages():
        if type(st).__name__ == cls_name:
            return st
    raise LookupError(f"the fitted workflow has no {cls_name}")


def _grid_key(model_name: str) -> tuple:
    """``(zoo entry, grid point)`` from a candidate's ``<family>_<i>_<j>``."""
    _, ci, gi = model_name.rsplit("_", 2)
    return int(ci), int(gi)


def _class1(doc: dict) -> float:
    """A score document's probability of class 1, else its margin."""
    if "probability_1" in doc:
        return float(doc["probability_1"])
    if "rawPrediction_1" in doc:
        return float(doc["rawPrediction_1"]) - float(doc["rawPrediction_0"])
    return float(doc["rawPrediction_0"])


def sample_rows(n: int, pcfg: dict, rng) -> tuple:
    """``(rows whose feature vector is compared, holdout rows whose score
    is compared)``, drawn from the run's seed."""
    idx = np.sort(rng.choice(n, size=min(n, FE_SAMPLE_ROWS), replace=False))
    _, ho = reference.holdout_split(n, float(pcfg["holdout_fraction"]),
                                    int(pcfg["split_seed"]))
    hidx = np.sort(rng.choice(ho, size=min(ho.size, SCORE_SAMPLE_ROWS),
                              replace=False))
    return idx, hidx


def collect(model, handles, summary, frame, pcfg: dict, rng) -> dict:
    """What one timed train produced: the vectorizers' output on a sample
    of rows, SanityChecker's kept columns, every grid point's mean fold
    metric, the winner, its holdout metrics, and the refit winner's scores
    of a sample of the holdout rows."""
    n = frame.n_rows
    idx, hidx = sample_rows(n, pcfg, rng)
    vec = model.compute_data_up_to(handles["vector"], frame.take(idx))
    docs = model.score(frame.take(hidx))[handles["prediction"].name].values
    hold = summary.holdout_evaluation["binary classification"]
    return {
        "n_rows": n,
        "sample_idx": idx,
        "vector": np.asarray(vec[handles["vector"].name].values, np.float32),
        "keep": np.asarray(_stage(model, "DropIndicesModel").keep_indices,
                           np.int64),
        "winner": _grid_key(summary.best_model_name),
        "cv": {_grid_key(r.model_name):
               float(r.metric_values[summary.validation_metric])
               for r in summary.validation_results},
        "holdout": (float(hold["au_roc"]), float(hold["au_pr"])),
        "holdout_rows": hidx,
        "holdout_scores": np.asarray([_class1(d) for d in docs], np.float64),
        "failures": len(summary.failures),
    }


def shapes_of(produced: dict, config: dict, n_rows: int) -> dict:
    p = config["pipeline"]
    n_hold = int(round(n_rows * float(p["holdout_fraction"])))
    n_train = n_rows - n_hold
    n_val = n_train // int(p["n_folds"])
    return {"n_rows": n_rows, "n_train": n_train, "n_fold_val": n_val,
            "n_fold_train": n_train - n_val, "k": int(p["n_folds"]),
            "d": int(produced["keep"].size),
            "d_full": int(produced["vector"].shape[1]),
            "winner": tuple(produced["winner"])}


def window_flops(config: dict, shapes: dict, units: int) -> dict:
    """Analytic FLOPs of all the trains in the window, by kind."""
    s = shapes
    work = opcount.zoo_train_work(config["pipeline"]["zoo"], s["n_fold_train"],
                                  s["n_train"], s["d"], s["k"], s["winner"])
    return {kind: v[0] * units for kind, v in work.items()}


# ---------------------------------------------------------------------------

def _newton(fam: dict, gi: int) -> bool:
    """Pure-L2 logistic regression: the converged Newton path."""
    return (fam["trainer"] == "logistic" and float(
        fam["grid"][gi].get("elastic_net_param", 0.0)) == 0.0)


def _winner_kind(fam: dict, gi: int) -> str:
    """``l2`` for a converged Newton winner, ``adam`` for the first-order
    paths (L1 points, hinge), ``tree`` for an ensemble: their refits agree
    with the reference to very different depths."""
    if fam["trainer"] in reference.TREES:
        return "tree"
    return "l2" if _newton(fam, gi) else "adam"


def _fe_numbers(produced, table, ref) -> dict:
    Xs = reference.apply_fe(table.take(produced["sample_idx"]), ref.fit)
    got = produced["vector"]
    out = {}
    if got.shape != Xs.shape:
        out["fe_max_abs"] = float("inf")
    else:
        out["fe_max_abs"] = float(np.max(np.abs(got - Xs)))
    kept = np.zeros(ref.keep.size, bool)
    kept[produced["keep"][produced["keep"] < kept.size]] = True
    wrong = (kept != ref.keep) & ~ref.unsure
    out["kept_mismatch"] = float(
        wrong.sum() + (produced["keep"] >= kept.size).sum())
    return out


def as_program(stand_in, table, produced: dict, pcfg: dict,
               lowp: bool = False) -> dict:
    """A reference run (the control's, or one with a fault planted) put in
    the program's place: its sweep metrics, its feature matrix on the
    compared rows, its kept columns, and its refit winner's holdout metrics
    and scores of the unit's compared holdout rows."""
    ci, gi = produced["winner"]
    out = dict(produced)
    out.setdefault("failures", 0)
    out["cv"] = dict(stand_in.cv)
    vec = reference.apply_fe(table.take(produced["sample_idx"]), stand_in.fit)
    out["vector"] = reference.lowp_round(vec) if lowp else vec
    out["keep"] = np.nonzero(stand_in.keep)[0]
    rows = reference.apply_fe(table.take(produced["holdout_rows"]),
                              stand_in.fit)
    rows = (reference.lowp_round(rows) if lowp else rows)[:, stand_in.keep]
    own, served = reference.reference_refit(
        stand_in, pcfg, ci, gi, [stand_in.X[stand_in.holdout_idx], rows],
        lowp=lowp)
    out["holdout"] = reference.exact_curves(
        stand_in.y[stand_in.holdout_idx], own)
    out["holdout_scores"] = reference.class1_score(
        pcfg["zoo"][ci]["trainer"], served)
    return out


def check(produced: dict, table, config: dict) -> dict:
    """The numbers compared (and those only reported) of one timed train
    against the plain reference's train of the same table."""
    pcfg = config["pipeline"]
    ref = reference.reference_train(table, pcfg, sweep=True)
    ci, gi = produced["winner"]
    numbers = _fe_numbers(produced, table, ref)
    numbers["sweep_failures"] = float(produced.get("failures", 0))
    for fi, fam in enumerate(pcfg["zoo"]):
        for g in range(len(fam["grid"])):
            name = f"cv_gap_{fam['short']}"
            if fam["trainer"] == "logistic":   # Newton points apart from Adam
                name += "_l2" if _newton(fam, g) else "_l1"
            gap = abs(produced["cv"].get((fi, g), float("inf"))
                      - ref.cv[(fi, g)])
            numbers[name] = max(numbers.get(name, 0.0), float(gap))
    best = max(ref.cv.values())
    numbers["winner_regret"] = float(best - ref.cv[(ci, gi)])
    s, = reference.reference_refit(ref, pcfg, ci, gi,
                                   [ref.X[ref.holdout_idx]])
    auroc, aupr = reference.exact_curves(ref.y[ref.holdout_idx], s)
    kind = _winner_kind(pcfg["zoo"][ci], gi)
    numbers[f"holdout_auroc_gap_{kind}"] = abs(produced["holdout"][0] - auroc)
    numbers[f"holdout_aupr_gap_{kind}"] = abs(produced["holdout"][1] - aupr)
    # the refit winner's score of each compared holdout row
    pos = np.searchsorted(ref.holdout_idx, produced["holdout_rows"])
    want = reference.class1_score(pcfg["zoo"][ci]["trainer"], s[pos])
    got = produced["holdout_scores"]
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        gap = np.full(want.shape, np.inf)
    else:
        gap = np.abs(got - want)
    numbers[f"holdout_score_gap_{kind}"] = float(np.median(gap))
    numbers[f"holdout_score_gap_mean_{kind}"] = float(np.mean(gap))
    numbers[f"holdout_score_gap_p99_{kind}"] = float(np.quantile(gap, 0.99))
    numbers[f"holdout_score_gap_max_{kind}"] = float(np.max(gap))
    return numbers
