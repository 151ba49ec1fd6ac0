"""The comparison that decides ``correct`` for the ``criteo_ctr`` zoo.

Named by the configuration under ``comparison``; the counterpart of
``chipbench.compare`` for a table with string columns and a zoo of linear
families only. What a timed unit produced is collected as there
(``compare.collect``); ``check`` runs ``reference_criteo`` on the same table
once the window has closed. Limits live in the configuration file, with the
readings they were set from in PERF.md.
"""

from __future__ import annotations

import numpy as np

from chipbench import compare, opcount_wide
from chipbench import reference_criteo as reference

collect = compare.collect
sample_rows = compare.sample_rows
shapes_of = compare.shapes_of


def window_flops(config: dict, shapes: dict, units: int) -> dict:
    """Analytic FLOPs of all the trains in the window."""
    flops, _ = opcount_wide.sweep_and_refit(config, shapes)
    return {"linear": flops * units}


def _fe_numbers(produced: dict, table, ref) -> dict:
    want = reference.apply_fe(table.take(produced["sample_idx"]), ref.fit)
    got = produced["vector"]
    out = {"fe_max_abs": float(np.max(np.abs(got - want)))
           if got.shape == want.shape else float("inf")}
    kept = np.zeros(ref.keep.size, bool)
    kept[produced["keep"][produced["keep"] < kept.size]] = True
    wrong = (kept != ref.keep) & ~ref.unsure
    out["kept_mismatch"] = float(
        wrong.sum() + (produced["keep"] >= kept.size).sum())
    return out


def _point_names(fam: dict, g: int, stable_from: float) -> list:
    """The numbers grid point ``g`` of ``fam`` counts towards:
    ``cv_gap_<family>`` over all of a family's points (the logistic points
    apart by their penalty), and ``cv_gap_<family>_stable`` over those of
    ``reg_param`` at least ``stable_from``, where 200 Adam steps from zero
    do converge and two sound implementations agree."""
    name = f"cv_gap_{fam['short']}"
    if fam["trainer"] == "logistic":
        l2 = float(fam["grid"][g].get("elastic_net_param", 0.0)) == 0.0
        name += "_l2" if l2 else "_l1"
    if float(fam["grid"][g]["reg_param"]) >= stable_from:
        return [name, name + "_stable"]
    return [name]


def as_program(stand_in, table, produced: dict, config: dict,
               lowp: bool = False) -> dict:
    """A reference run (the control's, or one with a fault planted) put in
    the program's place: its sweep metrics, its feature matrix on the
    compared rows, its kept columns, and its refit winner's holdout metrics
    and scores of the unit's compared holdout rows."""
    pcfg = config["pipeline"]
    ci, gi = produced["winner"]
    out = dict(produced)
    out.setdefault("failures", 0)
    out["cv"] = dict(stand_in.cv)
    round_ = reference.lowp_round if lowp else (lambda x: x)
    out["vector"] = round_(reference.apply_fe(
        table.take(produced["sample_idx"]), stand_in.fit))
    out["keep"] = np.nonzero(stand_in.keep)[0]
    rows = round_(reference.apply_fe(
        table.take(produced["holdout_rows"]), stand_in.fit))
    own, served = reference.reference_refit(
        stand_in, pcfg, ci, gi,
        [stand_in.X[stand_in.holdout_idx], rows[:, stand_in.keep]],
        lowp=lowp)
    out["holdout"] = reference.exact_curves(
        stand_in.y[stand_in.holdout_idx], own)
    out["holdout_scores"] = reference.class1_score(
        pcfg["zoo"][ci]["trainer"], served)
    return out


def check(produced: dict, table, config: dict, ref=None) -> dict:
    """The numbers compared (and those only reported) of one timed train
    against the plain reference's train of the same table (``ref``, where
    the caller has it already)."""
    pcfg = config["pipeline"]
    if ref is None:
        ref = reference.reference_train(table, config, sweep=True)
    ci, gi = produced["winner"]
    numbers = _fe_numbers(produced, table, ref)
    numbers["sweep_failures"] = float(produced.get("failures", 0))
    stable_from = float(config["stable_points"]["reg_param_from"])
    for fi, fam in enumerate(pcfg["zoo"]):
        for g in range(len(fam["grid"])):
            gap = abs(produced["cv"].get((fi, g), float("inf"))
                      - ref.cv[(fi, g)])
            for name in _point_names(fam, g, stable_from):
                numbers[name] = max(numbers.get(name, 0.0), float(gap))
    ranked = sorted(ref.cv.values())
    numbers["winner_regret"] = float(ranked[-1] - ref.cv[(ci, gi)])
    # how close the reference's own race was: what naming its runner-up
    # the winner would read as regret
    numbers["ref_top2_gap"] = float(ranked[-1] - ranked[-2])
    numbers["winner_cv_gap"] = abs(
        produced["cv"].get((ci, gi), float("inf")) - ref.cv[(ci, gi)])
    if (ci, gi) not in ref.refit_scores:     # a control run asks again
        ref.refit_scores[(ci, gi)], = reference.reference_refit(
            ref, pcfg, ci, gi, [ref.X[ref.holdout_idx]])
    s = ref.refit_scores[(ci, gi)]
    auroc, aupr = reference.exact_curves(ref.y[ref.holdout_idx], s)
    numbers["holdout_auroc_gap_linear"] = abs(produced["holdout"][0] - auroc)
    numbers["holdout_aupr_gap_linear"] = abs(produced["holdout"][1] - aupr)
    # the refit winner's score of each compared holdout row
    pos = np.searchsorted(ref.holdout_idx, produced["holdout_rows"])
    want = reference.class1_score(pcfg["zoo"][ci]["trainer"], s[pos])
    got = produced["holdout_scores"]
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        gap = np.full(want.shape, np.inf)
    else:
        gap = np.abs(got - want)
    numbers["holdout_score_gap_linear"] = float(np.median(gap))
    numbers["holdout_score_gap_mean_linear"] = float(np.mean(gap))
    numbers["holdout_score_gap_p99_linear"] = float(np.quantile(gap, 0.99))
    numbers["holdout_score_gap_max_linear"] = float(np.max(gap))
    return numbers
