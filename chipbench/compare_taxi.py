"""The comparison that decides ``correct`` for the ``nyc_taxi_duration``
zoo.

Named by the configuration under ``comparison``; the counterpart of
``chipbench.compare`` for a real-valued label. ``collect`` copies out of the
program what one timed unit produced: the vector on a sample of rows, the
kept columns, every grid point's mean fold RMSE, the winner, its holdout
RMSE and its prediction of each compared holdout row. ``check`` runs
``reference_taxi`` on the same table once the window has closed. Limits live
in the configuration file, with the readings they were set from in PERF.md.
"""

from __future__ import annotations

import numpy as np

from chipbench import compare, opcount_regression
from chipbench import reference_taxi as reference

sample_rows = compare.sample_rows


def _refuse_a_program_that_does_not_solve_least_squares() -> None:
    """The cell's linear family is least squares SOLVED from fold Grams
    (``models/linear.py::_gram_folds``), every one of its 8 points held by
    ``cv_gap_linreg``. A program without that solver trains them by 200
    Adam steps, which no limit of this configuration was set for, and it
    also lacks what the configuration's typed columns need of it (a date's
    phase to well under a second on the device, a regression forest's
    prediction with its base): it is refused here, where
    ``chipbench.run`` first reaches this cell's files, before any data is
    made, so that it fails at once and cleanly and not at a time limit."""
    from transmogrifai_tpu.models import linear
    if not hasattr(linear, "_gram_folds"):
        raise SystemExit(
            "chipbench: this program does not solve least squares from "
            "fold Grams; nyc_taxi_duration needs it")


_refuse_a_program_that_does_not_solve_least_squares()


def collect(model, handles, summary, frame, pcfg: dict, rng) -> dict:
    from transmogrifai_tpu.utils.profiling import sweep_counters
    n = frame.n_rows
    fallbacks = sweep_counters.run_to_json().get("sweepLoopFallbacks")
    idx, hidx = sample_rows(n, pcfg, rng)
    vec = model.compute_data_up_to(handles["vector"], frame.take(idx))
    docs = model.score(frame.take(hidx))[handles["prediction"].name].values
    hold = summary.holdout_evaluation["regression"]
    return {
        "n_rows": n,
        "sample_idx": idx,
        "vector": np.asarray(vec[handles["vector"].name].values, np.float32),
        "keep": np.asarray(compare._stage(
            model, "DropIndicesModel").keep_indices, np.int64),
        "winner": compare._grid_key(summary.best_model_name),
        "cv": {compare._grid_key(r.model_name):
               float(r.metric_values[summary.validation_metric])
               for r in summary.validation_results},
        "holdout": float(hold["rmse"]),
        "holdout_rows": hidx,
        "holdout_scores": np.asarray([d["prediction"] for d in docs],
                                     np.float64),
        "failures": len(summary.failures),
        "loop_fallbacks": fallbacks,
    }


shapes_of = compare.shapes_of


def window_flops(config: dict, shapes: dict, units: int) -> dict:
    """Analytic FLOPs of all the trains in the window, by kind."""
    work = opcount_regression.zoo_train_work(config["pipeline"]["zoo"],
                                             shapes)
    return {kind: v[0] * units for kind, v in work.items()}


def _kind(fam: dict) -> str:
    return "linear" if fam["trainer"] == reference.LINEAR else "tree"


def _fe_numbers(produced, table, ref) -> dict:
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


def as_program(stand_in, table, produced: dict, config: dict,
               lowp: bool = False) -> dict:
    """A reference run (the control's, or one with a fault planted) put in
    the program's place: its sweep metrics, its feature matrix on the
    compared rows, its kept columns, and its refit winner's holdout RMSE
    and predictions of the unit's compared holdout rows."""
    ci, gi = produced["winner"]
    out = dict(produced)
    out.setdefault("failures", 0)
    out.setdefault("loop_fallbacks", 0)
    out["cv"] = dict(stand_in.cv)
    round_ = reference.lowp_round if lowp else (lambda x: x)
    out["vector"] = round_(reference.apply_fe(
        table.take(produced["sample_idx"]), stand_in.fit))
    out["keep"] = np.nonzero(stand_in.keep)[0]
    rows = round_(reference.apply_fe(
        table.take(produced["holdout_rows"]), stand_in.fit))
    own, served = reference.reference_refit(
        stand_in, config, ci, gi,
        [stand_in.X[stand_in.holdout_idx], rows[:, stand_in.keep]],
        lowp=lowp)
    out["holdout"] = reference.rmse(own, stand_in.y[stand_in.holdout_idx])
    out["holdout_scores"] = np.asarray(served, np.float64)
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
    if produced.get("loop_fallbacks") is not None:   # else: no reading
        numbers["sweep_loop_fallbacks"] = float(produced["loop_fallbacks"])
    for fi, fam in enumerate(pcfg["zoo"]):
        name = f"cv_gap_{fam['short']}"
        for g in range(len(fam["grid"])):
            gap = abs(produced["cv"].get((fi, g), float("inf"))
                      - ref.cv[(fi, g)])
            numbers[name] = max(numbers.get(name, 0.0), float(gap))
    ranked = sorted(ref.cv.values())          # RMSE: the least wins
    numbers["winner_regret"] = float(ref.cv[(ci, gi)] - ranked[0])
    # how close the reference's own race was: what naming its runner-up
    # the winner would read as regret
    numbers["ref_top2_gap"] = float(ranked[1] - ranked[0])
    numbers["winner_cv_gap"] = abs(
        produced["cv"].get((ci, gi), float("inf")) - ref.cv[(ci, gi)])
    if (ci, gi) not in ref.refit_pred:       # a control run asks again
        ref.refit_pred[(ci, gi)], = reference.reference_refit(
            ref, config, ci, gi, [ref.X[ref.holdout_idx]])
    pred = ref.refit_pred[(ci, gi)]
    kind = _kind(pcfg["zoo"][ci])
    numbers["holdout_rmse_gap"] = abs(
        produced["holdout"] - reference.rmse(pred, ref.y[ref.holdout_idx]))
    # the refit winner's prediction of each compared holdout row
    want = pred[np.searchsorted(ref.holdout_idx, produced["holdout_rows"])]
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
