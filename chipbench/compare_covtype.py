"""The comparison that decides ``correct`` for the ``covtype_multi`` zoo.

Named by the configuration under ``comparison``; the counterpart of
``chipbench.compare`` for a label of several classes. ``collect`` copies out
of the program what one timed unit produced: as ``compare.collect`` does,
with the holdout's F1 and error in place of the curves and the K class
probabilities of each compared holdout row in place of one score. ``check``
runs ``reference_covtype`` on the same table once the window has closed.
Limits live in the configuration file, with the readings they were set from
in PERF.md.
"""

from __future__ import annotations

import numpy as np

from chipbench import compare, opcount_multi
from chipbench import reference_covtype as reference

sample_rows = compare.sample_rows


def _refuse_a_program_without_the_stacked_multiclass_sweep() -> None:
    """The cell's traffic is the fold-stacked, one-sync sweep of a label of
    several classes. The selector takes that path for an evaluator that has
    ``metric_batch_scores_folds_device`` (its own probe, for every
    evaluator); a program whose multiclass evaluator lacks it trains this
    zoo through the per-fold loop: another traffic (30 host syncs a train,
    the K one-vs-all trees of a round under a ``vmap``) than the cell
    states, and one whose cold set-up was never held against a run's time
    limit (its depth-12 forest program alone takes 285 s and 25 GB of host
    memory to compile for the chip at a fold's rows: PERF.md section 6,
    PR 31). Such a program cannot run this configuration: it is refused
    here, where ``chipbench.run`` first reaches this cell's files, before
    any data is made, so that it fails at once and cleanly and not at a
    time limit. A program that HAS the path and leaves it fails
    ``sweep_loop_fallbacks`` in ``check`` instead."""
    from transmogrifai_tpu.evaluators.multi import (
        OpMultiClassificationEvaluator,
    )
    if not hasattr(OpMultiClassificationEvaluator,
                   "metric_batch_scores_folds_device"):
        raise SystemExit(
            "chipbench: this program's multiclass evaluator has no "
            "fold-batched device metric; covtype_multi needs the "
            "fold-stacked sweep of a multiclass label")


_refuse_a_program_without_the_stacked_multiclass_sweep()


def collect(model, handles, summary, frame, pcfg: dict, rng) -> dict:
    from transmogrifai_tpu.utils.profiling import sweep_counters
    n = frame.n_rows
    # families or lane groups the window's trains sent to the per-fold loop
    fallbacks = sweep_counters.run_to_json().get("sweepLoopFallbacks")
    idx, hidx = sample_rows(n, pcfg, rng)
    vec = model.compute_data_up_to(handles["vector"], frame.take(idx))
    docs = model.score(frame.take(hidx))[handles["prediction"].name].values
    K = sum(1 for key in docs[0] if key.startswith("probability_"))
    hold = summary.holdout_evaluation["multiclass classification"]
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
        "holdout": (float(hold["f1"]), float(hold["error"])),
        "holdout_rows": hidx,
        "holdout_scores": np.asarray(
            [[d[f"probability_{c}"] for c in range(K)] for d in docs],
            np.float64),
        "failures": len(summary.failures),
        "loop_fallbacks": fallbacks,
    }


def shapes_of(produced: dict, config: dict, n_rows: int) -> dict:
    return dict(compare.shapes_of(produced, config, n_rows),
                K=int(config["n_classes"]))


def window_flops(config: dict, shapes: dict, units: int) -> dict:
    """Analytic FLOPs of all the trains in the window, by kind."""
    work = opcount_multi.zoo_train_work(config["pipeline"]["zoo"], shapes)
    return {kind: v[0] * units for kind, v in work.items()}


def _holdout_metrics(y: np.ndarray, probs: np.ndarray, K: int) -> tuple:
    _, _, f1, error = reference.weighted_prf(
        y.astype(np.int64), np.argmax(probs, axis=1), K)
    return f1, error


def as_program(stand_in, table, produced: dict, config: dict,
               lowp: bool = False) -> dict:
    """A reference run (the control's, or one with a fault planted) put in
    the program's place: its sweep metrics, its feature matrix on the
    compared rows, its kept columns, and its refit winner's holdout metrics
    and class probabilities of the unit's compared holdout rows."""
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
    out["holdout"] = _holdout_metrics(
        stand_in.y[stand_in.holdout_idx], own, stand_in.K)
    out["holdout_scores"] = served
    return out


def check(produced: dict, table, config: dict, ref=None) -> dict:
    """The numbers compared (and those only reported) of one timed train
    against the plain reference's train of the same table (``ref``, where
    the caller has it already)."""
    pcfg = config["pipeline"]
    if ref is None:
        ref = reference.reference_train(table, config, sweep=True)
    ci, gi = produced["winner"]
    numbers = compare._fe_numbers(produced, table, ref)
    numbers["sweep_failures"] = float(produced.get("failures", 0))
    if produced.get("loop_fallbacks") is not None:   # else: no reading
        numbers["sweep_loop_fallbacks"] = float(produced["loop_fallbacks"])
    weight_max = float(config["stable_points"]["row_weight_max"])
    for fi, fam in enumerate(pcfg["zoo"]):
        for g, point in enumerate(fam["grid"]):
            gap = abs(produced["cv"].get((fi, g), float("inf"))
                      - ref.cv[(fi, g)])
            names = [f"cv_gap_{fam['short']}"]
            if "elastic_net_param" in point:     # the L1 points apart
                l1 = bool(float(point["elastic_net_param"]))
                names[0] += "_l1" if l1 else "_l2"
                # a pure-L2 point is held to the limit where the fold F1
                # does not stand on a few rows (``reference.row_weight``)
                if not l1 and ref.row_weight[(fi, g)] <= weight_max:
                    names.append(names[0] + "_stable")
            for name in names:
                numbers[name] = max(numbers.get(name, 0.0), float(gap))
    ranked = sorted(ref.cv.values())
    numbers["winner_regret"] = float(ranked[-1] - ref.cv[(ci, gi)])
    # how close the reference's own race was: what naming its runner-up
    # the winner would read as regret
    numbers["ref_top2_gap"] = float(ranked[-1] - ranked[-2])
    numbers["winner_cv_gap"] = abs(
        produced["cv"].get((ci, gi), float("inf")) - ref.cv[(ci, gi)])
    if (ci, gi) not in ref.refit_probs:      # a control run asks again
        ref.refit_probs[(ci, gi)], = reference.reference_refit(
            ref, config, ci, gi, [ref.X[ref.holdout_idx]])
    probs = ref.refit_probs[(ci, gi)]
    f1, error = _holdout_metrics(ref.y[ref.holdout_idx], probs, ref.K)
    numbers["holdout_f1_gap"] = abs(produced["holdout"][0] - f1)
    numbers["holdout_error_gap"] = abs(produced["holdout"][1] - error)
    # the refit winner's K probabilities of each compared holdout row: the
    # largest gap over the classes, a row
    want = probs[np.searchsorted(ref.holdout_idx, produced["holdout_rows"])]
    got = produced["holdout_scores"]
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        gap = np.full(want.shape[0], np.inf)
    else:
        gap = np.max(np.abs(got - want), axis=1)
    numbers["holdout_prob_gap"] = float(np.median(gap))
    numbers["holdout_prob_gap_mean"] = float(np.mean(gap))
    numbers["holdout_prob_gap_p99"] = float(np.quantile(gap, 0.99))
    numbers["holdout_prob_gap_max"] = float(np.max(gap))
    return numbers
