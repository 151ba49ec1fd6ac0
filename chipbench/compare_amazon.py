"""The comparison that decides ``correct`` for the ``amazon_polarity_text``
configuration.

Named by the configuration under ``comparison``; the counterpart of
``chipbench.compare_criteo`` for columns of free text at a width where the
pure-L2 logistic points train by Newton. What a timed unit produced is
collected as there (``compare.collect``), with the window's counters of
what left the normal path beside it; ``check`` runs ``reference_amazon`` on
the same table once the window has closed. Limits live in the configuration
file, with the readings they were set from in PERF.md.
"""

from __future__ import annotations

import numpy as np

from chipbench import compare, opcount_text
from chipbench import reference_amazon as reference

sample_rows = compare.sample_rows
window_flops = compare.window_flops


def _refuse_a_program_without_a_free_text_path() -> None:
    """The cell's traffic is a column of free text filled from row-ordered
    entries that one native pass a column makes
    (``ops/vectorizers/hashing.py::text_column_entries``). A program
    without that entry point sends such a column through a dictionary of
    as many strings as there are rows and then tokenizes it in a Python
    loop a row (88 microseconds a row; ISSUE 33): another traffic than the
    cell states, and minutes a train. It is refused here, where
    ``chipbench.run`` first reaches this cell's files, before any data is
    made, so that it fails at once and cleanly. A program that HAS the path
    and leaves it fails ``fe_hash_fallbacks`` in ``check`` instead."""
    from transmogrifai_tpu.ops.vectorizers import hashing
    if not hasattr(hashing, "text_column_entries"):
        raise SystemExit(
            "chipbench: this program has no columnar free-text path "
            "(ops/vectorizers/hashing.py::text_column_entries); "
            "amazon_polarity_text needs it")


_refuse_a_program_without_a_free_text_path()


def collect(model, handles, summary, frame, pcfg: dict, rng) -> dict:
    from transmogrifai_tpu.utils.profiling import sweep_counters
    # what the window's trains sent off the normal path, read before the
    # read-back below transforms anything itself
    counters = sweep_counters.run_to_json()
    out = compare.collect(model, handles, summary, frame, pcfg, rng)
    out["loop_fallbacks"] = counters.get("sweepLoopFallbacks")
    out["hash_fallbacks"] = counters.get("feHashPerRowFallbacks")
    return out


def shapes_of(produced: dict, config: dict, n_rows: int) -> dict:
    """``compare.shapes_of`` and the blocks the text fill writes: one of
    ``num_hash_features + 2`` columns a hashed column."""
    width = int(config["num_hash_features"]) + 2
    return dict(compare.shapes_of(produced, config, n_rows),
                text_block_width=width,
                text_blocks=int(produced["vector"].shape[1]) // width)


def _fe_numbers(produced: dict, table, ref) -> dict:
    want = reference.apply_fe(table, ref.fit, rows=produced["sample_idx"])
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
    compared rows, its kept columns, and its refit winner's holdout metrics
    and scores of the unit's compared holdout rows."""
    pcfg = config["pipeline"]
    ci, gi = produced["winner"]
    out = dict(produced)
    for key in ("failures", "loop_fallbacks", "hash_fallbacks"):
        out.setdefault(key, 0)
    out["cv"] = dict(stand_in.cv)
    out["vector"] = reference.apply_fe(
        table, stand_in.fit, rows=produced["sample_idx"])
    out["keep"] = np.nonzero(stand_in.keep)[0]
    rows = reference.apply_fe(
        table, stand_in.fit, rows=produced["holdout_rows"])
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
    for key, name in (("loop_fallbacks", "sweep_loop_fallbacks"),
                      ("hash_fallbacks", "fe_hash_fallbacks")):
        if produced.get(key) is not None:        # else: no reading
            numbers[name] = float(produced[key])
    for fi, fam in enumerate(pcfg["zoo"]):
        for g in range(len(fam["grid"])):
            name = f"cv_gap_{fam['short']}"
            if fam["trainer"] == "logistic":   # Newton points apart from Adam
                name += "_l2" if compare._newton(fam, g) else "_l1"
            gap = abs(produced["cv"].get((fi, g), float("inf"))
                      - ref.cv[(fi, g)])
            numbers[name] = max(numbers.get(name, 0.0), float(gap))
    ranked = sorted(ref.cv.values())
    numbers["winner_regret"] = float(ranked[-1] - ref.cv[(ci, gi)])
    # how close the reference's own race was: what naming its runner-up
    # the winner would read as regret
    numbers["ref_top2_gap"] = float(ranked[-1] - ranked[-2])
    # how far the reference's best pure-L2 (Newton) point leads its best
    # point trained by Adam: the room a limit cycle has before it names
    # the winner
    newton = [v for (fi, g), v in ref.cv.items()
              if compare._newton(pcfg["zoo"][fi], g)]
    adam = [v for (fi, g), v in ref.cv.items()
            if not compare._newton(pcfg["zoo"][fi], g)]
    if newton and adam:
        numbers["ref_l2_lead"] = float(max(newton) - max(adam))
    numbers["winner_cv_gap"] = abs(
        produced["cv"].get((ci, gi), float("inf")) - ref.cv[(ci, gi)])
    s, = reference.reference_refit(ref, pcfg, ci, gi,
                                   [ref.X[ref.holdout_idx]])
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
