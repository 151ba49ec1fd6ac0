"""The ``covtype_train`` cell's self-check, off the chip.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_covtype_selfcheck.py

A sound run at a size a test can hold is correct; the control (the
reference one precision step down in the program's place), half of the
rows, another table's model and the runner-up named winner are not; the
three new per-layer readers return a value on a recorded ``RunFacts`` and
``None`` on an empty one. The size: 20,000 rows, the smallest tried at
which the cell's own limits hold everything, unchanged: the depth-12 forest
wins there as at the cell's size (by 1.1e-2 of F1 over the depth-6 one; a
validation row is 2.4e-4 of a fold's 4,200), the sound run reads
``cv_gap_lr_l2_stable`` 5e-8, ``cv_gap_rf`` 2e-8 and ``holdout_prob_gap`` 3e-8
(cpu), half of the rows and another table's model read ``cv_gap_rf`` 1.9e-2
and 2.1e-3 against the 1e-4 limit, and a program without the class axis is
refused at import. Slow (one to two minutes a case on the CPU).
"""

from __future__ import annotations

import importlib
import json

import pytest

from chipbench import control_covtype, run
from chipbench.run import RunFacts

SEED, ROWS = "11", 20_000
ARGS = ["--workload", "covtype_train", "--allow-cpu", "--rows", str(ROWS)]


def test_a_sound_run_is_correct():
    rc = run.main([*ARGS, "--seed", SEED, "--seconds", "1", "--trace", "0"])
    res = run.LAST
    assert rc == 0 and res["correct"], res["compared"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert res["compared"]["fe_max_abs"]["value"] == 0.0
    assert res["compared"]["kept_mismatch"]["value"] == 0.0
    assert set(res["compared"]) == {
        "fe_max_abs", "kept_mismatch", "sweep_failures",
        "sweep_loop_fallbacks", "cv_gap_lr_l2_stable", "cv_gap_rf",
        "winner_regret",
        "holdout_f1_gap", "holdout_prob_gap"}
    assert res["compared"]["sweep_loop_fallbacks"]["value"] == 0.0


def test_a_run_that_takes_the_per_fold_loop_is_not_correct(monkeypatch):
    """The cell is the fold-stacked sweep: the same zoo through the loop
    (here for a reason the selector observes, a budget nothing fits) gives
    the same answers and is another cell."""
    monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET", "1")
    rc = run.main([*ARGS, "--seed", SEED, "--seconds", "1", "--trace", "0"])
    res = run.LAST
    assert rc != 0 and not res["correct"]
    over = {k for k, c in res["compared"].items()
            if isinstance(c, dict) and not c["value"] <= c["limit"]}
    assert over == {"sweep_loop_fallbacks"}, res["compared"]


def test_a_program_without_the_stacked_multiclass_sweep_is_refused(
        monkeypatch):
    """The parent of PR 31 under this PR's benchmark files: a clean exit
    before any data is made (``compare_covtype``'s docstring says why)."""
    from chipbench import compare_covtype
    from transmogrifai_tpu.evaluators.multi import (
        OpMultiClassificationEvaluator,
    )
    monkeypatch.delattr(OpMultiClassificationEvaluator,
                        "metric_batch_scores_folds_device")
    with pytest.raises(SystemExit, match="fold-batched device metric"):
        compare_covtype._refuse_a_program_without_the_stacked_multiclass_sweep()


@pytest.mark.parametrize("fault,over", [
    (None, "fe_max_abs"), ("half", "cv_gap_rf"),
    ("stale", "cv_gap_rf"), ("winner", "winner_regret")])
def test_control_and_faults_are_not_correct(capsys, fault, over):
    argv = [*ARGS, "--seeds", SEED] + (["--fault", fault] if fault else [])
    rc = control_covtype.main(argv)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and not line["control_correct"]
    assert over in line["over"], line["over"]


def read(name: str, facts):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(facts)


NEW = ("sweep_loop_fallbacks", "eval_multiclass_host_s",
       "tree_ova_hist_roofline")


def test_new_readers_are_silent_on_an_empty_run():
    empty = RunFacts(cell={}, config={}, traffic={})
    assert [read(n, empty) for n in NEW] == [None] * len(NEW)
    # a program older than the counter and the span, a binary cell's shapes
    from types import SimpleNamespace as NS
    older = RunFacts(cell={}, config={}, traffic={}, units=1,
                     counters={"sweepHostSyncs": 1},
                     spans=[(0.0, 1.0, "stage.fit")],
                     shapes={"n_fold_train": 10, "n_train": 15, "d": 2,
                             "k": 3, "winner": (0, 0)},
                     trace=NS(kernel_seconds=lambda match: 1.0),
                     peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9})
    assert [read(n, older) for n in NEW] == [None] * len(NEW)


def test_new_readers_read_a_recorded_run():
    from types import SimpleNamespace as NS
    config = run.load_json(run.HERE, "configs", "covtype_multi.json")
    spans = [(0.0, 1.5, "evaluate.multiclass"),
             (2.0, 2.5, "evaluate.multiclass"),
             (10.0, 11.5, "evaluate.multiclass"),
             (12.0, 12.5, "evaluate.multiclass"),
             (3.0, 9.0, "selector.sweep")]
    shapes = {"n_fold_train": 348_608, "n_train": 522_911, "d": 54, "k": 3,
              "K": 7, "winner": (1, 1)}
    trace = NS(kernel_seconds=lambda match: 80.0 if match(
        "jit_train_score_stacked/fusion") and match(
        "jit_train_ensemble/copy") and not match("jit__train_linear/fusion")
        else 0.0)
    facts = RunFacts(
        cell={}, config=config, traffic={}, units=2, spans=spans,
        counters={"sweepLoopFallbacks": 0, "sweepHostSyncs": 2},
        shapes=shapes, trace=trace,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert read("sweep_loop_fallbacks", facts) == 0.0
    assert read("eval_multiclass_host_s", facts) == pytest.approx(2.0)
    # memory-bound: a code byte a (row, feature, level, class), 8 bytes a
    # (row, level, class), and the node tables: 7 classes x 3 trees x
    # (3 folds x 18 levels over 348,608 rows + 12 over 522,911)
    rows_levels = 7 * 3 * (3 * 18 * 348_608 + 12 * 522_911)
    tables = 7 * 3 * (3 * (63 + 4095) + 4095) * 54 * 64 * 8
    least = (rows_levels * (54 + 8) + tables) / 819e9
    got = read("tree_ova_hist_roofline", facts)
    assert got == pytest.approx(100 * least * 2 / 80.0)
    assert 0 < got < 100
