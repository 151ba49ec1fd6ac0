"""The ``taxi_duration_train`` cell's self-check, off the chip.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_taxi_selfcheck.py

A sound run at a size a test can hold is correct under the cell's own
limits; the control (the reference one precision step down in the
program's place), half of the rows, another table's model and the
runner-up named winner are not; a program that does not solve least
squares is refused at import; the two new per-layer readers return a value
on a recorded ``RunFacts`` and ``None`` on an empty one. The sound run
takes 80,000 rows: at 20,000 a depth-12 leaf holds a handful of rows and
float32 sums flip near-tied splits (``cv_gap_rf`` 1.0e-4 there, 4.5e-5 at
80,000; cpu). The control and the faults take 20,000, where they read
``fe_max_abs`` 0.249 (coordinates in bfloat16), ``cv_gap_linreg`` 2.7e-3
and 4.2e-3 (half, stale) and ``winner_regret`` 2.6e-2 (cpu). Slow (one to
three minutes a case on the CPU).
"""

from __future__ import annotations

import importlib
import json

import pytest

from chipbench import control_taxi, run
from chipbench.run import RunFacts

SEED = "11"
ARGS = ["--workload", "taxi_duration_train", "--allow-cpu"]


def test_a_sound_run_is_correct():
    rc = run.main([*ARGS, "--rows", "80000", "--seed", SEED, "--seconds",
                   "1", "--trace", "0"])
    res = run.LAST
    assert rc == 0 and res["correct"], res["compared"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["compared"]) == {
        "fe_max_abs", "kept_mismatch", "sweep_failures",
        "sweep_loop_fallbacks", "cv_gap_linreg", "cv_gap_rf",
        "winner_regret", "holdout_score_gap_tree"}
    assert res["compared"]["fe_max_abs"]["value"] < 1e-5
    assert res["compared"]["sweep_loop_fallbacks"]["value"] == 0.0


def test_a_program_that_does_not_solve_least_squares_is_refused(
        monkeypatch):
    """The parent of this configuration's PR under its benchmark files: a
    clean exit before any data is made (``compare_taxi``'s docstring says
    why)."""
    from chipbench import compare_taxi
    from transmogrifai_tpu.models import linear
    monkeypatch.delattr(linear, "_gram_folds")
    with pytest.raises(SystemExit, match="least squares"):
        compare_taxi._refuse_a_program_that_does_not_solve_least_squares()


@pytest.mark.parametrize("fault,over", [
    (None, "fe_max_abs"), ("half", "cv_gap_linreg"),
    ("stale", "cv_gap_rf"), ("winner", "winner_regret")])
def test_control_and_faults_are_not_correct(capsys, fault, over):
    argv = [*ARGS, "--rows", "20000", "--seeds", SEED] + (
        ["--fault", fault] if fault else [])
    rc = control_taxi.main(argv)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and not line["control_correct"]
    assert over in line["over"], line["over"]


def read(name: str, facts):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(facts)


NEW = ("linear_gram_roofline", "sweep_linear_descent_lanes")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_new_readers_are_silent_on_an_empty_run():
    empty = RunFacts(cell={}, config={}, traffic={})
    assert [read(n, empty) for n in NEW] == [None] * len(NEW)
    # a program older than the counter and the Gram program
    from types import SimpleNamespace as NS
    config = run.load_json(run.HERE, "configs", "nyc_taxi_duration.json")
    older = RunFacts(cell={}, config=config, traffic={}, units=1,
                     counters={"sweepHostSyncs": 1},
                     shapes={"n_fold_train": 10, "n_train": 15, "d": 2,
                             "k": 3, "winner": (1, 1)},
                     trace=NS(kernel_seconds=lambda match: 0.0),
                     peaks=PEAKS)
    assert [read(n, older) for n in NEW] == [None] * len(NEW)


def test_new_readers_read_a_recorded_run():
    from types import SimpleNamespace as NS
    config = run.load_json(run.HERE, "configs", "nyc_taxi_duration.json")
    shapes = {"n_fold_train": 875_187, "n_train": 1_312_780, "d": 9,
              "k": 3, "winner": (1, 1)}
    trace = NS(kernel_seconds=lambda match: 0.02 if match(
        "jit__gram_folds/fusion") and not match("jit__train_linear/fusion")
        else 0.0)
    facts = RunFacts(cell={}, config=config, traffic={}, units=2,
                     counters={"linearDescentLanes": 0}, shapes=shapes,
                     trace=trace, peaks=PEAKS)
    assert read("sweep_linear_descent_lanes", facts) == 0.0
    # the sweep's 3 fold Grams of the training split (a forest wins: no
    # refit Gram), memory-bound: the split and the 3 fold weightings read
    # once, two trains
    least = 2 * (4.0 * 1_312_780 * 9 + 4.0 * 3 * 1_312_780) / 819e9
    got = read("linear_gram_roofline", facts)
    assert got == pytest.approx(100 * least / 0.02)
    assert 0 < got < 100
    # a linear winner adds its refit's one weighting
    facts.shapes = dict(shapes, winner=(0, 2))
    assert read("linear_gram_roofline", facts) == pytest.approx(
        100 * 2 * (4.0 * 1_312_780 * 9 * 2 + 4.0 * 4 * 1_312_780)
        / 819e9 / 0.02)
