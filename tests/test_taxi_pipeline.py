"""The ``nyc_taxi_duration`` configuration through the program's normal path
at 20,000 rows, against its plain float64 reference
(``chipbench/reference_taxi.py``): typed columns (PickList, DateTime,
Integral, two Geolocations) through ``transmogrify`` and SanityChecker, the
regression selector's default zoo at the configuration's grids, the winner's
refit.

Off the chip the tree histograms take exact operands (as a rehearsal does),
so every gap is float32 against float64: the vector to 1e-5, every one of
the 8 least-squares points' fold RMSE to 5e-6 (solved from fold Grams:
1e-7 to 8e-7 read), the trees' to 5e-4 (at 20,000 rows a depth-12 leaf holds
a handful of rows and float32 sums flip near-tied splits: 1e-4 read).
"""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import compare_taxi, control, run
from chipbench.units import train, train_typed
from transmogrifai_tpu.utils.profiling import sweep_counters

ROWS, SEED = 20_000, 7


@pytest.fixture(scope="module")
def numbers():
    _, _, config, _ = run.load_cell("taxi_duration_train", ROWS)
    run.rehearse_off_chip(config)
    table, _ = control._unit(config, compare_taxi, SEED)
    frame = train_typed.to_frame(table, config["dataset"])
    sweep_counters.reset()
    model, handles, summary = train.train_unit(frame, config["pipeline"])
    produced = compare_taxi.collect(
        model, handles, summary, frame, config["pipeline"],
        np.random.default_rng(1))
    return produced, compare_taxi.check(produced, table, config)


def test_the_typed_vector_and_its_checks(numbers):
    produced, got = numbers
    # 21 columns: passenger count and its null, the pickup hour's sin, cos
    # and null, two pivots of 2 values with OTHER and null, two
    # geolocations of 3 values and null; SanityChecker keeps the 9 that
    # vary, one of each pivot's two values
    assert produced["vector"].shape[1] == 21
    assert produced["keep"].tolist() == [0, 2, 3, 5, 9, 13, 14, 17, 18]
    assert got["fe_max_abs"] <= 1e-5
    assert got["kept_mismatch"] == 0.0


def test_the_sweep_against_the_reference(numbers):
    produced, got = numbers
    assert got["sweep_failures"] == 0.0
    assert got["sweep_loop_fallbacks"] == 0.0
    assert len([k for k in produced["cv"] if k[0] == 0]) == 8
    assert got["cv_gap_linreg"] <= 5e-6
    assert got["cv_gap_rf"] <= 5e-4 and got["cv_gap_gbt"] <= 5e-4
    # a tree family leads the linear one clearly
    lin = min(v for k, v in produced["cv"].items() if k[0] == 0)
    assert produced["winner"][0] != 0
    assert produced["cv"][tuple(produced["winner"])] < lin - 0.05
    assert got["winner_regret"] <= 3e-4


def test_the_refit_winner_against_the_reference(numbers):
    _, got = numbers
    assert got["holdout_score_gap_tree"] <= 5e-3
    assert got["holdout_rmse_gap"] <= 5e-3
