"""Least squares solved from fold Grams (``models/linear.py::_gram_folds``).

The solver against a plain float64 numpy fit for every (fold, point) lane,
pure L2 and elastic net, with a column constant among one fold's rows; the
stacked sweep against the per-fold loop; the ``linearDescentLanes`` counter
on both sides of the Gram's reach; a regression forest's prediction keeping
its label's mean.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_taxi
from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models import linear
from transmogrifai_tpu.models.linear import OpLinearRegression
from transmogrifai_tpu.selector import DataSplitter, RegressionModelSelector
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.uid import UID
from transmogrifai_tpu.utils.profiling import sweep_counters
from transmogrifai_tpu.workflow import Workflow

GRID = [{"reg_param": r, "elastic_net_param": e}
        for r in (0.001, 0.01, 0.1, 0.2) for e in (0.0, 0.5)]


def _folds_table(n: int, k: int = 3, seed: int = 0):
    """Columns of very different offsets and scales, a 0/1 column, and a
    column constant among the rows weighting 0 trains on (its other rows
    vary); ``wf [k, n]``: each fold a weighting, 0 on its validation
    rows."""
    rng = np.random.default_rng(seed)
    fold = rng.integers(0, k, n)
    X = rng.normal(size=(n, 6)) * [1, 10, 0.1, 3, 1, 1] \
        + [0, 100, -5, 40.7, 0, 0]
    X[:, 4] = rng.integers(0, 2, n)
    X[:, 5] = np.where(fold != 0, 1.0, rng.uniform(size=n))
    y = 0.5 * X[:, 0] - 0.02 * X[:, 1] + 0.3 * X[:, 3] \
        + rng.normal(size=n) + 7.0
    wf = np.stack([(fold != f).astype(np.float32) for f in range(k)])
    return X.astype(np.float32), y, wf


@pytest.mark.parametrize("n", [2_000, 20_000])
def test_gram_lanes_match_float64(n):
    X, y, wf = _folds_table(n)
    rp = jnp.asarray([g["reg_param"] for g in GRID], jnp.float32)
    en = jnp.asarray([g["elastic_net_param"] for g in GRID], jnp.float32)
    Ws, bs = linear._gram_folds(
        jnp.asarray(X), jnp.asarray(y, jnp.float32), jnp.asarray(wf), rp,
        en, chunk=linear._gram_chunk_rows(n, 6, 3))
    Ws, bs = np.asarray(Ws)[..., 0], np.asarray(bs)[..., 0]
    assert Ws.shape == (3, len(GRID), 6) and bs.shape == (3, len(GRID))
    X64 = X.astype(np.float64)
    for f in range(3):
        rows = wf[f] > 0
        want = reference_taxi.linreg_fit(X[rows], y[rows], GRID)
        for g, (W, b) in enumerate(want):
            # coefficients in the lane's standardized units, predictions in
            # the label's: float32 Grams against float64 numpy
            scale = X64[rows].std(axis=0) / y[rows].std()
            np.testing.assert_allclose(Ws[f, g] * scale, W * scale,
                                       rtol=0, atol=1e-5)
            np.testing.assert_allclose(X64 @ Ws[f, g] + bs[f, g],
                                       X64 @ W + b, rtol=0,
                                       atol=1e-5 * y.std())
    # constant among weighting 0's rows: weight 0 there, not elsewhere
    assert np.all(Ws[0, :, 5] == 0.0)
    assert np.all(Ws[1:, ::2, 5] != 0.0)
    # the L1 points are sparser than the pure-L2 ones beside them
    assert np.count_nonzero(Ws[:, 7]) < np.count_nonzero(Ws[:, 6])


def _reg_frame(n=600, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n)
    x2 = rng.normal(size=n) * 5 + 20
    x3 = rng.normal(size=n)
    y = 2.0 * x - 0.3 * x2 + 0.1 * x3 + 0.5 * rng.normal(size=n) + 3
    return fr.HostFrame.from_dict({
        "x": (ft.Real, x.tolist()), "x2": (ft.Real, x2.tolist()),
        "x3": (ft.Real, x3.tolist()), "label": (ft.RealNN, y.tolist())})


def _train(frame):
    UID.reset()
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    vec = transmogrify(list(feats.values()))
    sel = RegressionModelSelector.with_cross_validation(
        n_folds=3, seed=1, models_and_parameters=[(OpLinearRegression(),
                                                   GRID)],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    pred = label.transform_with(sel, vec)
    return Workflow().set_input_frame(frame).set_result_features(
        pred).train().selector_summary()


def test_stacked_and_loop_agree(fold_loop):
    frame = _reg_frame()
    stacked = _train(frame)
    with fold_loop():
        loop = _train(frame)
    assert stacked.best_model_name == loop.best_model_name
    got = {r.model_name: r.metric_values["RMSE"]
           for r in stacked.validation_results}
    want = {r.model_name: r.metric_values["RMSE"]
            for r in loop.validation_results}
    assert set(got) == set(want) and len(got) == len(GRID)
    for name, v in got.items():
        assert v == pytest.approx(want[name], rel=1e-5), name
    assert stacked.holdout_evaluation["regression"]["rmse"] == \
        pytest.approx(loop.holdout_evaluation["regression"]["rmse"],
                      rel=1e-5)


def test_descent_lanes_counted_past_the_gram(monkeypatch):
    frame = _reg_frame()
    sweep_counters.reset()
    _train(frame)
    assert sweep_counters.run_to_json()["linearDescentLanes"] == 0
    # past the Gram's reach (here: a cap under the 3 columns SanityChecker
    # keeps) the sweep's 3 folds x 8 points and the winner's warm refit
    # take the descent
    monkeypatch.setattr(OpLinearRegression, "_GRAM_MAX_D", 2)
    sweep_counters.reset()
    _train(frame)
    assert sweep_counters.run_to_json()["linearDescentLanes"] == \
        3 * len(GRID) + 1


def test_regression_forest_keeps_its_label_mean():
    """A forest's trees grow on the label less its mean; the prediction
    adds the mean back, so a label far from 0 is predicted where it is."""
    from transmogrifai_tpu.models.trees import OpRandomForestRegressor
    rng = np.random.default_rng(5)
    X = rng.normal(size=(800, 3)).astype(np.float32)
    y = 100.0 + 3.0 * X[:, 0] + np.where(X[:, 1] > 0, 2.0, -2.0)
    est = OpRandomForestRegressor(num_trees=5, max_depth=4)
    model = est.fit_arrays(jnp.asarray(X), jnp.asarray(y, jnp.float32),
                           jnp.ones(800, jnp.float32), est.params)
    pred = np.asarray(model.predict_arrays(jnp.asarray(X)).prediction)
    assert abs(pred.mean() - y.mean()) < 0.2
    assert np.sqrt(np.mean((pred - y) ** 2)) < 0.5 * y.std()
