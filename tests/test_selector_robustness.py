"""CV sweep robustness: per-candidate failure isolation, maxWait budget,
transient-device retry (parity: reference OpValidator.scala:108 maxWait and
failed-future handling — a broken candidate must never abort train())."""

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector, DataSplitter,
)
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.utils.retry import (
    is_transient_device_error, with_device_retry,
)
from transmogrifai_tpu.workflow import Workflow


class ExplodingModel(OpLogisticRegression):
    """A candidate family that always raises during fit."""

    def grid_fit_arrays(self, X, y, w, grid):
        raise ValueError("deliberate candidate explosion")

    def fit_arrays(self, X, y, w, params):
        raise ValueError("deliberate candidate explosion")


from transmogrifai_tpu.models.linear import OpLinearRegression


class DivergingModel(OpLinearRegression):
    """Fits fine but predicts NaN (a diverged optimizer): the RMSE
    validation metric comes back non-finite."""

    def grid_predict_scores(self, models, X):
        return jnp.full((len(models), X.shape[0]), jnp.nan)


def _frame(n=240, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n).astype(float)
    x = rng.normal(size=n) + 0.8 * y
    return fr.HostFrame.from_dict({
        "x": (ft.Real, x.tolist()),
        "x2": (ft.Real, rng.normal(size=n).tolist()),
        "label": (ft.RealNN, y.tolist()),
    })


def _train(selector, frame):
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    vec = transmogrify(list(feats.values()), min_support=1)
    pred = label.transform_with(selector, vec)
    return (Workflow().set_input_frame(frame)
            .set_result_features(pred).train())


def test_exploding_candidate_is_isolated():
    frame = _frame()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (ExplodingModel(), [{"reg_param": 0.1}]),
            (OpLogisticRegression(max_iter=30),
             [{"reg_param": r} for r in (0.01, 0.1)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    model = _train(sel, frame)
    s = model.selector_summary()
    assert s.best_model_type == "OpLogisticRegression"
    assert len(s.failures) == 1
    assert "ExplodingModel" in s.failures[0]["modelName"]
    assert "deliberate candidate explosion" in s.failures[0]["reason"]
    # failures survive the summary JSON round-trip
    from transmogrifai_tpu.selector.model_selector import ModelSelectorSummary
    rt = ModelSelectorSummary.from_json(s.to_json())
    assert rt.failures == s.failures


def test_diverging_candidate_excluded_from_selection():
    from transmogrifai_tpu.selector import RegressionModelSelector
    frame = _frame(seed=3)
    sel = RegressionModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (DivergingModel(max_iter=5), [{"reg_param": 0.1}]),
            (OpLinearRegression(max_iter=30), [{"reg_param": 0.01}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    model = _train(sel, frame)
    s = model.selector_summary()
    assert s.best_model_type == "OpLinearRegression"
    assert any("non-finite" in f["reason"] for f in s.failures)
    # the diverged grid point is still reported with its NaN metric
    names = [r.model_name for r in s.validation_results]
    assert any("DivergingModel" in nm for nm in names)


def test_all_candidates_failing_raises():
    frame = _frame(seed=4)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[(ExplodingModel(), [{}])],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    with pytest.raises(RuntimeError, match="every candidate failed"):
        _train(sel, frame)


def test_max_wait_skips_later_families():
    frame = _frame(seed=5)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=30), [{"reg_param": 0.01}]),
            (OpLogisticRegression(max_iter=30), [{"reg_param": 0.1}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
        max_wait_s=0.0)  # budget exhausted immediately after first candidate
    model = _train(sel, frame)
    s = model.selector_summary()
    # the first family still scored (never end with zero candidates);
    # the second was skipped and recorded
    assert s.best_model_name.endswith("_0_0")
    assert any("max_wait" in f["reason"] for f in s.failures)


def test_max_wait_rescues_a_skipped_family_when_the_settle_fails():
    """``max_wait_s`` skipped the second family because the first had its
    metric future pending; that future fails at the settle (not an OOM), so
    nothing scored: the skipped family runs after all and wins, and the
    poisoned one is the only failure left on record."""

    class _Poisoned:
        def __array__(self, dtype=None):
            raise RuntimeError("poisoned program")

    frame = _frame(seed=5)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=30), [{"reg_param": 0.01}]),
            (OpLogisticRegression(max_iter=30), [{"reg_param": 0.1}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
        max_wait_s=0.0)
    sel.evaluators[0].metric_batch_scores_folds_device = (
        lambda y, scores, metric: _Poisoned())
    s = _train(sel, frame).selector_summary()
    assert s.best_model_name.endswith("_1_0")
    assert [f["modelName"] for f in s.failures] == ["OpLogisticRegression_0"]
    assert "poisoned program" in s.failures[0]["reason"]


def test_with_device_retry_transient_then_success():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("UNAVAILABLE: TPU device error — often a "
                               "kernel fault")
        return 42

    with pytest.warns(RuntimeWarning, match="transient device error"):
        assert with_device_retry(flaky, backoff_s=0.0) == 42
    assert calls["n"] == 2


def test_with_device_retry_passes_through_real_errors():
    def broken():
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        with_device_retry(broken, backoff_s=0.0)
    assert not is_transient_device_error(ValueError("UNAVAILABLE"))
    assert is_transient_device_error(RuntimeError("ABORTED: connection reset"))


def test_checkpointed_sweep_restarts(tmp_path):
    """Restartable sweep (the reference failure-recovery aux): completed
    (fold, family) batches persist and a re-run skips retraining them."""
    import json
    import os

    frame = _frame(seed=9)
    calls = {"n": 0}

    class CountingLR(OpLogisticRegression):
        def grid_fit_arrays(self, X, y, w, grid):
            calls["n"] += 1
            return super().grid_fit_arrays(X, y, w, grid)

    def make_sel(grid=(0.01, 0.1)):
        return BinaryClassificationModelSelector.with_cross_validation(
            n_folds=2, seed=1,
            models_and_parameters=[(CountingLR(max_iter=25),
                                    [{"reg_param": r} for r in grid])],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
            checkpoint_dir=str(tmp_path / "sweep"))

    ckpt = str(tmp_path / "sweep")
    model1 = _train(make_sel(), frame)
    fits_first = calls["n"]
    assert fits_first >= 2  # one grid fit per fold
    saved = json.load(open(os.path.join(ckpt, "sweep.json")))
    assert "fingerprint" in saved
    keys = sorted(saved["entries"])
    assert [k.split(":")[:2] for k in keys] == [["0", "0"], ["1", "0"]]
    assert all(len(v) == 2 for v in saved["entries"].values())

    # "restart": a fresh selector over the same checkpoint dir re-selects
    # the same winner WITHOUT refitting any sweep candidate (only the final
    # winner refit runs)
    from transmogrifai_tpu.uid import UID
    UID.reset()
    calls["n"] = 0
    model2 = _train(make_sel(), frame)
    # zero grid fits on restart (the winner refit rides fit_arrays)
    assert calls["n"] == 0
    s1, s2 = model1.selector_summary(), model2.selector_summary()
    assert s1.best_model_name == s2.best_model_name
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert v1 == v2

    # a DIFFERENT grid over the same dir must NOT reuse the stale entries
    UID.reset()
    calls["n"] = 0
    _train(make_sel(grid=(1.0, 10.0)), frame)
    assert calls["n"] >= 2  # fingerprint mismatch -> full sweep reruns


def test_newton_survives_collinear_onehot_reg0():
    """reg_param=0 on a perfectly collinear one-hot block (pivot + OTHER +
    null indicator sum to 1): the Newton/IRLS fast path must converge with
    finite weights instead of amplifying the singular Hessian to NaN
    (found driving LOCO over a Titanic fit, round 3)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    rng = np.random.default_rng(8)
    n = 400
    cls = rng.integers(0, 3, n)
    onehot = np.eye(3, dtype=np.float32)[cls]
    X = np.concatenate([onehot, 1.0 - onehot,          # collinear blocks
                        rng.normal(size=(n, 2)).astype(np.float32)], axis=1)
    y = ((cls == 0) | (X[:, -1] > 0.5)).astype(np.float64)
    est = OpLogisticRegression()  # defaults: reg_param=0 -> Newton path
    model = est.fit_arrays(jnp.asarray(X), jnp.asarray(y),
                           jnp.ones(n, jnp.float32), est.params)
    W = np.asarray(model.weights)
    assert np.all(np.isfinite(W))
    pred = model.predict_arrays(jnp.asarray(X))
    acc = float((np.asarray(pred.prediction) == y).mean())
    assert acc > 0.85
