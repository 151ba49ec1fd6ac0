"""External-model import parity (reference MLeapModelConverter.scala:93 —
foreign serialized models become local scoring functions).

The sklearn round-trips assert parity against the SOURCE LIBRARY's own
predictions (sklearn ships in this environment). The XGBoost artifact is a
committed schema-accurate JSON fixture (xgboost itself is not installed;
the expected outputs come from an independent reference traversal in this
file implementing xgboost's documented semantics: route left on x < t,
leaf weight in split_conditions, margin base = logit(base_score)).
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.local import import_sklearn, import_xgboost_json
from transmogrifai_tpu.models.trees import TreeEnsembleModel

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "xgb_binary_logistic.json")

rng = np.random.default_rng(11)
X = rng.normal(size=(300, 3)).astype(np.float32)
# include values equal to split thresholds: strict-vs-inclusive routing
X[:7, 0] = 0.5
X[7:12, 1] = -0.75
y_cls = (X[:, 0] + 0.5 * X[:, 1] - X[:, 2] + rng.normal(0, .5, 300) > 0)
y_reg = (2 * X[:, 0] - X[:, 1] + rng.normal(0, .1, 300)).astype(np.float64)


def _score(model, X):
    return model.device_apply(model.device_params(),
                              fr.VectorColumn(jnp.asarray(X)))


# -- xgboost JSON ------------------------------------------------------------

def _xgb_reference_margin(doc: dict, X: np.ndarray) -> np.ndarray:
    """Independent traversal with xgboost's documented semantics."""
    learner = doc["learner"]
    out = np.zeros(len(X))
    for tree in learner["gradient_booster"]["model"]["trees"]:
        left = tree["left_children"]
        right = tree["right_children"]
        cond = np.asarray(tree["split_conditions"], np.float32)
        feat = tree["split_indices"]
        for i, x in enumerate(X):
            node = 0
            while left[node] >= 0:
                node = left[node] if np.float32(x[feat[node]]) < cond[node] \
                    else right[node]
            out[i] += cond[node]
    p = float(learner["learner_model_param"]["base_score"])
    return out + np.log(p / (1 - p))


def test_xgboost_json_binary_parity():
    with open(FIXTURE) as fh:
        doc = json.load(fh)
    model = import_xgboost_json(FIXTURE)
    assert isinstance(model, TreeEnsembleModel)
    assert model.kind == "gbt_classifier" and model.learning_rate == 1.0
    expected_margin = _xgb_reference_margin(doc, X)
    got = _score(model, X)
    np.testing.assert_allclose(np.asarray(got.raw_prediction[:, 1]),
                               expected_margin, rtol=1e-5, atol=1e-6)
    expected_p1 = 1.0 / (1.0 + np.exp(-expected_margin))
    np.testing.assert_allclose(np.asarray(got.probability[:, 1]),
                               expected_p1, rtol=1e-5, atol=1e-6)
    # accepts dicts and JSON strings too
    assert import_xgboost_json(doc).kind == "gbt_classifier"
    assert import_xgboost_json(json.dumps(doc)).kind == "gbt_classifier"


def test_xgboost_json_rejects_unsupported():
    with open(FIXTURE) as fh:
        doc = json.load(fh)
    doc["learner"]["objective"]["name"] = "rank:pairwise"
    with pytest.raises(NotImplementedError):
        import_xgboost_json(doc)
    doc["learner"]["objective"]["name"] = "binary:logistic"
    doc["learner"]["gradient_booster"]["model"]["tree_info"] = [0, 1, 2]
    with pytest.raises(NotImplementedError):
        import_xgboost_json(doc)
    # categorical splits (enable_categorical) cannot map to thresholds
    doc["learner"]["gradient_booster"]["model"]["tree_info"] = [0, 0, 0]
    doc["learner"]["gradient_booster"]["model"]["trees"][0][
        "split_type"] = [1, 0, 0, 0, 0, 0, 0]
    with pytest.raises(NotImplementedError):
        import_xgboost_json(doc)
    # a typo'd path must surface as FileNotFoundError, not a JSON error
    with pytest.raises(FileNotFoundError):
        import_xgboost_json("/no/such/model.json")


def test_sklearn_rejects_silently_wrong_configs():
    from sklearn.ensemble import GradientBoostingClassifier
    from sklearn.linear_model import LogisticRegression
    # exponential loss: sklearn maps margin via expit(2*raw) — not sigmoid
    est = GradientBoostingClassifier(
        loss="exponential", n_estimators=5, max_depth=2).fit(X, y_cls)
    with pytest.raises(NotImplementedError):
        import_sklearn(est)
    # custom init estimator: per-row raw init, no scalar base_score
    est2 = GradientBoostingClassifier(
        init=LogisticRegression(), n_estimators=5, max_depth=2).fit(X, y_cls)
    with pytest.raises(NotImplementedError):
        import_sklearn(est2)


# -- sklearn round-trips -----------------------------------------------------

def test_sklearn_logistic_regression_parity():
    from sklearn.linear_model import LogisticRegression
    est = LogisticRegression(max_iter=200).fit(X, y_cls)
    model = import_sklearn(est)
    got = np.asarray(_score(model, X).probability)
    np.testing.assert_allclose(got, est.predict_proba(X),
                               rtol=1e-5, atol=1e-6)


def test_sklearn_linear_regression_parity():
    from sklearn.linear_model import LinearRegression, Ridge
    for est in (LinearRegression().fit(X, y_reg),
                Ridge(alpha=0.5).fit(X, y_reg)):
        model = import_sklearn(est)
        got = np.asarray(_score(model, X).prediction)
        np.testing.assert_allclose(got, est.predict(X), rtol=1e-4, atol=1e-4)


def test_sklearn_gbt_classifier_parity():
    from sklearn.ensemble import GradientBoostingClassifier
    est = GradientBoostingClassifier(
        n_estimators=25, max_depth=3, learning_rate=0.2, random_state=0
    ).fit(X, y_cls)
    model = import_sklearn(est)
    assert model.kind == "gbt_classifier"
    got = np.asarray(_score(model, X).probability)
    np.testing.assert_allclose(got, est.predict_proba(X),
                               rtol=1e-4, atol=1e-5)


def test_sklearn_gbt_regressor_parity():
    from sklearn.ensemble import GradientBoostingRegressor
    est = GradientBoostingRegressor(
        n_estimators=20, max_depth=3, learning_rate=0.3, random_state=0
    ).fit(X, y_reg)
    model = import_sklearn(est)
    got = np.asarray(_score(model, X).prediction)
    np.testing.assert_allclose(got, est.predict(X), rtol=1e-4, atol=1e-4)


def test_sklearn_random_forest_parity():
    from sklearn.ensemble import RandomForestClassifier, RandomForestRegressor
    est = RandomForestClassifier(
        n_estimators=15, max_depth=5, random_state=0).fit(X, y_cls)
    model = import_sklearn(est)
    assert model.kind == "rf_classifier"
    got = np.asarray(_score(model, X).probability)
    np.testing.assert_allclose(got, est.predict_proba(X),
                               rtol=1e-5, atol=1e-6)
    est_r = RandomForestRegressor(
        n_estimators=10, max_depth=5, random_state=0).fit(X, y_reg)
    got_r = np.asarray(_score(import_sklearn(est_r), X).prediction)
    np.testing.assert_allclose(got_r, est_r.predict(X), rtol=1e-4, atol=1e-4)


def test_sklearn_decision_tree_parity():
    from sklearn.tree import DecisionTreeClassifier, DecisionTreeRegressor
    est = DecisionTreeClassifier(max_depth=4, random_state=0).fit(X, y_cls)
    got = np.asarray(_score(import_sklearn(est), X).probability)
    np.testing.assert_allclose(got, est.predict_proba(X),
                               rtol=1e-5, atol=1e-6)
    est_r = DecisionTreeRegressor(max_depth=4, random_state=0).fit(X, y_reg)
    got_r = np.asarray(_score(import_sklearn(est_r), X).prediction)
    np.testing.assert_allclose(got_r, est_r.predict(X), rtol=1e-4, atol=1e-4)


def test_imported_model_serializes_like_native():
    """Imported models ride the normal fitted_state round-trip."""
    model = import_xgboost_json(FIXTURE)
    state = model.fitted_state()
    clone = TreeEnsembleModel.from_config(model.config())
    clone.set_fitted_state(state)
    a = np.asarray(_score(model, X).probability)
    b = np.asarray(_score(clone, X).probability)
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_depth_guard_and_unknown_estimator():
    from sklearn.ensemble import RandomForestRegressor
    from sklearn.svm import SVC
    deep = RandomForestRegressor(n_estimators=2, random_state=0).fit(
        np.asarray(rng.normal(size=(4000, 3)), np.float32),
        rng.normal(size=4000))
    # unbounded depth on 4k rows exceeds the dense-representation cap
    if max(e.tree_.max_depth for e in deep.estimators_) > 16:
        with pytest.raises(ValueError):
            import_sklearn(deep)
    with pytest.raises(NotImplementedError):
        import_sklearn(SVC().fit(X[:50], y_cls[:50]))


# -- multiclass --------------------------------------------------------------

y_mc = (X[:, 0] + 0.5 * X[:, 1] > 0.4).astype(int) \
    + (X[:, 2] > 0.2).astype(int)  # 3 classes


def test_sklearn_multinomial_logistic_parity():
    from sklearn.linear_model import LogisticRegression
    est = LogisticRegression(max_iter=300).fit(X, y_mc)
    got = np.asarray(_score(import_sklearn(est), X).probability)
    np.testing.assert_allclose(got, est.predict_proba(X),
                               rtol=1e-5, atol=1e-6)


def test_sklearn_multiclass_gbt_parity():
    from sklearn.ensemble import GradientBoostingClassifier
    est = GradientBoostingClassifier(
        n_estimators=12, max_depth=3, learning_rate=0.25, random_state=0
    ).fit(X, y_mc)
    model = import_sklearn(est)
    assert model.n_out == 3
    got = _score(model, X)
    np.testing.assert_allclose(np.asarray(got.probability),
                               est.predict_proba(X), rtol=1e-4, atol=1e-5)
    # raw margins match decision_function exactly (centered log-prior init)
    np.testing.assert_allclose(np.asarray(got.raw_prediction),
                               est.decision_function(X),
                               rtol=1e-4, atol=1e-4)


def test_sklearn_multiclass_rf_parity():
    from sklearn.ensemble import RandomForestClassifier
    est = RandomForestClassifier(
        n_estimators=12, max_depth=5, random_state=1).fit(X, y_mc)
    model = import_sklearn(est)
    assert model.n_out == 3 and model.kind == "rf_classifier"
    got = np.asarray(_score(model, X).probability)
    np.testing.assert_allclose(got, est.predict_proba(X),
                               rtol=1e-5, atol=1e-6)


def test_xgboost_multiclass_softprob_parity():
    """A hand-built multi:softprob booster (2 rounds x 3 classes, grouped
    tree_info) vs an independent traversal + softmax."""
    with open(FIXTURE) as fh:
        base_doc = json.load(fh)

    def stump(feat, thr, left_w, right_w):
        return {"left_children": [1, -1, -1], "right_children": [2, -1, -1],
                "split_indices": [feat, 0, 0],
                "split_conditions": [thr, left_w, right_w],
                "default_left": [1, 0, 0], "split_type": [0, 0, 0],
                "categories": [], "categories_nodes": [],
                "categories_segments": [], "categories_sizes": [],
                "base_weights": [0.0, 0.0, 0.0],
                "parents": [2147483647, 0, 0],
                "loss_changes": [1.0, 0.0, 0.0],
                "sum_hessian": [10.0, 5.0, 5.0], "id": 0,
                "tree_param": {"num_deleted": "0", "num_feature": "3",
                               "num_nodes": "3", "size_leaf_vector": "1"}}

    trees = [stump(0, 0.1, 0.4, -0.2), stump(1, -0.3, -0.1, 0.3),
             stump(2, 0.0, 0.2, -0.4),
             stump(1, 0.5, 0.15, -0.15), stump(2, -0.2, -0.3, 0.1),
             stump(0, -0.4, 0.05, 0.25)]
    doc = base_doc
    doc["learner"]["gradient_booster"]["model"]["trees"] = trees
    doc["learner"]["gradient_booster"]["model"]["tree_info"] = \
        [0, 1, 2, 0, 1, 2]
    doc["learner"]["gradient_booster"]["model"]["gbtree_model_param"][
        "num_trees"] = "6"
    doc["learner"]["learner_model_param"]["num_class"] = "3"
    doc["learner"]["objective"] = {"name": "multi:softprob"}
    model = import_xgboost_json(doc)
    assert model.n_out == 3

    margins = np.full((len(X), 3), 0.3, np.float64)  # base_score 3E-1
    for t, cls in zip(trees, [0, 1, 2, 0, 1, 2]):
        f, thr = t["split_indices"][0], np.float32(t["split_conditions"][0])
        lw, rw = t["split_conditions"][1], t["split_conditions"][2]
        margins[:, cls] += np.where(
            X[:, f].astype(np.float32) < thr, lw, rw)
    exp = np.exp(margins - margins.max(axis=1, keepdims=True))
    expected = exp / exp.sum(axis=1, keepdims=True)
    got = np.asarray(_score(model, X).probability)
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["predict_arrays", "fused", "bf16", "int8"])
@pytest.mark.parametrize("source", ["sklearn_per_class", "xgboost_scalar"])
def test_imported_base_score_is_an_argument(source, path):
    """An imported booster's base score (a per-class float32 vector from
    scikit-learn's multiclass prior, a scalar log-odds from XGBoost) rides
    in ``device_params()``: the score is ``base + learning_rate * sum`` in
    float32 to the bit, and on the rungs below f32 the base is added
    unrounded to the rung's own sum (what a zero base gives)."""
    from tree_reference import (
        assert_is_margin, assert_rung_adds_base_unrounded, fused_predict,
        margin_of,
    )
    if source == "sklearn_per_class":
        from sklearn.ensemble import GradientBoostingClassifier
        model = import_sklearn(GradientBoostingClassifier(
            n_estimators=6, max_depth=3, learning_rate=0.25, random_state=0
        ).fit(X, y_mc))
        assert np.shape(model.base_score) == (3,)
    else:
        model = import_xgboost_json(FIXTURE)
        assert np.ndim(model.base_score) == 0 and model.base_score != 0.0
    base = model.device_params()[2]
    assert base.dtype == np.float32 and base.shape == np.shape(
        model.base_score)
    if path in ("predict_arrays", "fused"):
        pred = (model.predict_arrays(jnp.asarray(X))
                if path == "predict_arrays" else fused_predict(model, X))
        assert_is_margin(margin_of(pred, model), model, X)
    else:
        assert_rung_adds_base_unrounded(model, X, path)


def test_imported_model_serves_inside_workflow():
    """The MLeap-analog end game: an imported foreign model wired as the
    prediction stage of a normal workflow — vectorization from raw
    features, batch scoring, row scoring closure, save/load."""
    from sklearn.ensemble import GradientBoostingClassifier

    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.workflow import Workflow, load_model

    from transmogrifai_tpu.types import feature_types as ft

    n = 200
    frame = fr.HostFrame.from_dict({
        "a": (ft.Real, [float(v) for v in X[:n, 0]]),
        "b": (ft.Real, [float(v) for v in X[:n, 1]]),
        "label": (ft.RealNN, [float(v) for v in y_cls[:n]]),
    })
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    vec = transmogrify(list(feats.values()))

    # vectorize once to get the exact matrix the stage will see, train
    # the foreign model on it, then import
    probe = (Workflow().set_input_frame(frame)
             .set_result_features(vec).train())
    Xv = np.asarray(probe.score(frame, keep_raw_features=False)
                    .columns[vec.name].values, np.float32)
    est = GradientBoostingClassifier(
        n_estimators=10, max_depth=2, random_state=0).fit(Xv, y_cls[:n])
    imported = import_sklearn(est)

    pred = label.transform_with(imported, vec)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred).train())
    scored = model.score(frame)
    p1 = np.asarray([d["probability_1"]
                     for d in scored.columns[pred.name].values])
    np.testing.assert_allclose(p1, est.predict_proba(Xv)[:, 1],
                               rtol=1e-4, atol=1e-5)
    # row path + persistence
    fn = model.score_function()
    row_out = fn({"a": float(X[0, 0]), "b": float(X[0, 1])})
    row_pred = next(v for v in row_out.values() if "probability_1" in v)
    assert abs(row_pred["probability_1"] - p1[0]) < 1e-4
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        model.save(d)
        again = load_model(d).score(frame)
        p2 = np.asarray([v["probability_1"]
                         for v in again.columns[pred.name].values])
        np.testing.assert_allclose(p1, p2, rtol=1e-6)


def test_multi_output_forest_rejected():
    from sklearn.ensemble import RandomForestClassifier
    Y2 = np.stack([y_mc, y_cls.astype(int)], axis=1)  # 2D target
    est = RandomForestClassifier(n_estimators=3, max_depth=3,
                                 random_state=0).fit(X, Y2)
    with pytest.raises(NotImplementedError):
        import_sklearn(est)


def test_label_slot_exemption_is_narrow():
    """AllowLabelAsInput on PredictionModel covers only slot 0: a
    response-DERIVED vector in the features slot is still leakage."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.vectorizers import RealVectorizer

    label = FeatureBuilder.RealNN("label").as_response()
    leaky_vec = label.transform_with(RealVectorizer())
    assert leaky_vec.is_response
    model = import_xgboost_json(FIXTURE)
    with pytest.raises(ValueError, match="leakage"):
        label.transform_with(model, leaky_vec)


def test_multi_output_regressor_forest_rejected():
    from sklearn.ensemble import RandomForestRegressor
    Y2 = np.stack([y_reg, -y_reg], axis=1)
    est = RandomForestRegressor(n_estimators=3, max_depth=3,
                                random_state=0).fit(X, Y2)
    with pytest.raises(NotImplementedError):
        import_sklearn(est)
