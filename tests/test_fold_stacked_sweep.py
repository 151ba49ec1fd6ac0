"""Fold-stacked ModelSelector sweep: parity with the per-fold loop,
one-host-sync observability, fallback rules (no fold axis / memory guard),
and checkpoint-resume under the new per-family keys."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models.base import Predictor, supports_fold_stacking
from transmogrifai_tpu.models.extras import (
    OpGeneralizedLinearRegression, OpNaiveBayes,
)
from transmogrifai_tpu.models.linear import (
    OpLinearRegression, OpLinearSVC, OpLogisticRegression,
)
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector, DataSplitter, RegressionModelSelector,
)
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.uid import UID
from transmogrifai_tpu.utils.profiling import sweep_counters
from transmogrifai_tpu.workflow import Workflow


def _frame(n=300, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n).astype(float)
    x = rng.normal(size=n) + 0.8 * y
    return fr.HostFrame.from_dict({
        "x": (ft.Real, x.tolist()),
        "x2": (ft.Real, rng.normal(size=n).tolist()),
        "label": (ft.RealNN, y.tolist()),
    })


def _train(selector, frame):
    UID.reset()
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    vec = transmogrify(list(feats.values()), min_support=1)
    pred = label.transform_with(selector, vec)
    return (Workflow().set_input_frame(frame)
            .set_result_features(pred).train())


def _binary_selector(**kw):
    return BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=25),
             [{"reg_param": r, "elastic_net_param": e}
              for r in (0.0, 0.1) for e in (0.0, 0.5)]),  # Newton + Adam mix
            (OpLinearSVC(max_iter=25), [{"reg_param": r}
                                        for r in (0.01, 0.1)]),
            (OpNaiveBayes(), [{"smoothing": s} for s in (0.5, 1.0)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1), **kw)


def _summaries_equal(s1, s2, tol=1e-6):
    assert s1.best_model_name == s2.best_model_name
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert set(v1) == set(v2)
    for k in v1:
        for m in v1[k]:
            assert abs(v1[k][m] - v2[k][m]) <= tol, (k, m)


def test_stacked_parity_binary(fold_loop):
    """The fold-stacked sweep selects the identical winner with identical
    per-candidate mean metrics and summary JSON as the per-fold loop."""
    frame = _frame()
    sweep_counters.reset()
    s1 = _train(_binary_selector(), frame).selector_summary()
    c1 = sweep_counters.to_json()
    sweep_counters.reset()
    with fold_loop():
        s2 = _train(_binary_selector(), frame).selector_summary()
    c2 = sweep_counters.to_json()
    _summaries_equal(s1, s2)
    # identical validationResults in the summary JSON too
    j1 = {r["modelName"]: r for r in s1.to_json()["validationResults"]}
    j2 = {r["modelName"]: r for r in s2.to_json()["validationResults"]}
    assert set(j1) == set(j2)
    for name in j1:
        assert j1[name]["modelParams"] == j2[name]["modelParams"]
    assert all(v["mode"] == "fold_stacked" for v in c1.values()), c1
    assert all(v["mode"] == "fold_loop" for v in c2.values()), c2


def test_stacked_parity_regression(fold_loop):
    frame = _frame(seed=3)
    models = lambda: [  # noqa: E731
        (OpLinearRegression(max_iter=25),
         [{"reg_param": r} for r in (0.01, 0.1)]),
        (OpGeneralizedLinearRegression(max_iter=25),
         [{"reg_param": r} for r in (0.0, 0.1)]),
    ]
    s1 = _train(RegressionModelSelector.with_cross_validation(
        n_folds=2, seed=1, models_and_parameters=models(),
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1)),
        frame).selector_summary()
    with fold_loop():
        s2 = _train(RegressionModelSelector.with_cross_validation(
            n_folds=2, seed=1, models_and_parameters=models(),
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1)),
            frame).selector_summary()
    _summaries_equal(s1, s2)


def test_stacked_one_host_sync_per_family(fold_loop):
    """The acceptance counter: vmappable families cost exactly ONE host
    sync (and one dispatch) on the fast path, k of each on the loop."""
    frame = _frame(seed=5)
    sweep_counters.reset()
    _train(_binary_selector(), frame)
    for name, c in sweep_counters.to_json().items():
        assert c["mode"] == "fold_stacked", (name, c)
        assert c["hostSyncs"] == 1, (name, c)
        assert c["deviceDispatches"] == 1, (name, c)
    sweep_counters.reset()
    with fold_loop():
        _train(_binary_selector(), frame)
    for name, c in sweep_counters.to_json().items():
        assert c["mode"] == "fold_loop", (name, c)
        assert c["hostSyncs"] == 3, (name, c)   # one per fold
        assert c["deviceDispatches"] == 3, (name, c)


class CountingLR(OpLogisticRegression):
    """Per-fold-trainer override: the stacked path must NOT bypass it."""
    counts = {"n": 0}

    def grid_fit_arrays(self, X, y, w, grid):
        type(self).counts["n"] += 1
        return super().grid_fit_arrays(X, y, w, grid)


def test_fold_stacking_capability_rules():
    assert supports_fold_stacking(OpLogisticRegression())
    assert supports_fold_stacking(OpLinearSVC())
    assert supports_fold_stacking(OpLinearRegression())
    assert supports_fold_stacking(OpNaiveBayes())
    # a subclass overriding the per-fold trainer below the opt-in loses
    # the fold axis — its custom semantics must keep running
    assert not supports_fold_stacking(CountingLR())
    from transmogrifai_tpu.models.trees import OpGBTClassifier
    assert not supports_fold_stacking(OpGBTClassifier())  # never opted in


def test_fallback_family_without_fold_axis():
    """A family whose subclass overrides grid_fit_arrays routes through
    the per-fold loop (override honored), while vmappable co-candidates
    still take the stacked path."""
    frame = _frame(seed=6)
    CountingLR.counts["n"] = 0
    sweep_counters.reset()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (CountingLR(max_iter=25), [{"reg_param": 0.01}]),
            (OpLinearSVC(max_iter=25), [{"reg_param": 0.01}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    _train(sel, frame)
    assert CountingLR.counts["n"] == 2  # one per fold: override ran
    c = sweep_counters.to_json()
    assert c["CountingLR_0"]["mode"] == "fold_loop"
    assert c["OpLinearSVC_1"]["mode"] == "fold_stacked"


def test_memory_guard_falls_back(monkeypatch):
    """An impossible HBM budget trips the stacked-batch guard: families
    fall back to the per-fold loop and the sweep still completes with
    identical results."""
    frame = _frame(seed=7)
    monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET", "1")
    sweep_counters.reset()
    s1 = _train(_binary_selector(), frame).selector_summary()
    assert all(v["mode"] == "fold_loop"
               for v in sweep_counters.to_json().values())
    monkeypatch.delenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET")
    s2 = _train(_binary_selector(), frame).selector_summary()
    _summaries_equal(s1, s2)


@pytest.mark.parametrize("fit_intercept,standardize,chunk", [
    (True, True, 512), (True, True, 5000), (False, True, 1024),
    (True, False, 1536)])
def test_newton_in_place_equals_the_copying_trainer(fit_intercept,
                                                    standardize, chunk):
    """``_newton_in_place`` (folds as row weightings of ONE matrix, walked
    in row chunks with a shorter last one, the Hessian in bfloat16) ends
    where ``_train_logistic_newton`` ends on each fold's gathered rows."""
    import jax
    from transmogrifai_tpu.models import linear as L
    rng = np.random.default_rng(0)
    n, d, k = 5000, 37, 3
    X = (rng.normal(size=(n, d)) * rng.uniform(0.5, 30, size=d)
         + 10 * rng.normal(size=d)).astype(np.float32)
    if not standardize:
        X = ((X - X.mean(0)) / X.std(0)).astype(np.float32)
    beta = rng.normal(size=d) / np.sqrt(d)
    y = (((X - X.mean(0)) / X.std(0)) @ beta + rng.normal(size=n)
         > 0).astype(np.float32)
    fold = rng.integers(0, k, size=n)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    wf = np.stack([np.where(fold != f, w, 0.0) for f in range(k)])
    rp = jnp.asarray([0.001, 0.01, 0.1, 0.2], jnp.float32)
    kw = dict(fit_intercept=fit_intercept, standardize=standardize)
    Ws, bs = L._newton_in_place(jnp.asarray(X), jnp.asarray(y),
                                jnp.asarray(wf), rp, chunk=chunk, **kw)
    assert Ws.shape == (k, 4, d, 2) and bs.shape == (k, 4, 2)
    for f in range(k):
        rows = np.nonzero(fold != f)[0]
        W0, b0, _ = jax.vmap(lambda r: L._train_logistic_newton(
            jnp.asarray(X[rows]), jnp.asarray(y[rows]),
            jnp.asarray(w[rows]), r, **kw))(rp)
        scale = float(jnp.max(jnp.abs(W0)))
        assert float(jnp.max(jnp.abs(Ws[f] - W0))) < 1e-4 * scale
        assert float(jnp.max(jnp.abs(bs[f] - b0))) < 1e-4


def test_newton_points_stay_stacked_where_copies_do_not_fit(monkeypatch):
    """A matrix whose gathered folds and their copies do not fit the
    budget keeps its Newton points on the stacked path, trained in place
    (the sweep and the winner's cold refit), with the metrics of the
    copying trainer; only where not even a row chunk fits does the family
    take the loop."""
    from transmogrifai_tpu.models import linear as L
    frame = _frame(seed=11)
    sweep_counters.reset()
    s1 = _train(_binary_selector(), frame).selector_summary()
    in_place = []
    real = L._newton_in_place
    monkeypatch.setattr(L, "_newton_copies_fit", lambda k, n, d: False)
    monkeypatch.setattr(L, "_newton_in_place", lambda *a, **kw: (
        in_place.append(a[2].shape[0]), real(*a, **kw))[1])
    sweep_counters.reset()
    s2 = _train(_binary_selector(), frame).selector_summary()
    c2 = sweep_counters.to_json()
    assert c2["OpLogisticRegression_0"]["mode"] == "fold_stacked", c2
    assert sweep_counters.run_to_json().get("sweepLoopFallbacks", 0) == 0
    assert 3 in in_place                    # the sweep's folds as weightings
    _summaries_equal(s1, s2, tol=1e-5)


class CrashOnce(OpLinearSVC):
    """Simulates a mid-sweep crash (NOT an isolated candidate failure):
    KeyboardInterrupt escapes the per-family isolation by design."""
    crash = {"on": True}

    def grid_fit_arrays(self, X, y, w, grid):
        if type(self).crash["on"]:
            raise KeyboardInterrupt("simulated mid-sweep crash")
        return super().grid_fit_arrays(X, y, w, grid)


def test_checkpoint_resume_mid_sweep_per_family_keys(tmp_path):
    """A crash after the first (stacked) family completes leaves its
    per-family checkpoint key; the re-run replays it without refitting
    and sweeps only the remainder."""
    frame = _frame(seed=9)
    ckpt = str(tmp_path / "sweep")

    def make_sel():
        return BinaryClassificationModelSelector.with_cross_validation(
            n_folds=3, seed=1,
            models_and_parameters=[
                (OpLogisticRegression(max_iter=25),
                 [{"reg_param": r} for r in (0.01, 0.1)]),
                (CrashOnce(max_iter=25), [{"reg_param": 0.01}]),
            ],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
            checkpoint_dir=ckpt)

    CrashOnce.crash["on"] = True
    with pytest.raises(KeyboardInterrupt):
        _train(make_sel(), frame)
    saved = json.load(open(os.path.join(ckpt, "sweep.json")))
    keys = sorted(saved["entries"])
    # the completed LR family checkpoints ONE per-family stacked key
    # carrying k x |grid| per-fold values (fold-major)
    assert len(keys) == 1 and keys[0].startswith("0:stacked:3x"), keys
    assert len(saved["entries"][keys[0]]) == 3 * 2

    # resume: LR must not refit (instance-level wrapper counts calls
    # without disturbing the class-based capability check)
    CrashOnce.crash["on"] = False
    sel = make_sel()
    lr = sel.models_and_grids[0][0]
    calls = {"n": 0}
    orig = lr.sweep_folds

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)
    lr.sweep_folds = counting
    model = _train(sel, frame)
    assert calls["n"] == 0  # replayed from the per-family checkpoint
    s = model.selector_summary()
    names = {r.model_name for r in s.validation_results}
    assert any(n.startswith("OpLogisticRegression_0") for n in names)
    assert any(n.startswith("CrashOnce_1") for n in names)


def test_stacked_splits_plan():
    from transmogrifai_tpu.selector.validator import (
        OpCrossValidation, OpTrainValidationSplit,
    )
    tr, va = OpCrossValidation(n_folds=3, seed=0).stacked_splits(100)
    assert tr.shape == (3, 100 - 100 // 3) and va.shape == (3, 100 // 3)
    for f in range(3):
        assert not np.intersect1d(tr[f], va[f]).size
    tr1, va1 = OpTrainValidationSplit(train_ratio=0.8).stacked_splits(50)
    assert tr1.shape[0] == 1 and va1.shape[0] == 1

    class Unequal(OpCrossValidation):
        def splits(self, n, y=None):
            out = super().splits(n, y)
            return [(out[0][0][:-1], out[0][1])] + out[1:]

    with pytest.raises(ValueError, match="unequal fold shapes"):
        Unequal(n_folds=2).stacked_splits(40)


def test_fold_metric_batches_match_per_fold():
    """Evaluator fold batches == per-fold metric batches, every metric."""
    from transmogrifai_tpu.evaluators.binary import (
        OpBinaryClassificationEvaluator,
    )
    from transmogrifai_tpu.evaluators.regression import OpRegressionEvaluator
    rng = np.random.default_rng(0)
    k, G, n = 3, 4, 200
    y = (rng.uniform(size=(k, n)) < 0.5).astype(np.float32)
    s = rng.normal(size=(k, G, n)).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    for metric in ("auPR", "auROC", "F1", "Error"):
        got = ev.metric_batch_scores_folds(y, s, metric)
        assert got.shape == (k, G)
        for f in range(k):
            want = ev.metric_batch_scores(y[f], s[f], metric)
            np.testing.assert_allclose(got[f], want, atol=1e-6)
    rev = OpRegressionEvaluator()
    yr = rng.normal(size=(k, n)).astype(np.float32)
    for metric in ("RMSE", "MSE", "MAE", "R2"):
        got = rev.metric_batch_scores_folds(yr, s, metric)
        for f in range(k):
            want = rev.metric_batch_scores(yr[f], s[f], metric)
            np.testing.assert_allclose(got[f], want, atol=1e-5)


def test_stacked_sweep_under_mesh():
    """The stacked (fold x grid) batch shards 2-D over an active mesh
    (rows on "data"; the fold axis takes "model" when it divides it) and
    reproduces the unsharded metrics."""
    from transmogrifai_tpu.parallel.mesh import make_mesh, use_mesh
    frame = _frame(seed=11)
    s1 = _train(_binary_selector(), frame).selector_summary()
    ctx = make_mesh(n_data=4, n_model=2)
    with use_mesh(ctx):
        sweep_counters.reset()
        s2 = _train(_binary_selector(), frame).selector_summary()
        assert all(v["mode"] == "fold_stacked"
                   for v in sweep_counters.to_json().values())
    _summaries_equal(s1, s2, tol=5e-4)  # padded-shard reductions reorder


def test_glm_mlp_fold_models_stay_lazy():
    """Fold-stacked extras models hold device views; host conversion
    happens only at serialization time."""
    rng = np.random.default_rng(0)
    k, n, d = 2, 60, 3
    X = jnp.asarray(rng.normal(size=(k, n, d)).astype(np.float32))
    y = jnp.asarray((rng.uniform(size=(k, n)) < 0.5).astype(np.float32))
    w = jnp.ones((k, n), jnp.float32)
    glm = OpGeneralizedLinearRegression(max_iter=10)
    models = glm.grid_fit_arrays_folds(X, y, w, [{"reg_param": 0.0},
                                                 {"reg_param": 0.1}])
    assert len(models) == k and len(models[0]) == 2
    scores = glm.grid_predict_scores_folds(models, X)
    assert scores.shape == (k, 2, n)
    state = models[0][0].fitted_state()
    assert isinstance(state["weights"], np.ndarray)

    from transmogrifai_tpu.models.extras import (
        OpMultilayerPerceptronClassifier,
    )
    mlp = OpMultilayerPerceptronClassifier(max_iter=5, layers=(4,))
    mmodels = mlp.grid_fit_arrays_folds(X, y, w, [{"step_size": 0.01},
                                                  {"step_size": 0.02}])
    mscores = mlp.grid_predict_scores_folds(mmodels, X)
    assert mscores.shape == (k, 2, n)
    assert np.all(np.isfinite(np.asarray(mscores)))
