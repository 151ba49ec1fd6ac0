"""One-sync sweep (round 9): async family overlap behind a single settle
barrier, run-level sync counters, the stacked warm-started winner refit,
tree bin-code reuse in the refit, and the shape-keyed refit checkpoint."""

import os

import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models.extras import (
    OpGeneralizedLinearRegression, OpNaiveBayes,
)
from transmogrifai_tpu.models.linear import (
    OpLinearRegression, OpLinearSVC, OpLogisticRegression,
)
from transmogrifai_tpu.models.trees import OpGBTClassifier, OpGBTRegressor
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


def _reg_frame(n=400, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = 2.0 * x - 1.3 * x2 + 0.05 * rng.normal(size=n)
    return fr.HostFrame.from_dict({
        "x": (ft.Real, x.tolist()),
        "x2": (ft.Real, x2.tolist()),
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


def _mixed_selector(**kw):
    """Linear + NB + tree families: every stacked path in one sweep."""
    return BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=25),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpNaiveBayes(), [{"smoothing": s} for s in (0.5, 1.0)]),
            (OpGBTClassifier(num_rounds=4, max_depth=2),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1), **kw)


def _summaries_equal(s1, s2, tol=0.0):
    assert s1.best_model_name == s2.best_model_name
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert set(v1) == set(v2)
    for k in v1:
        for m in v1[k]:
            assert abs(v1[k][m] - v2[k][m]) <= tol, (k, m)


# ---------------------------------------------------------------------------
# one-sync dispatch/settle
# ---------------------------------------------------------------------------

def test_one_sync_whole_sweep_counters():
    """The tentpole assertion: an entire train() with NOTHING set, on
    whatever backend the suite runs — linear, NB and tree families
    together — settles behind ONE blocking host sync, every
    family dispatched asynchronously; per-family counters keep their
    metric-pull meaning (one per family / per tree group)."""
    frame = _frame(seed=5)
    sweep_counters.reset()
    _train(_mixed_selector(), frame)
    run = sweep_counters.run_to_json()
    assert run["sweepHostSyncs"] == 1, run
    assert run["asyncFamilies"] == 3, run
    per = sweep_counters.to_json()
    assert per["OpLogisticRegression_0"]["mode"] == "fold_stacked"
    assert per["OpLogisticRegression_0"]["hostSyncs"] == 1
    assert per["OpNaiveBayes_1"]["hostSyncs"] == 1
    assert per["OpGBTClassifier_2"]["mode"] == "tree_stacked"
    assert per["OpGBTClassifier_2"]["hostSyncs"] == 1
    assert per["OpGBTClassifier_2"]["stackedGroups"] == 1


def test_sweep_device_spans_one_per_chunk_in_dispatch_order():
    """Every dispatched chunk of the async sweep gets ONE ``sweep.device``
    span, stamped as the settle walks its one barrier in dispatch order:
    the spans do not overlap, follow the dispatch order, lie inside the
    sweep (no program runs before its dispatch), the last ends inside the
    settle's window, and the walk costs no extra host sync. The winner's
    refit gets its own ``refit.device`` span."""
    from transmogrifai_tpu.utils.profiling import profiler
    from transmogrifai_tpu.utils.tracing import recorder
    frame = _frame(seed=5)
    profiler.reset()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=25),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpNaiveBayes(), [{"smoothing": s} for s in (0.5, 1.0)]),
            (OpGBTClassifier(num_rounds=3),
             [{"max_depth": 2}, {"max_depth": 3, "learning_rate": 0.1},
              {"max_depth": 3, "learning_rate": 0.3}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    _train(sel, frame)
    assert sweep_counters.run_to_json()["sweepHostSyncs"] == 1
    spans = recorder.spans
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    dev = by_name["sweep.device"]
    # one per dispatched chunk: 2 fold-stacked families + 2 tree depth groups
    assert [(d.attrs["family"], d.attrs["unitKind"], d.attrs.get("depth"),
             d.attrs["lanes"]) for d in dev] == [
        ("OpLogisticRegression_0", "stacked", None, 2),
        ("OpNaiveBayes_1", "stacked", None, 2),
        ("OpGBTClassifier_2", "tree", 2, 1),
        ("OpGBTClassifier_2", "tree", 3, 2)]
    assert all(d.attrs["chunk"] == 0 and "exact" in d.attrs for d in dev)
    assert [d.attrs.get("group") for d in dev] == [None, None, 0, 1]
    for a, b in zip(dev, dev[1:]):
        assert a.t0 <= a.t1 <= b.t0 <= b.t1     # ordered, non-overlapping
    sweep = by_name["selector.sweep"][0]
    settle = by_name["sweep.settle"][0]
    assert sweep.t0 <= dev[0].t0 and dev[-1].t1 <= sweep.t1
    assert settle.t0 <= dev[-1].t1 <= settle.t1
    # a program cannot run before the dispatch that launched it ended
    launches = sorted(by_name["sweep.family"] + by_name["sweep.tree_group"],
                      key=lambda sp: sp.t0)
    assert len(launches) == len(dev)
    for launch, d in zip(launches, dev):
        assert d.t0 >= launch.t1 - 1e-3
    refit = by_name["refit.device"]
    assert len(refit) == 1 and refit[0].attrs["family"].startswith("Op")
    assert by_name["selector.refit"][0].t0 <= refit[0].t0 <= refit[0].t1


def test_async_parity_with_per_family_settle_and_loop(fold_loop):
    """Async overlap changes WHEN metrics materialize, never their
    values: summaries are identical (exactly) across the one-settle
    stacked sweep and the per-fold loop."""
    frame = _frame(seed=7)
    s_async = _train(_mixed_selector(), frame).selector_summary()
    with fold_loop():
        s_loop = _train(_mixed_selector(), frame).selector_summary()
    _summaries_equal(s_async, s_loop, tol=0.0)


def test_custom_evaluator_without_device_metric_settles_per_family():
    """An evaluator exposing only the host fold-metric has no futures to
    defer: every family takes the per-fold loop."""
    from transmogrifai_tpu.evaluators.binary import (
        OpBinaryClassificationEvaluator,
    )

    class HostOnlyEvaluator(OpBinaryClassificationEvaluator):
        metric_batch_scores_folds_device = None  # pre-round-9 evaluator

        def metric_batch_scores_folds(self, y, scores, metric=None,
                                      w=None):
            return np.asarray(
                OpBinaryClassificationEvaluator
                .metric_batch_scores_folds_device(self, y, scores, metric,
                                                  w))

    frame = _frame(seed=9)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=25), [{"reg_param": 0.01}]),
            (OpLinearSVC(max_iter=25), [{"reg_param": 0.01}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    sel.evaluators = [HostOnlyEvaluator()]
    sel.validation_metric = "auPR"
    sweep_counters.reset()
    _train(sel, frame)
    run = sweep_counters.run_to_json()
    assert run["asyncFamilies"] == 0
    assert run["sweepHostSyncs"] == 4  # one per (fold, family)
    per = sweep_counters.to_json()
    assert all(v["mode"] == "fold_loop" for v in per.values())


def test_settle_isolates_poisoned_family():
    """A family whose async future materializes non-finite garbage is
    excluded by the existing non-finite rule; a family whose DISPATCH
    raises is isolated without touching already-dispatched peers."""

    class BoomSVC(OpLinearSVC):
        def sweep_folds(self, batch, grid, _n_classes=None):
            raise RuntimeError("boom at dispatch")

    # NOTE: the override sits AT the selector's stacked unit itself, so
    # capability routing still sends BoomSVC down the stacked path.
    frame = _frame(seed=11)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=25), [{"reg_param": 0.01}]),
            (BoomSVC(max_iter=25), [{"reg_param": 0.01}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    model = _train(sel, frame)
    s = model.selector_summary()
    assert any("BoomSVC" in f["modelName"] for f in s.failures), s.failures
    assert s.best_model_name.startswith("OpLogisticRegression_0")


# ---------------------------------------------------------------------------
# warm-started winner refit
# ---------------------------------------------------------------------------

def test_warm_refit_regression_metric_parity(monkeypatch):
    """The warm-started (fold-averaged init, donated buffers) winner
    refit reproduces the cold serial refit's train/holdout metrics within
    the artifact-gated 1e-5 on a converged convex sweep, and counts in
    refitWarmStarts. Least squares is solved from its Grams up to
    ``_GRAM_MAX_D`` columns, and a solved point needs no warm start: the
    descent and its warm refit are what a wider matrix takes, here with
    the cap at 0."""
    monkeypatch.setattr(OpLinearRegression, "_GRAM_MAX_D", 0)
    frame = _reg_frame(seed=3)

    def make_sel():
        return RegressionModelSelector.with_cross_validation(
            n_folds=3, seed=1,
            models_and_parameters=[
                (OpLinearRegression(max_iter=400),
                 [{"reg_param": r} for r in (0.01, 0.1)]),
            ],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))

    sweep_counters.reset()
    s_warm = _train(make_sel(), frame).selector_summary()
    assert sweep_counters.run_to_json()["refitWarmStarts"] == 1

    # the cold refit, for the reason the selector observes: the family
    # reports it cannot use a warm handle
    monkeypatch.setattr(OpLinearRegression, "supports_warm_refit",
                        lambda self: False)
    sweep_counters.reset()
    s_cold = _train(make_sel(), frame).selector_summary()
    assert sweep_counters.run_to_json()["refitWarmStarts"] == 0

    _summaries_equal(s_warm, s_cold, tol=0.0)  # sweep untouched by warm
    for block in ("train_evaluation", "holdout_evaluation"):
        e_w, e_c = getattr(s_warm, block), getattr(s_cold, block)
        assert set(e_w) == set(e_c)
        for ev_name in e_w:
            for m, v in e_w[ev_name].items():
                v2 = e_c[ev_name][m]
                if isinstance(v, float) and isinstance(v2, float):
                    assert abs(v - v2) <= 1e-5, (block, m, v, v2)


def test_glm_and_mlp_warm_refit_unit():
    """GLM and MLP refit_winner consume the retained [k][G] model nest:
    warm_used is True and the refit model is finite/usable."""
    rng = np.random.default_rng(0)
    k, n, d = 2, 120, 3
    Xf = jnp.asarray(rng.normal(size=(k, n, d)).astype(np.float32))
    yf = jnp.asarray((rng.uniform(size=(k, n)) < 0.5).astype(np.float32))
    wf = jnp.ones((k, n), jnp.float32)
    X = Xf[0]
    y, w = yf[0], wf[0]

    glm = OpGeneralizedLinearRegression(max_iter=20)
    grid = [{"reg_param": 0.0}, {"reg_param": 0.1}]
    scores, warm = glm.grid_scores_folds_retained(Xf, yf, wf, grid, Xf)
    assert scores is not None and warm is not None
    model, used = glm.refit_winner(X, y, w, {**glm.params, **grid[1]},
                                   warm=warm, lane=1)
    assert used and np.all(np.isfinite(np.asarray(model.weights)))

    from transmogrifai_tpu.models.extras import (
        OpMultilayerPerceptronClassifier,
    )
    mlp = OpMultilayerPerceptronClassifier(max_iter=5, layers=(4,))
    mgrid = [{"step_size": 0.01}, {"step_size": 0.02}]
    mscores, mwarm = mlp.grid_scores_folds_retained(Xf, yf, wf, mgrid, Xf)
    assert mscores is not None and mwarm is not None
    mmodel, mused = mlp.refit_winner(X, y, w, {**mlp.params, **mgrid[0]},
                                     warm=mwarm, lane=0)
    assert mused
    assert all(np.all(np.isfinite(W)) for W, _ in mmodel.params)
    # shape-mismatched warm falls back to the cold PRNG init
    bad = OpMultilayerPerceptronClassifier(max_iter=5, layers=(7,))
    _, bused = bad.refit_winner(X, y, w, {**bad.params, **mgrid[0]},
                                warm=mwarm, lane=0)
    assert not bused


def test_newton_winner_refits_cold_bitwise():
    """A Newton-eligible LR winner (binary pure-L2) ignores the warm
    handle: the refit is the serial path's exact cold Newton fit."""
    rng = np.random.default_rng(1)
    n, d = 200, 3
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    lr = OpLogisticRegression(max_iter=50)
    fake_warm = (jnp.zeros((2, 1, d, 2)), jnp.zeros((2, 1, 2)))
    warm_model, used = lr.refit_winner(X, y, w,
                                       {**lr.params, "reg_param": 0.01},
                                       warm=fake_warm, lane=0)
    assert not used
    cold = lr.fit_arrays(X, y, w, {**lr.params, "reg_param": 0.01})
    np.testing.assert_array_equal(np.asarray(warm_model.weights),
                                  np.asarray(cold.weights))


# ---------------------------------------------------------------------------
# tree refit bin-code reuse
# ---------------------------------------------------------------------------

def test_tree_refit_bin_reuse_is_bitwise():
    """refit_winner with the sweep's dataset-level bin plan produces the
    bit-identical model to the cold fit_arrays that re-bins — the reuse
    deletes the duplicate quantization pass, not the result."""
    rng = np.random.default_rng(2)
    n, d = 500, 4
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    est = OpGBTClassifier(num_rounds=4, max_depth=3)
    params = {**est.params, "learning_rate": 0.2}
    plan = est.fold_sweep_plan(X, [params])
    cold = est.fit_arrays(X, y, w, params)
    reused, used = est.refit_winner(X, y, w, params,
                                    hints={"bin_plans": plan})
    assert used
    s_cold, s_new = cold.fitted_state(), reused.fitted_state()
    assert set(s_cold) == set(s_new)
    for key in s_cold:
        np.testing.assert_array_equal(np.asarray(s_cold[key]),
                                      np.asarray(s_new[key]), err_msg=key)


def test_tree_sweep_refit_skips_rebinning(monkeypatch):
    """End-to-end: the winner refit of a tree sweep performs NO new
    quantile-edge computation — the sweep's bin-once plan covers it."""
    from transmogrifai_tpu.models import trees as trees_mod
    calls = {"n": 0}
    orig = trees_mod._TreePredictor._edges_of

    def counting(self, X, max_bins):
        calls["n"] += 1
        return orig(self, X, max_bins)

    monkeypatch.setattr(trees_mod._TreePredictor, "_edges_of", counting)
    frame = _frame(seed=13)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpGBTClassifier(num_rounds=3, max_depth=2),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    _train(sel, frame)
    # exactly ONE edge computation: the sweep's dataset-level plan; the
    # refit reuses it (pre-round-9 this was 2 — sweep plan + refit rebin)
    assert calls["n"] == 1, calls


def test_regression_tree_sweep_one_sync(monkeypatch):
    """Regression evaluator's device metric variant serves the async
    path too (GBT regressor + linear regression in one sweep)."""
    frame = _reg_frame(seed=5)
    sel = RegressionModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLinearRegression(max_iter=30),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpGBTRegressor(num_rounds=3, max_depth=2),
             [{"learning_rate": 0.2}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    sweep_counters.reset()
    _train(sel, frame)
    run = sweep_counters.run_to_json()
    assert run["sweepHostSyncs"] == 1 and run["asyncFamilies"] == 2, run


# ---------------------------------------------------------------------------
# refit checkpoint
# ---------------------------------------------------------------------------

def test_refit_checkpoint_resume_skips_winner_retrain(tmp_path,
                                                      monkeypatch):
    """A rerun against a completed checkpoint dir replays the sweep AND
    restores the refit winner from its shape-keyed entry: zero model
    fits, identical summary, bit-identical fitted winner."""
    frame = _frame(seed=17)
    ckpt = str(tmp_path / "sweep")

    def make_sel():
        return BinaryClassificationModelSelector.with_cross_validation(
            n_folds=2, seed=1,
            models_and_parameters=[
                (OpLogisticRegression(max_iter=25),
                 [{"reg_param": r} for r in (0.01, 0.1)]),
            ],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
            checkpoint_dir=ckpt)

    m1 = _train(make_sel(), frame)
    assert os.path.exists(os.path.join(ckpt, "refit.json"))
    assert os.path.exists(os.path.join(ckpt, "refit.npz"))

    calls = {"n": 0}
    orig = OpLogisticRegression.fit_arrays

    def counting(self, *a, **kw):
        calls["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(OpLogisticRegression, "fit_arrays", counting)
    m2 = _train(make_sel(), frame)
    assert calls["n"] == 0  # sweep replayed AND refit restored
    s1, s2 = m1.selector_summary(), m2.selector_summary()
    assert s1.best_model_name == s2.best_model_name
    for block in ("train_evaluation", "holdout_evaluation"):
        assert getattr(s1, block) == getattr(s2, block)


def test_stale_refit_checkpoint_is_ignored(tmp_path):
    """A refit entry written by a DIFFERENT sweep config (fingerprint
    mismatch) must not be restored."""
    frame = _frame(seed=19)
    ckpt = str(tmp_path / "sweep")

    def make_sel(reg):
        return BinaryClassificationModelSelector.with_cross_validation(
            n_folds=2, seed=1,
            models_and_parameters=[
                (OpLogisticRegression(max_iter=25),
                 [{"reg_param": reg}]),
            ],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
            checkpoint_dir=ckpt)

    _train(make_sel(0.01), frame)
    s2 = _train(make_sel(0.1), frame).selector_summary()  # different config
    assert s2.best_model_name.startswith("OpLogisticRegression_0")
    params = s2.to_json()["bestModelParams"]
    assert params["reg_param"] == 0.1

# ---------------------------------------------------------------------------
# retained-path contract compatibility (post-review regressions)
# ---------------------------------------------------------------------------

def test_retained_path_gates_n_classes_for_old_arity_overrides():
    """`grid_scores_folds_retained` must signature-gate `_n_classes` before
    threading it into overridable trainer methods — a pre-round-9 subclass
    with the old arity would otherwise TypeError and be dropped from
    selection instead of training."""
    from transmogrifai_tpu.models.extras import (
        OpMultilayerPerceptronClassifier,
    )

    class OldArityLR(OpLogisticRegression):
        def _fold_stacked_params(self, X, y, w, grid):  # pre-round-9 arity
            return super()._fold_stacked_params(X, y, w, grid)

    class OldArityMLP(OpMultilayerPerceptronClassifier):
        def grid_fit_arrays_folds(self, X, y, w, grid):  # pre-round-9 arity
            return super().grid_fit_arrays_folds(X, y, w, grid)

    rng = np.random.default_rng(0)
    k, n, d = 2, 64, 4
    X = jnp.asarray(rng.normal(size=(k, n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(k, n)), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    Xva = X[:, :16]

    s, warm = OldArityLR(max_iter=5).grid_scores_folds_retained(
        X, y, w, [{"reg_param": 0.1}], Xva, _n_classes=2)
    assert s is not None and s.shape == (k, 1, 16)
    assert warm is not None  # the fused body still retains the handle

    s, warm = OldArityMLP(max_iter=3).grid_scores_folds_retained(
        X, y, w, [{"step_size": 0.1}], Xva, _n_classes=2)
    assert s is not None and s.shape == (k, 1, 16)


def test_retained_path_none_models_signal_falls_back():
    """`grid_fit_arrays_folds` returning None is the documented
    can't-serve-the-stacked-path signal; the retained path must convert it
    to (None, None) — selector fold-loop fallback — not crash."""
    from transmogrifai_tpu.models.extras import (
        OpGeneralizedLinearRegression, OpMultilayerPerceptronClassifier,
    )

    class NoneMLP(OpMultilayerPerceptronClassifier):
        def grid_fit_arrays_folds(self, X, y, w, grid, _n_classes=None):
            return None

    class NoneGLM(OpGeneralizedLinearRegression):
        def grid_fit_arrays_folds(self, X, y, w, grid):
            return None

    rng = np.random.default_rng(1)
    k, n, d = 2, 32, 3
    X = jnp.asarray(rng.normal(size=(k, n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(k, n)), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    Xva = X[:, :8]

    assert NoneMLP(max_iter=3).grid_scores_folds_retained(
        X, y, w, [{"step_size": 0.1}], Xva, _n_classes=2) == (None, None)
    assert NoneGLM(max_iter=3).grid_scores_folds_retained(
        X, y, w, [{"reg_param": 0.1}], Xva) == (None, None)


def test_finalize_releases_losing_warm_handles(monkeypatch):
    """Only the winning family's warm handle may survive into the refit —
    the losers' stacked fold parameters are released before the full-data
    program peaks HBM."""
    from transmogrifai_tpu.selector.model_selector import ModelSelector

    seen = {}
    orig = ModelSelector._refit

    def spy(self, best_ci, best_gj, best_params, Xt, yt, wt, refit_state):
        seen["warm_keys"] = set(refit_state.get("warm", {}))
        seen["best_ci"] = best_ci
        return orig(self, best_ci, best_gj, best_params, Xt, yt, wt,
                    refit_state)

    monkeypatch.setattr(ModelSelector, "_refit", spy)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=25),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpLinearSVC(max_iter=25), [{"reg_param": 0.1}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    _train(sel, _frame(seed=23))
    assert seen["warm_keys"] <= {seen["best_ci"]}
