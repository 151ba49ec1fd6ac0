"""Fold x grid-stacked TREE sweep (round 8): exact stacked-vs-loop metric
parity for RF/GBT on binary and regression suites, the one-sync-per-
depth-group counter contract, HBM-guard lane chunking, checkpoint resume
across layouts (stacked <-> loop), the selector's route table, the batched
histogram engines, and the capability rules."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models.base import (
    supports_fold_stacking, supports_tree_stacking,
)
from transmogrifai_tpu.models.linear import OpLinearSVC, OpLogisticRegression
from transmogrifai_tpu.models.trees import (
    OpDecisionTreeClassifier, OpGBTClassifier, OpGBTRegressor,
    OpRandomForestClassifier, OpRandomForestRegressor, OpXGBoostClassifier,
)
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector, DataSplitter, RegressionModelSelector,
)
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.uid import UID
from transmogrifai_tpu.utils.profiling import sweep_counters
from transmogrifai_tpu.workflow import Workflow


def _frame(n=240, seed=0, regression=False, classes=2):
    rng = np.random.default_rng(seed)
    if regression:
        x = rng.normal(size=n)
        y = 2.0 * x + rng.normal(size=n) * 0.3
    else:
        y = rng.integers(0, classes, n).astype(float)
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


def _tree_binary_selector(**kw):
    """Same-shape lanes per family: every lane of a family shares one
    compiled-program shape, so stacked-vs-loop parity is EXACT (both
    paths score through the binned batch metric)."""
    return BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpGBTClassifier(num_rounds=3, max_depth=2, max_bins=8),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),
            (OpRandomForestClassifier(num_rounds=3, max_depth=2,
                                      max_bins=8),
             [{"reg_lambda": rl} for rl in (1e-3, 1e-2)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1), **kw)


def _tree_regression_selector(**kw):
    return RegressionModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpGBTRegressor(num_rounds=3, max_depth=2, max_bins=8),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),
            (OpRandomForestRegressor(num_rounds=3, max_depth=2, max_bins=8),
             [{"reg_lambda": rl} for rl in (1e-3, 1e-2)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1), **kw)


@pytest.fixture(scope="module")
def shared_frame():
    """ONE 240-row binary frame shared by every test that exercises the
    canonical ``_tree_binary_selector`` (tier-1 wall: training the same
    selector on per-test frames re-paid the full sweep repeatedly)."""
    return _frame()


@pytest.fixture(scope="module")
def stacked_run(shared_frame):
    """Module-scoped canonical STACKED sweep: (summary, counters) for
    ``_tree_binary_selector`` trained once with nothing set."""
    sweep_counters.reset()
    s = _train(_tree_binary_selector(), shared_frame).selector_summary()
    return s, sweep_counters.to_json()


@pytest.fixture(scope="module")
def loop_run(shared_frame, fold_loop):
    """Module-scoped canonical per-fold LOOP sweep on the same frame."""
    with fold_loop():
        sweep_counters.reset()
        s = _train(_tree_binary_selector(), shared_frame).selector_summary()
        return s, sweep_counters.to_json()


def _summaries_equal(s1, s2, tol=1e-6):
    assert s1.best_model_name == s2.best_model_name
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert set(v1) == set(v2)
    for k in v1:
        for m in v1[k]:
            assert abs(v1[k][m] - v2[k][m]) <= tol, (k, m)


def test_tree_stacked_parity_binary(stacked_run, loop_run):
    """RF + GBT: the fold x grid-stacked path reproduces the per-fold
    loop's winner and per-candidate metrics EXACTLY (same binned sweep
    metric, same bin-once codes, same PRNG draws)."""
    s1, c1 = stacked_run
    s2, c2 = loop_run
    _summaries_equal(s1, s2, tol=0.0)
    assert all(v["mode"] == "tree_stacked" for v in c1.values()), c1
    assert all(v["mode"] == "fold_loop" for v in c2.values()), c2


def test_tree_stacked_parity_regression(fold_loop):
    frame = _frame(seed=3, regression=True)
    s1 = _train(_tree_regression_selector(), frame).selector_summary()
    with fold_loop():
        s2 = _train(_tree_regression_selector(), frame).selector_summary()
    _summaries_equal(s1, s2, tol=0.0)


def test_tree_stacked_one_sync_per_depth_group(fold_loop):
    """The acceptance counter: a tree depth-group costs <= 1 blocking
    host sync and 1 fused dispatch for all k folds x L lanes. A
    mixed-depth grid forms one group per depth; each costs one
    dispatch + one sync (the loop pays k dispatches and, for mixed
    shapes with no batched scorer, k x L syncs)."""
    frame = _frame(seed=5)
    sel = lambda: BinaryClassificationModelSelector.with_cross_validation(  # noqa: E731
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpGBTClassifier(num_rounds=3, max_depth=2, max_bins=8),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),   # 1 group
            (OpRandomForestClassifier(num_rounds=3, max_depth=2,
                                      max_bins=8),
             [{"max_depth": 2}, {"max_depth": 3}]),           # 2 groups
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    sweep_counters.reset()
    _train(sel(), frame)
    c = sweep_counters.to_json()
    gbt, rf = c["OpGBTClassifier_0"], c["OpRandomForestClassifier_1"]
    assert gbt["mode"] == rf["mode"] == "tree_stacked"
    assert gbt["stackedGroups"] == 1 and rf["stackedGroups"] == 2
    # <= 1 sync and 1 dispatch PER GROUP (no chunking at default budget)
    assert gbt["hostSyncs"] == gbt["deviceDispatches"] == 1, gbt
    assert rf["hostSyncs"] == rf["deviceDispatches"] == 2, rf
    assert gbt["laneChunks"] == 1 and rf["laneChunks"] == 2
    with fold_loop():
        sweep_counters.reset()
        _train(sel(), frame)
    c = sweep_counters.to_json()
    assert c["OpGBTClassifier_0"]["hostSyncs"] == 3       # one per fold
    assert c["OpRandomForestClassifier_1"]["hostSyncs"] == 6  # k x L


def test_tree_stacked_mixed_depth_close_to_loop(fold_loop):
    """Mixed-depth grids: the loop path has no batched scorer (mixed
    shapes) and falls to the EXACT per-model metric, while the stacked
    path scores through the binned batch metric — the same binned-vs-
    exact estimator gap the linear sweep already carries. Values agree
    to the binned-metric resolution."""
    frame = _frame(seed=6)
    sel = lambda: BinaryClassificationModelSelector.with_cross_validation(  # noqa: E731
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpRandomForestClassifier(num_rounds=3, max_depth=2,
                                      max_bins=8),
             [{"max_depth": 2}, {"max_depth": 3}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    s1 = _train(sel(), frame).selector_summary()
    with fold_loop():
        s2 = _train(sel(), frame).selector_summary()
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert set(v1) == set(v2)
    for k in v1:
        for m in v1[k]:
            assert abs(v1[k][m] - v2[k][m]) <= 5e-3, (k, m)


def test_tree_stacking_capability_rules():
    assert supports_tree_stacking(OpGBTClassifier())
    assert supports_tree_stacking(OpGBTRegressor())
    assert supports_tree_stacking(OpXGBoostClassifier())
    assert supports_tree_stacking(OpRandomForestClassifier())
    assert supports_tree_stacking(OpRandomForestRegressor())
    # decision trees mutate bootstrap inside a custom fit_arrays below the
    # opt-in: their semantics must keep running in the loop
    assert not supports_tree_stacking(OpDecisionTreeClassifier())
    # non-tree families never opt into the TREE contract (and trees never
    # opt into the linear fold-stacking one)
    assert not supports_tree_stacking(OpLinearSVC())
    assert not supports_fold_stacking(OpGBTClassifier())

    class CountingGBT(OpGBTClassifier):
        def grid_fit_arrays(self, X, y, w, grid, **kw):
            return super().grid_fit_arrays(X, y, w, grid, **kw)

    assert not supports_tree_stacking(CountingGBT())


@pytest.mark.parametrize("stackable", [True, False])
@pytest.mark.parametrize("device_metric", [True, False])
@pytest.mark.parametrize("fits", [True, False])
def test_selector_route_table(stackable, device_metric, fits, monkeypatch,
                              fold_loop, shared_frame):
    """The selector's whole routing decision, read from ``sweep_counters``'
    ``mode``: a family's unit runs stacked exactly when the family has a
    stacked form AND the evaluator has the device fold metric AND the unit
    fits the budget; any one missing sends it to the per-fold loop. No
    environment variable takes part (the budget variable only sizes the
    "does not fit" case)."""
    import contextlib

    from transmogrifai_tpu.evaluators.binary import (
        OpBinaryClassificationEvaluator,
    )

    class HostMetricEvaluator(OpBinaryClassificationEvaluator):
        metric_batch_scores_folds_device = None  # no device fold metric

    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpLogisticRegression(max_iter=10), [{"reg_param": 0.01}]),
            (OpRandomForestClassifier(num_rounds=2, max_depth=2,
                                      max_bins=8), [{}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    if not device_metric:
        sel.evaluators = [HostMetricEvaluator()]
        sel.validation_metric = "auPR"
    if not fits:
        monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET", "1")
    sweep_counters.reset()
    with (contextlib.nullcontext() if stackable else fold_loop()):
        _train(sel, shared_frame)
    modes = {f: c["mode"] for f, c in sweep_counters.to_json().items()}
    if stackable and device_metric and fits:
        assert modes == {"OpLogisticRegression_0": "fold_stacked",
                         "OpRandomForestClassifier_1": "tree_stacked"}
        assert sweep_counters.run_to_json()["sweepHostSyncs"] == 1
    else:
        assert set(modes.values()) == {"fold_loop"}, modes


def test_tree_stacked_multiclass_falls_back():
    """A binary evaluator's fold metric takes one scalar score a row: under
    it a family of three outputs keeps the per-fold loop, and says why
    (under the multiclass evaluator it rides the stacked path:
    ``tests/test_multiclass_sweep.py``)."""
    frame = _frame(seed=7, classes=3)
    sweep_counters.reset()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[
            (OpRandomForestClassifier(num_rounds=2, max_depth=2,
                                      max_bins=8), [{}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    _train(sel, frame)
    c = sweep_counters.to_json()
    assert c["OpRandomForestClassifier_0"]["mode"] == "fold_loop", c
    assert sweep_counters.run_to_json()["sweepLoopFallbackReasons"] == {
        "no_device_metric": 1}


def test_hbm_guard_lane_chunking(monkeypatch, shared_frame, stacked_run):
    """A budget that fits one lane but not two splits each depth-group
    into lane chunks — one dispatch + one sync per chunk, metrics
    identical to the unchunked run (the shared module-scoped stacked
    sweep); an impossible budget (not even one lane) drops the family
    all the way to the loop."""
    frame = shared_frame
    est = OpGBTClassifier(num_rounds=3, max_depth=2, max_bins=8)
    group = est.tree_stack_groups(
        [{"learning_rate": 0.1}, {"learning_rate": 0.3}])[0]
    # the training frame: 240 rows, 0.2 holdout -> 192; 3 folds -> 128
    # training rows / 64 validation rows; 2 transmogrified features
    shared, per_lane = est.tree_stack_bytes(3, 128, 64, 2, group)
    monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET",
                       str(shared + 1.5 * per_lane))
    sweep_counters.reset()
    s1 = _train(_tree_binary_selector(), frame).selector_summary()
    c = sweep_counters.to_json()
    for name, fc in c.items():
        assert fc["mode"] == "tree_stacked", (name, fc)
        assert fc["stackedGroups"] == 1, (name, fc)
        assert fc["laneChunks"] == 2, (name, fc)       # 2 lanes, 1 each
        assert fc["hostSyncs"] == 2, (name, fc)        # one per chunk
    monkeypatch.delenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET")
    _summaries_equal(s1, stacked_run[0], tol=0.0)
    # not even one lane: the whole family keeps the per-fold loop
    monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET", "1")
    sweep_counters.reset()
    s3 = _train(_tree_binary_selector(), frame).selector_summary()
    assert all(v["mode"] == "fold_loop"
               for v in sweep_counters.to_json().values())
    _summaries_equal(s1, s3, tol=0.0)


class CrashOnce(OpLinearSVC):
    """Simulates a mid-sweep crash (NOT an isolated candidate failure):
    KeyboardInterrupt escapes the per-family isolation by design."""
    crash = {"on": True}

    def grid_fit_arrays(self, X, y, w, grid):
        if type(self).crash["on"]:
            raise KeyboardInterrupt("simulated mid-sweep crash")
        return super().grid_fit_arrays(X, y, w, grid)


def _crash_selector(ckpt, stacked_tree_first=True):
    return BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpGBTClassifier(num_rounds=3, max_depth=2, max_bins=8),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),
            (CrashOnce(max_iter=25), [{"reg_param": 0.01}]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
        checkpoint_dir=ckpt)


def test_checkpoint_stacked_written_loop_resumed(tmp_path, monkeypatch):
    """A crash after the tree family completes on the STACKED path leaves
    per-group treestack keys; a re-run that would take the LOOP layout
    (here: a budget no stacked unit fits) replays them without refitting
    (and vice versa below)."""
    frame = _frame(seed=10)
    ckpt = str(tmp_path / "sweep")
    CrashOnce.crash["on"] = True
    with pytest.raises(KeyboardInterrupt):
        _train(_crash_selector(ckpt), frame)
    saved = json.load(open(os.path.join(ckpt, "sweep.json")))
    keys = sorted(saved["entries"])
    # {ci}:treestack:{gi}:{k}x{n_tr}x{d}:{L}x{depth} — shape-keyed like
    # the per-fold and linear stacked keys (reshaped data must recompute)
    assert len(keys) == 1 and keys[0].startswith("0:treestack:0:3x") \
        and keys[0].endswith(":2x2"), keys
    assert len(saved["entries"][keys[0]]) == 3 * 2  # fold-major k x L

    CrashOnce.crash["on"] = False
    sel = _crash_selector(ckpt)
    gbt = sel.models_and_grids[0][0]
    calls = {"n": 0}
    orig = gbt.grid_fit_arrays

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)
    gbt.grid_fit_arrays = counting
    monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET", "1")
    model = _train(sel, frame)
    assert calls["n"] == 0  # replayed from the treestack checkpoint
    names = {r.model_name
             for r in model.selector_summary().validation_results}
    assert any(n.startswith("OpGBTClassifier_0") for n in names)
    assert any(n.startswith("CrashOnce_1") for n in names)


def test_checkpoint_loop_written_stacked_resumed(tmp_path, fold_loop):
    """The reverse layout hop: per-fold keys written by the loop path
    replay under the stacked path without retraining."""
    frame = _frame(seed=11)
    ckpt = str(tmp_path / "sweep")
    CrashOnce.crash["on"] = True
    with fold_loop(), pytest.raises(KeyboardInterrupt):
        _train(_crash_selector(ckpt), frame)
    saved = json.load(open(os.path.join(ckpt, "sweep.json")))
    assert all(":treestack:" not in k for k in saved["entries"])
    assert len(saved["entries"]) == 3  # one per (fold, tree family)

    CrashOnce.crash["on"] = False
    sel = _crash_selector(ckpt)
    gbt = sel.models_and_grids[0][0]
    calls = {"n": 0}
    orig = gbt.tree_stack_scores

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)
    gbt.tree_stack_scores = counting
    model = _train(sel, frame)
    assert calls["n"] == 0  # replayed from the per-fold checkpoint
    sweep_counters.reset()


def test_checkpoint_mid_family_group_resume(tmp_path):
    """A crash BETWEEN depth-groups of one family: the completed group's
    treestack key replays, only the remaining group dispatches."""
    frame = _frame(seed=12)
    ckpt = str(tmp_path / "sweep")

    def make_sel():
        return BinaryClassificationModelSelector.with_cross_validation(
            n_folds=2, seed=1,
            models_and_parameters=[
                (OpRandomForestClassifier(num_rounds=2, max_depth=2,
                                          max_bins=8),
                 [{"max_depth": 2}, {"max_depth": 3}]),  # 2 depth-groups
            ],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
            checkpoint_dir=ckpt)

    sel = make_sel()
    rf = sel.models_and_grids[0][0]
    calls = {"n": 0}
    orig = rf.tree_stack_scores

    def crash_second(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise KeyboardInterrupt("crash between depth-groups")
        return orig(*a, **k)

    rf.tree_stack_scores = crash_second
    with pytest.raises(KeyboardInterrupt):
        _train(sel, frame)
    saved = json.load(open(os.path.join(ckpt, "sweep.json")))
    keys = sorted(saved["entries"])
    assert len(keys) == 1 and keys[0].startswith("0:treestack:0:2x") \
        and keys[0].endswith(":1x2"), keys

    sel2 = make_sel()
    rf2 = sel2.models_and_grids[0][0]
    calls2 = {"n": 0}
    orig2 = rf2.tree_stack_scores

    def counting(*a, **k):
        calls2["n"] += 1
        return orig2(*a, **k)
    rf2.tree_stack_scores = counting
    model = _train(sel2, frame)
    assert calls2["n"] == 1  # only the crashed group re-dispatched
    names = {r.model_name
             for r in model.selector_summary().validation_results}
    assert len(names) == 2


def test_tree_stacked_under_mesh(shared_frame, stacked_run):
    """The stacked (fold x lane) tree batch shards 2-D over an active
    mesh (rows on "data", folds on "model" when they divide it) and
    completes on the GSPMD scatter engine. Trees are discrete: sharded
    scatter+psum reduction order can flip near-tied splits, so the
    assertion is structural (mode, coverage, finite metrics) plus a
    loose value check against the shared single-device stacked run."""
    from transmogrifai_tpu.parallel.mesh import make_mesh, use_mesh
    frame = shared_frame
    s1 = stacked_run[0]
    ctx = make_mesh(n_data=4, n_model=2)
    with use_mesh(ctx):
        sweep_counters.reset()
        s2 = _train(_tree_binary_selector(), frame).selector_summary()
        c = sweep_counters.to_json()
    assert all(v["mode"] == "tree_stacked" for v in c.values()), c
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert set(v1) == set(v2)
    for k in v1:
        for m in v1[k]:
            assert np.isfinite(v2[k][m])
            # tiny tie-prone trees: one flipped split moves auPR by ~0.05
            # on 64 validation rows; the bound catches wrong-data bugs,
            # not fp-tie reshuffles
            assert abs(v1[k][m] - v2[k][m]) <= 0.12, (k, m)


def test_batched_scatter_histogram_folds_exactly():
    """The custom_vmap rule in ops/histograms.py: a vmapped call folds
    the batch axis into the node axis and reproduces the per-slice
    histograms bit for bit, batched operands or not."""
    from transmogrifai_tpu.ops.histograms import node_bin_histogram_xla
    rng = np.random.default_rng(0)
    B, n, d, nn, nb = 3, 64, 4, 2, 8
    Xb = jnp.asarray(rng.integers(0, nb, (n, d)), jnp.int32)
    node = jnp.asarray(rng.integers(0, nn, (B, n)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(B, n)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(B, n)), jnp.float32)
    f = lambda nd, gg, hh: node_bin_histogram_xla(  # noqa: E731
        Xb, nd, gg, hh, n_nodes=nn, n_bins=nb)
    hg, hh_ = jax.vmap(f)(node, g, h)
    assert hg.shape == (B, nn, d, nb)
    for i in range(B):
        rg, rh = f(node[i], g[i], h[i])
        np.testing.assert_array_equal(np.asarray(hg[i]), np.asarray(rg))
        np.testing.assert_array_equal(np.asarray(hh_[i]), np.asarray(rh))
    # nested vmap (the fold x lane x class shape) under jit
    node2 = jnp.stack([node, node])
    g2 = jnp.stack([g, 2 * g])
    h2 = jnp.stack([h, 3 * h])
    out = jax.jit(lambda a, b, c: jax.vmap(jax.vmap(f))(a, b, c))(
        node2, g2, h2)
    ref = f(node[1], 2 * g[1], 3 * h[1])
    np.testing.assert_array_equal(np.asarray(out[0][1, 1]),
                                  np.asarray(ref[0]))


def test_stacked_engines_agree(monkeypatch):
    """The sorted engine (routed to by patching the ONE routing function:
    off a TPU it never is) under the stacked fold x lane vmaps agrees with
    the scatter engine."""
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.selector.validator import OpCrossValidation
    rng = np.random.default_rng(1)
    n, d = 160, 3
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    tr, va = OpCrossValidation(n_folds=2, seed=0).stacked_splits(n)
    jtr, jva = jnp.asarray(tr), jnp.asarray(va)
    est = OpGBTClassifier(num_rounds=2, max_depth=2, max_bins=8)
    grid = [{"learning_rate": 0.1}, {"learning_rate": 0.3}]
    plan = est.fold_sweep_plan(X, grid)
    _, codes, _ = plan[8]
    codes = codes.astype(jnp.int8)
    args = (jnp.take(codes, jtr, axis=0), jnp.take(y, jtr, axis=0),
            jnp.take(w, jtr, axis=0), jnp.take(codes, jva, axis=0))
    lnb = est.tree_stack_lnb(y)
    group = est.tree_stack_groups(grid)[0]
    s_scatter = np.asarray(
        est.tree_stack_scores(*args, group["params"], lnb))
    monkeypatch.setattr(trees, "_hist_engine",
                        lambda n_rows, n_devices=1, stacked=False: "sorted")
    s_sorted = np.asarray(
        est.tree_stack_scores(*args, group["params"], lnb))
    assert np.abs(s_scatter - s_sorted).max() <= 1e-5


@pytest.mark.parametrize("hist", ["sorted", "scatter"])
def test_tree_programs_carry_level_and_phase_scopes(hist, monkeypatch):
    """The compiled stacked tree program names each level's phases in its
    ops' ``op_name`` (``tree.L<level>/hist|split|partition``, ``/gather``
    on the sorted engine, ``tree.leaf``, ``tree.predict``) — what a device
    trace is split by — and the scopes are metadata only: the program's
    outputs are bitwise those of the same program traced without them."""
    import contextlib
    import re

    from transmogrifai_tpu.models import trees
    rng = np.random.default_rng(3)
    k, n, d, L = 2, 256, 4, 2
    Xb = jnp.asarray(rng.integers(0, 16, (k, n, d)), jnp.int8)
    y = jnp.asarray(rng.integers(0, 2, (k, n)), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    Xva = Xb[:, :64]
    args = (Xb, y, w, Xva, jnp.zeros(k, jnp.float32),
            jnp.asarray([0.1, 0.3], jnp.float32), jnp.ones(L, jnp.float32),
            jnp.zeros(L, jnp.float32), jnp.ones(L, jnp.float32))
    kw = dict(n_rounds=2, max_depth=2, n_bins=16, loss="logistic",
              subsample=1.0, colsample=1.0, bootstrap=False, seed=0,
              hist=hist, forest_margin=False)
    jax.clear_caches()   # the inner jits' traces are cached by shape
    # the lowered text, not the compiled one: the persistent compile cache
    # keys on the program without its metadata, so a cached executable
    # keeps the names it was first built with
    text = trees.train_score_stacked.lower(*args, **kw).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    phases = ["hist", "split", "partition"] + (
        ["gather"] if hist == "sorted" else [])
    for level in (0, 1):
        for phase in phases:
            assert any(f"tree.L{level}/{phase}" in nm for nm in names), \
                (level, phase)
    assert not any("tree.L2/" in nm for nm in names)    # depth 2: two levels
    assert any("tree.leaf" in nm for nm in names)
    assert any("tree.predict" in nm for nm in names)
    scoped = np.asarray(trees.train_score_stacked(*args, **kw))

    monkeypatch.setattr(trees, "device_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare_fn = jax.jit(trees.train_score_stacked.__wrapped__,
                      static_argnames=tuple(kw))
    assert "tree.L0" not in bare_fn.lower(*args, **kw).as_text(
        debug_info=True)
    bare = np.asarray(bare_fn(*args, **kw))
    jax.clear_caches()   # later tests trace the scoped growers again
    np.testing.assert_array_equal(scoped, bare)


def test_tree_stack_groups_and_bytes():
    est = OpGBTClassifier(num_rounds=4, max_depth=3, max_bins=16)
    groups = est.tree_stack_groups([
        {"learning_rate": 0.1}, {"learning_rate": 0.3},
        {"max_depth": 5}, {"num_trees": 8},   # alias num_trees->num_rounds
    ])
    shapes = [(g["max_depth"], g["num_rounds"], sorted(g["lanes"]))
              for g in groups]
    assert shapes == [(3, 4, [0, 1]), (5, 4, [2]), (3, 8, [3])]
    shared, per_lane = est.tree_stack_bytes(3, 1000, 500, 28, groups[0])
    assert shared > 0 and per_lane > 0
    # deeper groups keep more node stats live
    _, per_lane_deep = est.tree_stack_bytes(3, 1000, 500, 28, groups[1])
    assert per_lane_deep > per_lane
