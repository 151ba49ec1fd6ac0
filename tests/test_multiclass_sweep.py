"""A label of several classes through the ONE sweep path: the fold-batched
multiclass metric on the device, softmax lanes and K one-vs-all trees a
forest member on the fold-stacked path with a class axis, against the
per-fold loop and against the benchmark's plain reference
(``chipbench/reference_covtype.py``, which imports nothing of the program),
on the ``covtype_multi`` configuration's table at a few thousand rows.
"""

from __future__ import annotations

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare_covtype, data, pipeline  # noqa: E402
from chipbench import reference_covtype as reference  # noqa: E402
from chipbench.generators import covtype_like  # noqa: E402
from transmogrifai_tpu import frame as fr  # noqa: E402
from transmogrifai_tpu.evaluators.multi import (  # noqa: E402
    OpMultiClassificationEvaluator,
)
from transmogrifai_tpu.models.trees import OpRandomForestClassifier  # noqa: E402
from transmogrifai_tpu.selector import (  # noqa: E402
    DataSplitter, MultiClassificationModelSelector,
)
from transmogrifai_tpu.utils.profiling import profiler, sweep_counters  # noqa: E402
from transmogrifai_tpu.utils.tracing import recorder  # noqa: E402

SEED = 2147483777
ROWS = 4000
PUBLISHED_ROWS = 581012


def _config() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "covtype_multi.json")) as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# the fold-batched metric
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def seeded_scores():
    """``y [k, n]`` and class scores ``[k, G, K, n]`` with class 3 absent
    from fold 1 (an empty class) and tied top scores in a few rows."""
    rng = np.random.default_rng(11)
    k, G, K, n = 3, 4, 7, 600
    y = rng.integers(0, K, size=(k, n))
    y[1][y[1] == 3] = 2
    scores = rng.normal(size=(k, G, K, n)).astype(np.float32)
    scores[:, :, 5, :40] = scores[:, :, 2, :40] = 9.0   # ties: first wins
    return y, scores


@pytest.mark.parametrize("metric", ["F1", "Precision", "Recall", "Error"])
def test_device_fold_metric_equals_the_host(seeded_scores, metric):
    y, scores = seeded_scores
    ev = OpMultiClassificationEvaluator()
    got = ev.metric_batch_scores_folds(y, scores, metric)
    assert got.shape == scores.shape[:2]
    for f in range(y.shape[0]):
        for g in range(scores.shape[1]):
            pred = fr.PredictionColumn(
                np.argmax(scores[f, g], axis=0).astype(np.float32),
                scores[f, g].T, scores[f, g].T)
            want = ev.metric_from_arrays(y[f], pred, metric)
            assert abs(got[f, g] - want) < 1e-6, (f, g, got[f, g], want)


def test_device_fold_metric_takes_two_class_margins():
    """Under a two-class label the families hand on margins: decided at
    0, as the class scores ``[-s, s]`` would be."""
    rng = np.random.default_rng(3)
    y = rng.integers(0, 2, size=(2, 300))
    s = rng.normal(size=(2, 3, 300)).astype(np.float32)
    ev = OpMultiClassificationEvaluator()
    np.testing.assert_array_equal(
        ev.metric_batch_scores_folds(y, s, "F1"),
        ev.metric_batch_scores_folds(y, np.stack([-s, s], axis=2), "F1"))


# ---------------------------------------------------------------------------
# the cell's zoo on the stacked path, against the loop and the reference
# ---------------------------------------------------------------------------

def _train(table, config):
    from chipbench.units import train as kind
    frame = pipeline.to_frame(table)
    profiler.reset(app_name="test")
    model, handles, summary = kind.train_unit(frame, config["pipeline"])
    return model, handles, summary, frame


@pytest.fixture(scope="module")
def stacked_train():
    """One whole train of the cell's zoo at 4,000 rows, read back and
    compared as a benchmark run compares its timed unit."""
    config = _config()
    table = data.make_table(config["dataset"], ROWS, SEED)
    model, handles, summary, frame = _train(table, config)
    counters = dict(sweep_counters.run_to_json())
    families = sweep_counters.to_json()
    spans = [(s.name, dict(s.attrs)) for s in recorder.spans]
    produced = compare_covtype.collect(
        model, handles, summary, frame, config["pipeline"],
        np.random.default_rng(0))
    numbers = compare_covtype.check(produced, table, config)
    return produced, numbers, counters, families, spans


@pytest.fixture(scope="module")
def loop_train(fold_loop):
    config = _config()
    table = data.make_table(config["dataset"], ROWS, SEED)
    with fold_loop():
        _, _, summary, _ = _train(table, config)
    counters = dict(sweep_counters.run_to_json())
    cv = {compare_covtype.compare._grid_key(r.model_name):
          float(r.metric_values[summary.validation_metric])
          for r in summary.validation_results}
    return cv, compare_covtype.compare._grid_key(summary.best_model_name), \
        counters


def test_stacked_sweep_gives_the_loops_metrics_and_winner(stacked_train,
                                                          loop_train):
    """Every point's mean fold F1 within 1e-6 of the per-fold loop's (the
    device metric sums in float32, the loop's host metric in float64), and
    the same winner."""
    produced, _, _, _, _ = stacked_train
    cv, winner, _ = loop_train
    assert sorted(produced["cv"]) == sorted(cv) and len(cv) == 10
    for key, want in cv.items():
        assert abs(produced["cv"][key] - want) < 1e-6, (key, want)
    assert produced["winner"] == winner


def test_stacked_sweep_settles_once_and_falls_back_nowhere(stacked_train,
                                                           loop_train):
    _, _, counters, families, _ = stacked_train
    assert counters["sweepHostSyncs"] == 1
    assert counters["sweepLoopFallbacks"] == 0
    assert {f["mode"] for f in families.values()} == {"fold_stacked",
                                                      "tree_stacked"}
    _, _, loop = loop_train     # 3 folds x 10 points, one pull each
    assert loop["sweepHostSyncs"] == 30
    assert loop["sweepLoopFallbackReasons"] == {"no_stacked_form": 2}


def test_sweep_spans_carry_the_class_axis(stacked_train):
    _, _, _, _, spans = stacked_train
    for name, least in (("sweep.family", 1), ("sweep.tree_group", 2),
                        ("sweep.device", 3)):
        n_out = [a.get("nOut") for n, a in spans if n == name]
        assert len(n_out) >= least and set(n_out) == {7}, (name, n_out)
    kinds = {a["unitKind"] for n, a in spans if n == "sweep.device"}
    assert kinds == {"stacked", "tree"}
    # the report of the training rows and that of the holdout
    assert [a["classes"] for n, a in spans
            if n == "evaluate.multiclass"] == [7, 7]


def test_vector_and_kept_columns_equal_the_reference(stacked_train):
    produced, numbers, _, _, _ = stacked_train
    assert produced["vector"].shape[1] == 108
    assert produced["keep"].size == 54
    assert numbers["fe_max_abs"] == 0.0          # to the bit
    assert numbers["kept_mismatch"] == 0.0
    assert numbers["sweep_failures"] == 0.0


def test_fold_metrics_agree_with_the_reference(stacked_train):
    """The pure-L2 softmax points and the one-vs-all forest agree with the
    reference to rounding (a flipped validation row is 8e-4 of a fold's
    1,200); the L1 points end 200 Adam(0.1) steps on a limit cycle at the
    weak strengths."""
    _, numbers, _, _, _ = stacked_train
    assert numbers["cv_gap_lr_l2"] < 2e-3        # reads 2.8e-4
    assert numbers["cv_gap_rf"] < 2e-3           # reads 1.4e-8
    assert numbers["cv_gap_lr_l1"] < 4e-2
    assert numbers["winner_regret"] < 2e-3


@pytest.mark.parametrize("rows_as_class_2,weight", [
    (6000, 1.0), (300, 20.0), (30, 200.0), (0, 1.0)])
def test_a_rows_weight_in_the_fold_f1(rows_as_class_2, weight):
    """``reference.row_weight``: class 2 holds 6,000 of 100,000 rows; where
    30 of them are predicted as it, one more row so predicted moves the
    support-weighted precision as 200 ordinary rows would, and the F1 by
    about half of that; a class predicted for no row weighs nothing."""
    y = np.repeat([0, 1, 2], [57_000, 37_000, 6_000])
    yhat = y.copy()
    yhat[94_000 + rows_as_class_2:] = 1      # the rest of class 2 as class 1
    assert reference.row_weight(y, yhat, 3) == pytest.approx(weight)
    if weight < 20:     # an ordinary row: its recall moves as much
        return
    moved = yhat.copy()
    moved[0] = 2                             # a row of class 0 joins them
    f1, f1_moved = (reference.weighted_prf(y, p, 3)[2] for p in (yhat, moved))
    assert abs(f1 - f1_moved) == pytest.approx(
        weight / y.size / 2, rel=0.25)


def test_a_softmax_point_is_held_where_no_row_weighs_much(stacked_train):
    """``cv_gap_lr_l2_stable`` is over the pure-L2 points at which no row
    weighs more than ``stable_points.row_weight_max`` ordinary rows in the
    reference's own folds: at 4,000 rows the point of reg 0.2 (the two
    large classes predicted alone) and not every point."""
    _, numbers, _, _, _ = stacked_train
    assert numbers["cv_gap_lr_l2_stable"] <= numbers["cv_gap_lr_l2"]
    assert numbers["cv_gap_lr_l2_stable"] < 1e-6     # reads 6e-8


def test_refit_probabilities_agree_with_the_reference(stacked_train):
    _, numbers, _, _, _ = stacked_train
    assert numbers["holdout_prob_gap"] < 2e-2
    assert numbers["holdout_f1_gap"] < 2e-2


# ---------------------------------------------------------------------------
# the guard with a class axis
# ---------------------------------------------------------------------------

GUARD_GRID = [{}, {"min_child_weight": 2.0}]   # two lanes of one shape


def _guard_sweep(monkeypatch, lanes_room):
    rng = np.random.default_rng(5)
    n, d, K = 360, 4, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.argmax(X[:, :K] + 0.5 * rng.gumbel(size=(n, K)), axis=1
                  ).astype(np.float32)
    est = OpRandomForestClassifier(num_rounds=2, max_depth=2, max_bins=8)
    sel = MultiClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1, models_and_parameters=[(est, GUARD_GRID)],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    group, = est.tree_stack_groups(GUARD_GRID)
    if lanes_room is not None:  # room for so many lanes of K trees each
        shared, lane = est.tree_stack_bytes(3, 240, 120, d, group, K)
        monkeypatch.setenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET",
                           str(shared + lane * lanes_room + 1))
    from transmogrifai_tpu.models import trees
    calls = []
    real = trees.train_score_stacked
    monkeypatch.setattr(trees, "train_score_stacked",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    profiler.reset(app_name="test")
    _, means, failures, _ = sel._sweep(
        jnp.asarray(X), jnp.asarray(y), jnp.ones(n, jnp.float32), y)
    assert not failures
    return list(means[0]), sweep_counters.to_json(), \
        sweep_counters.run_to_json(), [kw["n_out"] for kw in calls]


@pytest.mark.parametrize("lanes_room, dispatches, reasons", [
    (None, 1, {}),              # both lanes, 3 classes each, in one program
    (2, 1, {}),
    (1, 2, {}),                 # a lane a dispatch
    (0.9, None, {"budget": 1}),  # not one lane of 3 classes: the loop
])
def test_tree_guard_counts_the_classes_of_a_lane(
        monkeypatch, lanes_room, dispatches, reasons):
    """``tree_stack_bytes`` counts the class axis; a group too wide for the
    budget trains a few lanes a dispatch, one settle, the unchunked metric
    to the bit; where not one lane of K trees fits the family takes the
    loop, and says why."""
    want, _, _, _ = _guard_sweep(monkeypatch, None)
    got, families, run, programs = _guard_sweep(monkeypatch, lanes_room)
    fam = families["OpRandomForestClassifier_0"]
    assert run["sweepLoopFallbackReasons"] == reasons
    if reasons:
        assert fam["mode"] == "fold_loop"
        assert np.allclose(got, want, atol=1e-6)
        return
    assert fam["mode"] == "tree_stacked" and run["sweepHostSyncs"] == 1
    assert programs == [3] * dispatches
    assert got == want


def test_tree_stack_bytes_counts_the_class_axis():
    est = OpRandomForestClassifier(num_rounds=2, max_depth=4, max_bins=16)
    group = est.tree_stack_groups([{}])[0]
    shared1, lane1 = est.tree_stack_bytes(3, 1000, 500, 10, group)
    shared7, lane7 = est.tree_stack_bytes(3, 1000, 500, 10, group, 7)
    # what is as wide as the outputs: margins, gradients, hessians, scores;
    # the histograms and the one-hot chunk once (the K trees grow in turn)
    assert shared7 == shared1
    assert lane7 - lane1 == 3 * 6 * (12.0 * 1000 + 8.0 * 500)


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

GEN_ROWS = 60_000


@pytest.fixture(scope="module")
def spec():
    return _config()["dataset"]


@pytest.mark.parametrize("stream", [0, 1])
@pytest.mark.parametrize("seed", [1, 2, 77, SEED, 3000000019])
def test_generator_fixes_supports_and_class_shares(spec, seed, stream):
    """Area and soil counts are the quotas whatever the seed and stream,
    so the reference's SanityChecker rules keep the same 54 columns; class
    shares stay within 0.2 points of the published ones at the published
    size, and here within that bound scaled by the root of the rows."""
    table = data.make_table(spec, GEN_ROWS, seed, stream)
    assert len(table.nums) == 54 and not table.cats
    area = [int(table.nums[f"Wilderness_Area{j + 1}"].sum())
            for j in range(4)]
    soil = [int(table.nums[f"Soil_Type{j + 1}"].sum()) for j in range(40)]
    floor = int(np.ceil(60 * GEN_ROWS / PUBLISHED_ROWS))
    assert area == list(covtype_like.quotas(spec["area_counts"], GEN_ROWS))
    assert soil == list(covtype_like.quotas(spec["soil_shares"], GEN_ROWS,
                                            floor))
    assert min(soil) >= floor and sum(soil) == sum(area) == GEN_ROWS
    for c in spec["quantitative"]:
        v = table.nums[c["name"]]
        assert v.min() >= c["lo"] and v.max() <= c["hi"]
        assert np.array_equal(v, np.rint(v))
    share = np.bincount(table.label.astype(int), minlength=7) / GEN_ROWS
    want = np.asarray(spec["class_counts"]) / PUBLISHED_ROWS
    bound = 0.002 * np.sqrt(PUBLISHED_ROWS / GEN_ROWS)
    assert np.abs(share - want).max() < bound
    X = reference.apply_fe(table, reference.fit_fe(table))
    keep, unsure = reference.sanity_keep(X, table.label)
    assert not unsure.any()
    assert list(np.nonzero(keep)[0]) == list(range(0, 108, 2))


def test_program_keeps_the_same_columns_on_another_stream(stacked_train,
                                                          spec):
    """The program's SanityChecker on a table of another seed and stream
    keeps what it kept on the train's."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.workflow import Workflow
    frame = pipeline.to_frame(data.make_table(spec, ROWS, 5, 1))
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    checked = label.transform_with(SanityChecker(),
                                   transmogrify(list(feats.values())))
    model = Workflow().set_input_frame(frame).set_result_features(
        checked).train()
    keep = compare_covtype.compare._stage(
        model, "DropIndicesModel").keep_indices
    assert list(keep) == list(stacked_train[0]["keep"])


# ---------------------------------------------------------------------------
# the other families with several outputs
# ---------------------------------------------------------------------------

def _family(name):
    from transmogrifai_tpu.models import extras, trees
    return {
        "gbt": lambda: (trees.OpGBTClassifier(), [
            {"num_rounds": 3, "max_depth": 3, "learning_rate": lr}
            for lr in (0.1, 0.3)]),
        "naive_bayes": lambda: (extras.OpNaiveBayes(), [{}]),
        "mlp": lambda: (extras.OpMultilayerPerceptronClassifier(
            layers=(8,), max_iter=20), [{}]),
    }[name]()


@pytest.mark.parametrize("name", ["gbt", "naive_bayes", "mlp"])
def test_other_families_ride_the_stacked_path_with_a_class_axis(
        name, fold_loop):
    """One-vs-all boosting (a zero base score a class), naive Bayes and the
    MLP hand on class scores too: one settle, no fallback, the loop's fold
    metrics."""
    rng = np.random.default_rng(0)
    n, d, K = 900, 6, 4
    X = np.abs(rng.normal(size=(n, d))).astype(np.float32)
    y = np.argmax(X[:, :K] + rng.gumbel(size=(n, K)), axis=1
                  ).astype(np.float32)

    def sweep(loop):
        sel = MultiClassificationModelSelector.with_cross_validation(
            n_folds=3, seed=42, models_and_parameters=[_family(name)],
            splitter=DataSplitter(reserve_test_fraction=0.1, seed=42))
        profiler.reset(app_name="test")
        args = (jnp.asarray(X), jnp.asarray(y), jnp.ones(n, jnp.float32), y)
        if loop:
            with fold_loop():
                _, means, failures, _ = sel._sweep(*args)
        else:
            _, means, failures, _ = sel._sweep(*args)
        assert not failures
        return ({(c, g): m for m, c, g in means},
                sweep_counters.run_to_json())

    got, run = sweep(False)
    want, _ = sweep(True)
    assert run["sweepHostSyncs"] == 1 and run["sweepLoopFallbacks"] == 0
    assert sorted(got) == sorted(want)
    for key in want:
        assert abs(got[key] - want[key]) < 1e-6, (key, got[key], want[key])
