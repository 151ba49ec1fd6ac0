"""External-model import: serialized third-party models -> native scoring.

Parity: reference ``local/.../MLeapModelConverter.scala:93-160`` converts
foreign serialized models (MLeap bundles of Spark stages) into local scoring
functions. The TPU-native equivalents here convert the two lingua-franca
model interchange families into this framework's device models:

- ``import_xgboost_json``: an XGBoost ``save_model`` JSON booster ->
  :class:`TreeEnsembleModel` (binary logistic or squared-error regression).
- ``import_sklearn``: a fitted scikit-learn estimator (logistic/linear
  regression, gradient boosting, random forest, decision tree) -> the
  matching native model.

Both produce models that score on the SAME jitted device path as natively
trained ones (``models/trees.py`` binned complete-tree gathers /
``models/linear.py`` matmul), so imported models batch, jit, shard, and
serialize exactly like everything else.

Conversion notes (how foreign trees map onto the binned representation):

- Native trees are dense complete depth-D arrays over BINNED features:
  prediction gathers ``go_left = x_bin <= split_bin``. A foreign tree with
  float thresholds converts by collecting every threshold used per feature
  into that feature's bin-edge list, then rewriting each split's threshold
  as its edge INDEX. ``bin_data`` assigns ``x_bin = searchsorted(edges, x,
  'left')`` (on a TPU as the count of edges below ``x``, which is the same
  number), so ``x_bin <= b  <=>  x <= edges[b]``:
  sklearn routes left on ``x <= t`` (edge = t exactly) while XGBoost routes
  left on ``x < t`` (edge = nextafter(t, -inf), the largest float32 below
  t — exact float semantics, not an epsilon).
- Arbitrary topologies embed into the complete tree: absent/non-splitting
  nodes keep feature -1 (routes every row left), so a leaf at level L lands
  at dense-leaf slot ``pos << (D - L)`` down the all-left spine.
- XGBoost ``default_left`` (missing-value routing) is ignored: the
  transmogrification layer never emits NaN (nulls become indicator
  columns). NaN inputs would bin past every edge and route right.
- Dense depth-D arrays are 2^D leaves per tree: importing is refused above
  depth 16 (reference-scale models are <= 12; unbounded sklearn forests
  must be grown with ``max_depth`` set).
"""

from __future__ import annotations

import json
import math
import os
import numpy as np

from transmogrifai_tpu.models.linear import (
    LinearClassificationModel, LinearRegressionModel,
)
from transmogrifai_tpu.models.trees import TreeEnsembleModel

__all__ = ["import_xgboost_json", "import_sklearn"]

#: complete-tree representation is 2^depth leaves: refuse beyond this
_MAX_IMPORT_DEPTH = 16


# ---------------------------------------------------------------------------
# shared: foreign tree spec -> binned dense ensemble
# ---------------------------------------------------------------------------

class _TreeSpec:
    """One foreign tree in child-pointer form. ``feature[i] < 0`` marks a
    leaf whose output is ``value[i]``; internal nodes route left when
    ``x[feature] <= edge`` with ``edge`` already in inclusive-left form."""

    def __init__(self, feature, edge, left, right, value):
        self.feature = np.asarray(feature, np.int32)
        self.edge = np.asarray(edge, np.float32)
        self.left = np.asarray(left, np.int32)
        self.right = np.asarray(right, np.int32)
        self.value = np.asarray(value, np.float32)

    def depth(self) -> int:
        # iterative: an unbounded sklearn tree can out-recurse Python long
        # before the depth guard would fire
        best, stack = 0, [(0, 0)]
        while stack:
            node, level = stack.pop()
            if self.feature[node] < 0:
                best = max(best, level)
            else:
                stack.append((int(self.left[node]), level + 1))
                stack.append((int(self.right[node]), level + 1))
        return best


def _ensemble_from_specs(specs, *, kind: str, n_features: int,
                         learning_rate: float,
                         base_score) -> TreeEnsembleModel:
    """Build the dense binned ensemble from foreign tree specs.

    ``specs`` is either a flat list (binary/regression: one output) or a
    nested list ``[round][class]`` (multiclass: n_out trees per round —
    xgboost tree_info groups / sklearn per-class estimator columns).
    ``base_score`` may be a scalar or a per-class vector (sklearn
    multiclass GBM inits at the per-class prior log-odds)."""
    nested = bool(specs) and isinstance(specs[0], (list, tuple))
    grid = [list(row) for row in specs] if nested else [[s] for s in specs]
    n_rounds, n_out = len(grid), len(grid[0])
    flat = [s for row in grid for s in row]
    depth = max(max(s.depth() for s in flat), 1)
    if depth > _MAX_IMPORT_DEPTH:
        raise ValueError(
            f"imported tree depth {depth} exceeds {_MAX_IMPORT_DEPTH} "
            "(dense complete-tree representation; retrain the source model "
            "with a bounded max_depth)")
    # per-feature sorted unique edge lists -> rectangular [d, E] matrix
    per_feat: list[set] = [set() for _ in range(n_features)]
    for s in flat:
        for i in range(len(s.feature)):
            f = int(s.feature[i])
            if f >= 0:
                per_feat[f].add(np.float32(s.edge[i]))
    edge_lists = [np.asarray(sorted(es), np.float32) for es in per_feat]
    n_edges = max(max((len(e) for e in edge_lists), default=0), 1)
    pad = np.float32(np.finfo(np.float32).max)
    bin_edges = np.full((n_features, n_edges), pad, np.float32)
    for f, e in enumerate(edge_lists):
        bin_edges[f, :len(e)] = e

    n_leaves = 1 << depth
    feats = [np.full((n_rounds, n_out, 1 << lv), -1, np.int32)
             for lv in range(depth)]
    bins = [np.zeros((n_rounds, n_out, 1 << lv), np.int32)
            for lv in range(depth)]
    leaves = np.zeros((n_rounds, n_out, n_leaves), np.float32)

    for r, row in enumerate(grid):
        for c, s in enumerate(row):
            def embed(node: int, level: int, pos: int) -> None:
                if s.feature[node] < 0:
                    # all-left descent: feature stays -1 below, rows land
                    leaves[r, c, pos << (depth - level)] = s.value[node]
                    return
                f = int(s.feature[node])
                feats[level][r, c, pos] = f
                bins[level][r, c, pos] = int(
                    np.searchsorted(edge_lists[f], np.float32(s.edge[node])))
                embed(int(s.left[node]), level + 1, pos * 2)
                embed(int(s.right[node]), level + 1, pos * 2 + 1)
            embed(0, 0, 0)

    import jax.numpy as jnp
    base = (np.asarray(base_score, np.float32)
            if np.ndim(base_score) else float(base_score))
    model = TreeEnsembleModel(kind=kind, n_out=n_out,
                              learning_rate=float(learning_rate),
                              base_score=base, max_depth=depth)
    model.bin_edges = bin_edges
    model.trees = (tuple(jnp.asarray(f) for f in feats),
                   tuple(jnp.asarray(b) for b in bins),
                   jnp.asarray(leaves))
    return model


# ---------------------------------------------------------------------------
# XGBoost JSON
# ---------------------------------------------------------------------------

def import_xgboost_json(source) -> TreeEnsembleModel:
    """Load an XGBoost ``Booster.save_model("....json")`` artifact.

    ``source`` is a file path, a JSON string, or the parsed dict. Supports
    ``binary:logistic`` (-> ``gbt_classifier``), ``multi:softprob`` /
    ``multi:softmax`` (per-class ``tree_info`` groups -> multiclass
    ``gbt_classifier``) and ``reg:squarederror`` (-> ``gbt_regressor``).
    Leaf weights in the artifact already include eta, so the imported
    model uses learning_rate 1.0; the stored ``base_score`` maps onto the
    margin through the objective's link (logit for binary:logistic,
    identity for multiclass — a uniform per-class margin is
    softmax-invariant — and for regression).
    """
    if isinstance(source, dict):
        doc = source
    elif isinstance(source, os.PathLike) \
            or (isinstance(source, str)
                and not source.lstrip().startswith("{")):
        with open(source) as fh:  # missing path -> FileNotFoundError
            doc = json.load(fh)
    else:
        doc = json.loads(source)
    learner = doc["learner"]
    objective = learner["objective"]["name"]
    booster = learner["gradient_booster"]
    if booster.get("name", "gbtree") not in ("gbtree", ""):
        raise NotImplementedError(
            f"unsupported booster {booster.get('name')!r} "
            "(only gbtree imports)")
    gb_model = booster["model"]
    tree_info = [int(t) for t in gb_model.get("tree_info", [])]
    n_features = int(learner["learner_model_param"]["num_feature"])
    num_class = int(learner["learner_model_param"].get("num_class", "0"))
    base_raw = float(learner["learner_model_param"]["base_score"])
    if objective == "binary:logistic":
        kind = "gbt_classifier"
        p = min(max(base_raw, 1e-15), 1 - 1e-15)
        base = math.log(p / (1.0 - p))
    elif objective in ("multi:softprob", "multi:softmax"):
        # per-iteration class groups; the uniform base margin is
        # softmax-invariant, so probabilities match exactly (raw margins
        # carry the same constant shift xgboost applies)
        kind = "gbt_classifier"
        base = base_raw
    elif objective in ("reg:squarederror", "reg:linear"):
        kind = "gbt_regressor"
        base = base_raw
    else:
        raise NotImplementedError(
            f"unsupported objective {objective!r} (binary:logistic, "
            "multi:softprob/softmax and reg:squarederror import)")
    if num_class <= 1 and any(t != 0 for t in tree_info):
        raise NotImplementedError(
            "grouped tree_info without num_class (boosted random forests / "
            "non-class groups) not supported")

    specs = []
    for tree in gb_model["trees"]:
        if any(int(t) != 0 for t in tree.get("split_type", ())) \
                or tree.get("categories_nodes"):
            raise NotImplementedError(
                "categorical splits (enable_categorical boosters) encode "
                "category-set partitions, not numeric thresholds — only "
                "numeric-split boosters import")
        left = np.asarray(tree["left_children"], np.int32)
        right = np.asarray(tree["right_children"], np.int32)
        cond = np.asarray(tree["split_conditions"], np.float32)
        feat = np.asarray(tree["split_indices"], np.int32)
        is_leaf = left < 0
        # leaves: split_conditions holds the leaf weight; mark feature -1.
        # internal: xgboost routes left on x < t -> inclusive edge is the
        # largest float32 strictly below t
        feature = np.where(is_leaf, -1, feat).astype(np.int32)
        edge = np.where(is_leaf, np.float32(0),
                        np.nextafter(cond, np.float32(-np.inf),
                                     dtype=np.float32))
        specs.append(_TreeSpec(feature, edge, left, right,
                               np.where(is_leaf, cond, np.float32(0))))
    if num_class > 1:
        if len(specs) % num_class:
            raise ValueError(
                f"{len(specs)} trees do not divide into {num_class} "
                "class groups")
        # tree_info assigns each tree its class; iterations are contiguous
        n_rounds = len(specs) // num_class
        by_round: list[list] = [[None] * num_class for _ in range(n_rounds)]
        seen = [0] * num_class
        for s, cls in zip(specs, tree_info):
            if not 0 <= cls < num_class or seen[cls] >= n_rounds:
                raise ValueError(
                    f"malformed tree_info: class {cls} out of range or "
                    f"over {n_rounds} rounds for num_class={num_class}")
            by_round[seen[cls]][cls] = s
            seen[cls] += 1
        if any(s is None for row in by_round for s in row):
            raise ValueError("tree_info class groups are unbalanced")
        specs = by_round
    return _ensemble_from_specs(specs, kind=kind, n_features=n_features,
                                learning_rate=1.0, base_score=base)


# ---------------------------------------------------------------------------
# scikit-learn
# ---------------------------------------------------------------------------

def _sk_tree_spec(tree, leaf_value) -> _TreeSpec:
    """sklearn ``tree_`` (routes left on x <= threshold: edge = threshold
    exactly) -> spec; ``leaf_value(node) -> float`` maps the value array."""
    n = tree.node_count
    feature = np.asarray(tree.feature, np.int32).copy()
    is_leaf = np.asarray(tree.children_left) < 0
    feature[is_leaf] = -1
    value = np.array([leaf_value(i) if is_leaf[i] else 0.0
                      for i in range(n)], np.float32)
    return _TreeSpec(feature, np.where(is_leaf, 0.0, tree.threshold),
                     tree.children_left, tree.children_right, value)


def _sk_dummy_init(est):
    """The GBM's init estimator, validated to be the default prior
    (Dummy*) or 'zero'. Custom init estimators produce a PER-ROW raw init
    (link of the init model's predictions) that no constant base_score
    can represent."""
    init = getattr(est, "init_", None)
    if init is None or init == "zero" or est.init == "zero":
        return None
    if not type(init).__name__.startswith("Dummy"):
        raise NotImplementedError(
            f"GBM with custom init estimator {type(init).__name__} has a "
            "per-row raw init; only the default prior init imports")
    return init


def _sk_gbt_base(est, is_classifier: bool) -> float:
    """Raw-prediction init of a fitted sklearn GBM: log-odds of the prior
    for classification, the constant/mean for regression ('zero' -> 0)."""
    init = _sk_dummy_init(est)
    if init is None:
        return 0.0
    if is_classifier:
        p = float(np.clip(init.class_prior_[1], 1e-15, 1 - 1e-15))
        return math.log(p / (1.0 - p))
    return float(np.ravel(init.constant_)[0])


def import_sklearn(est):
    """Convert a fitted scikit-learn estimator into the native model with
    the same scoring behavior (verified-parity families below, binary AND
    multiclass; anything else raises):

    - ``LogisticRegression`` -> :class:`LinearClassificationModel`
    - ``LinearRegression`` / ``Ridge`` / ``Lasso`` / ``ElasticNet``
      -> :class:`LinearRegressionModel`
    - ``GradientBoostingClassifier`` / ``GradientBoostingRegressor``
      -> :class:`TreeEnsembleModel` (gbt; multiclass as per-class tree
      columns with the centered-log-prior init)
    - ``RandomForestClassifier`` / ``RandomForestRegressor`` /
      ``DecisionTree*`` -> :class:`TreeEnsembleModel` (rf; a lone decision
      tree is a forest of one; multiclass as per-class probability trees)
    """
    name = type(est).__name__
    if name == "LogisticRegression":
        coef = np.asarray(est.coef_)
        if coef.shape[0] == 1:  # binary: margin -> 2-column softmax form
            d = coef.shape[1]
            W = np.zeros((d, 2))
            W[:, 1] = coef[0]
            b = np.array([0.0, float(est.intercept_[0])])
            return LinearClassificationModel(weights=W, intercept=b)
        # multinomial: predict_proba = softmax(X @ coef.T + intercept)
        return LinearClassificationModel(
            weights=coef.T.astype(np.float64),
            intercept=np.asarray(est.intercept_, np.float64))
    if name in ("LinearRegression", "Ridge", "Lasso", "ElasticNet"):
        coef = np.asarray(est.coef_, np.float64)
        if coef.ndim > 1 and coef.shape[0] != 1:
            raise NotImplementedError(
                "multi-output linear regression import is single-target "
                f"only (coef_ shape {coef.shape})")
        return LinearRegressionModel(
            weights=coef.ravel(),
            intercept=float(np.ravel(est.intercept_)[0]))
    if name == "GradientBoostingClassifier":
        if getattr(est, "loss", "log_loss") not in ("log_loss", "deviance"):
            # exponential loss maps margin->proba via expit(2*raw), not
            # the sigmoid the native gbt_classifier applies
            raise NotImplementedError(
                f"GradientBoostingClassifier loss {est.loss!r}: only "
                "log_loss imports with probability parity")
        if est.n_classes_ == 2:
            specs = [_sk_tree_spec(t.tree_,
                                   lambda i, tr=t.tree_: tr.value[i, 0, 0])
                     for t in est.estimators_[:, 0]]
            return _ensemble_from_specs(
                specs, kind="gbt_classifier",
                n_features=est.n_features_in_,
                learning_rate=float(est.learning_rate),
                base_score=_sk_gbt_base(est, True))
        # multiclass: per-class tree columns, raw = centered-log-prior
        # init + lr * per-class sums, proba = softmax(raw)
        init = _sk_dummy_init(est)
        if init is None:
            base = np.zeros(est.n_classes_)
        else:
            prior = np.clip(np.asarray(init.class_prior_, np.float64),
                            1e-15, None)
            base = np.log(prior) - np.mean(np.log(prior))
        specs = [[_sk_tree_spec(t.tree_,
                                lambda i, tr=t.tree_: tr.value[i, 0, 0])
                  for t in stage] for stage in est.estimators_]
        return _ensemble_from_specs(
            specs, kind="gbt_classifier", n_features=est.n_features_in_,
            learning_rate=float(est.learning_rate), base_score=base)
    if name == "GradientBoostingRegressor":
        specs = [_sk_tree_spec(t.tree_,
                               lambda i, tr=t.tree_: tr.value[i, 0, 0])
                 for t in est.estimators_[:, 0]]
        return _ensemble_from_specs(
            specs, kind="gbt_regressor", n_features=est.n_features_in_,
            learning_rate=float(est.learning_rate),
            base_score=_sk_gbt_base(est, False))
    if name in ("RandomForestClassifier", "DecisionTreeClassifier"):
        trees = [e.tree_ for e in est.estimators_] \
            if name == "RandomForestClassifier" else [est.tree_]
        if trees[0].value.shape[1] != 1:
            # multi-output (2D y) forests carry one class block PER output;
            # pk() reads output 0 only and would silently drop the rest
            raise NotImplementedError(
                "multi-output (2D-target) forest import not supported")
        n_cls = trees[0].value.shape[2]

        def pk(i, tr, k):  # leaf class-k probability (normalized counts)
            row = tr.value[i, 0, :]
            tot = float(row.sum())
            return float(row[k]) / tot if tot > 0 else 0.0

        if n_cls == 2:
            specs = [_sk_tree_spec(tr, lambda i, tr=tr: pk(i, tr, 1))
                     for tr in trees]
        else:
            # per-class probability trees sharing one structure: the
            # native rf path means per-class leaves then normalizes —
            # identical to sklearn's mean of per-tree probability vectors
            specs = [[_sk_tree_spec(tr, lambda i, tr=tr, k=k: pk(i, tr, k))
                      for k in range(n_cls)] for tr in trees]
        return _ensemble_from_specs(
            specs, kind="rf_classifier", n_features=est.n_features_in_,
            learning_rate=1.0, base_score=0.0)
    if name in ("RandomForestRegressor", "DecisionTreeRegressor"):
        trees = [e.tree_ for e in est.estimators_] \
            if name == "RandomForestRegressor" else [est.tree_]
        if trees[0].value.shape[1] != 1:
            # same silent-drop hazard as the classifier branch: 2D-target
            # forests store one value block per output
            raise NotImplementedError(
                "multi-output (2D-target) forest import not supported")
        specs = [_sk_tree_spec(tr, lambda i, tr=tr: tr.value[i, 0, 0])
                 for tr in trees]
        return _ensemble_from_specs(
            specs, kind="rf_regressor", n_features=est.n_features_in_,
            learning_rate=1.0, base_score=0.0)
    raise NotImplementedError(f"no import path for sklearn {name}")
