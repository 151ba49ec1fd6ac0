"""Predictor/PredictionModel bases.

Parity: reference ``core/.../stages/sparkwrappers/specific/OpPredictorWrapper
.scala:70-153`` and the OP model wrappers (`OpLogisticRegression` etc.) —
every model is an Estimator of (response RealNN, features OPVector) ->
Prediction, whose fitted form is a Transformer exposing row-level scoring.

TPU-first: instead of wrapping an external engine, each model family
implements ``fit_arrays(X, y, w, params)`` as pure JAX and, when the math
allows, ``grid_fit_arrays`` training the entire hyperparameter grid as one
stacked ``vmap``/sharded program (the ModelSelector's sweep axis — reference
P3 thread-pool parallelism becomes a batched leading axis).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.stages.base import (
    AllowLabelAsInput, DeviceTransformer, Estimator,
)
from transmogrifai_tpu.types import feature_types as ft

__all__ = ["Predictor", "PredictionModel", "FoldBatch",
           "supports_fold_stacking", "supports_tree_stacking",
           "compile_refit"]


class FoldBatch:
    """The sweep's k-fold plan over ONE resident training matrix.

    ``X [n, d]``, ``y``, ``w [n]`` are the prepared training rows as the
    selector holds them; ``tr_idx [k, n_tr]`` / ``va_idx [k, n_va]`` are
    the validator's stacked fold plan over those rows. A family that can
    train a fold as a row-weight over ``X`` (``fold_weights``) never copies
    the matrix; one that needs each fold as an array of its own asks for
    ``training_folds`` / ``validation_folds``, which gather once, are
    shared by every family that asks, and are counted in
    ``sweepOperandBytes``."""

    def __init__(self, X, y, w, tr_idx: np.ndarray, va_idx: np.ndarray):
        from transmogrifai_tpu.parallel import mesh as pmesh
        self.n = int(X.shape[0])                 # logical training rows
        self.X, self.y, self.w = pmesh.shard_training_rows(X, y, w)
        self.tr_idx, self.va_idx = tr_idx, va_idx
        self.k, self.n_tr = (int(v) for v in tr_idx.shape)
        self.n_va = int(va_idx.shape[1])
        self.d = int(X.shape[1])
        self._cache: dict = {}

    def _once(self, key, build):
        if key not in self._cache:
            self._cache[key] = build()
        return self._cache[key]

    def fold_weights(self):
        """``[k, rows of X]``: row weight where the row trains fold ``f``,
        0 where it validates it (and on mesh padding)."""
        def build():
            jtr = jnp.asarray(self.tr_idx)
            wf = jnp.zeros((self.k, int(self.X.shape[0])), jnp.float32)
            return wf.at[jnp.arange(self.k)[:, None], jtr].set(
                jnp.take(self.w, jtr).astype(jnp.float32))
        return self._once("wf", build)

    def n_classes_hint(self) -> int:
        """Class count of the rows that train some fold: the ONE label
        pull every softmax family would otherwise block on at dispatch."""
        def build():
            member = np.zeros(int(self.X.shape[0]), bool)
            member[self.tr_idx.ravel()] = True
            top = jnp.max(jnp.where(jnp.asarray(member), self.y, 0.0))
            return max(int(np.asarray(top)) + 1, 2)
        return self._once("n_classes", build)

    def training_folds(self):
        """``(X [k, n_tr, d], y [k, n_tr], w [k, n_tr])`` gathered copies,
        rows padded and sharded 2-D under a mesh (rows on "data", folds on
        "model" when they divide it)."""
        def build():
            from transmogrifai_tpu.parallel import mesh as pmesh
            from transmogrifai_tpu.utils.profiling import sweep_counters
            jtr = jnp.asarray(self.tr_idx)
            Xtr = jnp.take(self.X, jtr, axis=0)
            sweep_counters.count_run(operand_bytes=Xtr.nbytes)
            return pmesh.shard_stacked_training_rows(
                Xtr, jnp.take(self.y, jtr, axis=0),
                jnp.take(self.w, jtr, axis=0))
        return self._once("train", build)

    def validation_folds(self):
        """``X [k, n_va, d]`` gathered; unpadded: metrics see real rows."""
        def build():
            from transmogrifai_tpu.utils.profiling import sweep_counters
            Xva = jnp.take(self.X, jnp.asarray(self.va_idx), axis=0)
            sweep_counters.count_run(operand_bytes=Xva.nbytes)
            return Xva
        return self._once("val", build)

    def validation_labels(self):
        return self._once("yva", lambda: jnp.take(
            self.y, jnp.asarray(self.va_idx), axis=0))


def compile_refit(fn, *, donate_argnums: tuple[int, ...] = (),
                  static_argnames: tuple[str, ...] = ()):
    """Compile a warm-refit program with its initial-parameter buffers
    DONATED (round 9): the stacked fold parameters feeding the winner's
    warm start are dead after the refit consumes them, so donation lets
    XLA reuse their device storage for the refit's own parameter arrays
    in place instead of holding both copies live. Donation is a no-op
    (and a warning) on backends without buffer aliasing — plain CPU — so
    it is applied only where the runtime honors it."""
    import jax
    donate = donate_argnums if jax.default_backend() != "cpu" else ()
    return jax.jit(fn, donate_argnums=donate,
                   static_argnames=static_argnames)


class Predictor(Estimator):
    """Base estimator for (label, features) -> Prediction models."""

    in_types = (ft.RealNN, ft.OPVector)
    out_type = ft.Prediction

    #: hyperparameters exposed to grid search, with defaults
    default_params: dict[str, Any] = {}

    def __init__(self, uid: Optional[str] = None, **params):
        unknown = set(params) - set(self.default_params)
        if unknown:
            raise ValueError(f"{type(self).__name__}: unknown params {unknown}")
        self.params = {**self.default_params, **params}
        super().__init__(uid=uid)

    def config(self) -> dict:
        return dict(self.params)

    @classmethod
    def from_config(cls, config: dict, uid: Optional[str] = None):
        return cls(uid=uid, **config)

    # -- data plumbing -------------------------------------------------------
    def _xyw(self, data) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        label_name, feat_name = self.input_names
        y_col = data.device_col(label_name)
        x_col = data.device_col(feat_name)
        w = getattr(data, "sample_weight", None)
        if w is None:
            w = jnp.ones_like(y_col.values)
        return x_col.values, y_col.values, w

    # -- model-family contract ----------------------------------------------
    def fit_arrays(self, X, y, w, params: dict) -> "PredictionModel":
        raise NotImplementedError

    def grid_fit_arrays(self, X, y, w, grid: Sequence[dict]
                        ) -> list["PredictionModel"]:
        """Train every grid point. Default: sequential; vmappable families
        override with a stacked-axis batched trainer."""
        return [self.fit_arrays(X, y, w, {**self.params, **g}) for g in grid]

    def grid_predict_scores(self, models: Sequence["PredictionModel"], X):
        """Fast sweep path: validation scores for all fitted grid models as
        one [G, n] device array (margins for binary, predictions for
        regression), or None when the family has no batched path — the
        selector then falls back to per-model evaluation."""
        return None

    # -- fold-stacked sweep contract -----------------------------------------
    def grid_fit_arrays_folds(self, X, y, w, grid: Sequence[dict]
                              ) -> Optional[list]:
        """Fold-stacked trainer: the CV sweep's fast path. ``X: [k, n, d]``,
        ``y/w: [k, n]`` carry a leading fold axis (``OpCrossValidation``
        guarantees equal fold shapes precisely so this axis exists); a
        vmappable family trains all k folds x |grid| points as ONE compiled
        program and returns a ``[k][G]`` nested list of fitted models whose
        parameters stay device-resident (no host pull inside the sweep).

        Default: ``None`` — family has no fold axis; the selector falls back
        to its per-fold loop. Families opt in by overriding; the selector's
        eligibility check (``supports_fold_stacking``) additionally refuses
        the stacked path for subclasses that override the per-fold trainers
        below the opt-in, so custom ``fit_arrays``/``grid_fit_arrays``
        semantics are never silently bypassed."""
        return None

    def grid_predict_scores_folds(self, models: Sequence[Sequence[
            "PredictionModel"]], X):
        """Fold-stacked scoring: ``models`` is the ``[k][G]`` nest from
        ``grid_fit_arrays_folds``, ``X: [k, n_va, d]`` the stacked
        validation folds; returns one ``[k, G, n_va]`` device score array
        (margins for binary, predictions for regression), past two classes
        ``[k, G, C, n_va]`` class scores (the class axis before the rows;
        an evaluator with ``scores_class_axis`` reduces them), or None
        when the family has no batched score."""
        return None

    def fold_stack_unit_width(self, grid: Sequence[dict]) -> int:
        """Per-row, per-grid-lane f32 lane count the fold-stacked trainer
        keeps live (logits/scores/residuals) — the selector's HBM guard
        multiplies this by k x G x rows. Default 4 covers the linear/GLM/NB
        families (<= 2 classes + gradients); families with wider per-row
        intermediates (hidden activations) override."""
        return 4

    def grid_scores_folds(self, X, y, w, grid: Sequence[dict], Xva,
                          _n_classes: Optional[int] = None):
        """One-call fold-stacked train+score — what the selector's fast
        path actually invokes. Default composes the two contract methods;
        families with a fully-stacked trainer override to go straight from
        stacked parameters to stacked scores, skipping the per-(fold, grid)
        model materialization round trip entirely (the sweep discards the
        models anyway — the winner refits later). Returns ``[k, G, n_va]``
        scores or None when the family can't serve the stacked path.
        ``_n_classes`` threads the selector's once-per-sweep class count
        to stacked trainers that accept it (signature-gated so custom
        overrides with the old arity keep working)."""
        import inspect
        kw = {}
        if _n_classes is not None and "_n_classes" in \
                inspect.signature(self.grid_fit_arrays_folds).parameters:
            kw["_n_classes"] = _n_classes
        models = self.grid_fit_arrays_folds(X, y, w, grid, **kw)
        if models is None:
            return None
        return self.grid_predict_scores_folds(models, Xva)

    def grid_scores_folds_retained(self, X, y, w, grid: Sequence[dict],
                                   Xva, _n_classes: Optional[int] = None):
        """One-sync sweep dispatch unit (round 9): like
        ``grid_scores_folds`` but additionally returns an opaque
        warm-start handle — the family's stacked fold parameters, kept
        device-resident so the winner refit can initialize from them
        (``refit_winner``) — as ``(scores, warm)``. ``warm`` is ``None``
        when the family has nothing reusable (closed-form fits, custom
        overrides). ``_n_classes`` threads the selector's once-per-sweep
        label-class count so the dispatch phase issues no per-family
        blocking device pull; families whose stacked trainers accept it
        receive it, others compute their own (the pre-round-9 behavior).

        Default: delegate to ``grid_scores_folds`` (honoring subclass
        overrides of it) with no warm handle."""
        import inspect
        kw = {}
        if _n_classes is not None and "_n_classes" in \
                inspect.signature(self.grid_scores_folds).parameters:
            kw["_n_classes"] = _n_classes
        return self.grid_scores_folds(X, y, w, grid, Xva, **kw), None

    def sweep_folds(self, batch: "FoldBatch", grid: Sequence[dict],
                    _n_classes: Optional[int] = None):
        """What the selector's stacked sweep invokes: ``(scores [k, G,
        n_va], warm handle)`` of every fold x grid point over ``batch``
        (``[k, G, C, n_va]`` class scores past two classes).
        Default: gather each fold into an array of its own and hand them to
        ``grid_scores_folds_retained``; a family that trains folds as row
        weights over the resident matrix overrides."""
        Xtr, ytr, wtr = batch.training_folds()
        return self.grid_scores_folds_retained(
            Xtr, ytr, wtr, grid, batch.validation_folds(),
            _n_classes=_n_classes)

    def fold_stack_bytes(self, batch: "FoldBatch", grid: Sequence[dict]
                         ) -> float:
        """Device bytes ``sweep_folds`` needs beyond the resident matrix,
        for the selector's HBM guard: the k-fold training gather plus a
        standardized/derived copy and the gradient residency the trainers
        materialize, the stacked validation folds, and the per-grid-lane
        intermediates the vmapped trainer keeps live."""
        b, G = batch, max(len(grid), 1)
        return (4.0 * b.k * b.n_tr * max(b.d, 1) * 3.0
                + 4.0 * b.k * b.n_va * max(b.d, 1)
                + 4.0 * b.k * (b.n_tr + b.n_va) * G
                * self.fold_stack_unit_width(grid))

    # -- winner refit (round 9) ----------------------------------------------
    def refit_winner(self, X, y, w, params: dict, *, warm=None,
                     lane: Optional[int] = None, hints: Optional[dict] = None
                     ) -> tuple["PredictionModel", bool]:
        """Refit the sweep winner on the full prepared training data.
        ``warm`` is the handle ``grid_scores_folds_retained`` returned for
        this family (stacked fold parameters), ``lane`` the winning grid
        index into it, ``hints`` selector-provided reuse state (trees: the
        dataset-level ``bin_plans``). Returns ``(model, warm_used)`` —
        families that can initialize from the fold parameters (or skip
        recomputing sweep byproducts) override; the default is the exact
        cold refit the serial path always ran, so refit results without an
        override stay bitwise-identical."""
        return self.fit_arrays(X, y, w, params), False

    def supports_warm_refit(self) -> bool:
        """True when ``refit_winner`` can actually use a ``warm`` handle —
        the selector retains a family's stacked fold parameters past the
        sweep ONLY then (holding them until the refit costs HBM, so
        families with cold refits must not opt in)."""
        return False

    def fit_model(self, data) -> "PredictionModel":
        X, y, w = self._xyw(data)
        return self.fit_arrays(X, y, w, self.params)


def _stacking_safe(est: Predictor, opt_in: tuple[str, ...],
                   guarded: tuple[str, ...]) -> bool:
    """Shared capability rule for both stacking contracts: the family
    defined one of the ``opt_in`` methods somewhere below ``Predictor``
    (opted in), AND no subclass overrides any of the ``guarded`` per-fold
    trainers/scorers *more derived than* that opt-in in the MRO — a test
    double or wrapper that redefines them (counting fits, injecting
    failures, changing the math) must keep its semantics, so the sweep
    routes such families through the per-fold loop where the override is
    actually called."""
    mro = type(est).__mro__
    owner_i = min((i for i, c in enumerate(mro) if c is not Predictor
                   and any(n in vars(c) for n in opt_in)), default=None)
    if owner_i is None:
        return False  # never opted in (base default = no stacked axis)
    for name in guarded:
        def_i = next((i for i, c in enumerate(mro) if name in vars(c)), None)
        if def_i is not None and def_i < owner_i:
            return False  # more-derived per-fold override would be bypassed
    return True


def supports_fold_stacking(est: Predictor) -> bool:
    """True when the estimator's fold-stacked trainer
    (``grid_fit_arrays_folds``/``grid_scores_folds``) is safe to use in
    place of its per-fold one (see ``_stacking_safe``)."""
    return _stacking_safe(
        est,
        ("grid_fit_arrays_folds", "grid_scores_folds",
         "_fold_stacked_params", "sweep_folds"),
        ("grid_fit_arrays", "fit_arrays", "grid_predict_scores",
         "grid_predict_scores_folds"))


def supports_tree_stacking(est: Predictor) -> bool:
    """True when the estimator's fold x grid-stacked TREE trainer
    (``tree_stack_scores`` + ``tree_stack_groups``, opted in by
    ``models.trees._TreePredictor``) is safe to use in place of its
    per-fold loop. Same override discipline as ``supports_fold_stacking``:
    subclasses redefining the per-fold trainers below the opt-in (e.g.
    ``OpDecisionTree*``, which mutate ``bootstrap`` inside a custom
    ``fit_arrays``) keep the loop where their semantics run."""
    return _stacking_safe(
        est,
        ("tree_stack_scores", "tree_stack_groups"),
        ("grid_fit_arrays", "fit_arrays", "grid_predict_scores"))


class PredictionModel(AllowLabelAsInput, DeviceTransformer):
    """Fitted model: consumes only the features vector at transform time.

    ``AllowLabelAsInput``: the optional leading label input exists for
    lineage/naming parity only — ``runtime_input_names`` excludes it, so
    wiring a fitted/imported model directly under a workflow (the MLeap
    serving analog) is not label leakage."""

    in_types = (ft.RealNN, ft.OPVector)
    out_type = ft.Prediction

    def runtime_input_names(self) -> tuple[str, ...]:
        return (self.input_names[1],) if len(self.input_names) == 2 \
            else self.input_names

    def validate_inputs(self, features) -> None:
        super().validate_inputs(features)
        # the AllowLabelAsInput exemption covers ONLY the designated label
        # slot (0): a response-DERIVED features vector is still leakage
        feat_slots = features[1:] if len(features) >= 2 else features
        bad = [f.name for f in feat_slots if f.is_response]
        if bad:
            raise ValueError(
                f"{self}: response-derived feature(s) {bad} cannot feed "
                "the model's FEATURES slot (label leakage); only the "
                "leading label input may be a response")

    # device_apply(params, features_col) -> PredictionColumn
    def predict_arrays(self, X) -> fr.PredictionColumn:
        """One JITTED apply. In the fused layer program this path is
        already compiled; here (sweep fallback scoring, LOCO, row path) an
        eager device_apply would dispatch every primitive separately —
        for tree ensembles that is thousands of eager gathers per call.

        The cache keys on ``config()``: device_apply bakes structural
        Python attributes (probabilistic/family/kind/...) into the trace,
        and those may change via ``set_fitted_state`` after a first
        predict — a stale trace would silently keep the OLD semantics.

        What a trace may hold, and what it must not: attributes that
        choose the program's STRUCTURE (a kind, a class count, a depth, a
        link) and values the user stated (a grid's learning rate) are
        trace constants. A value FITTED FROM THE DATA (weights, trees,
        bin edges, a base score, a prior) is never one: it goes through
        ``device_params()`` as an array. A data value in the trace makes
        the lowered module differ from table to table, so that every
        retrain on fresh data misses the persistent compile cache and
        compiles this program, and the fused program that scores with it,
        anew (``tests/test_devicewatch.py -k fresh_table``)."""
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        cfg = self.config()
        cached = self.__dict__.get("_jit_apply")
        if cached is None or cached[0] != cfg:
            def predict_arrays(p, c):  # names the program jit_predict_arrays
                return self.device_apply(p, c)

            cached = (cfg, jax.jit(predict_arrays))
            self.__dict__["_jit_apply"] = cached
        with compile_telemetry.building(f"predict:{type(self).__name__}"):
            return cached[1](self.device_params(), fr.VectorColumn(X))

    def transform_row(self, *values):
        """Row path: last value is the feature vector (label may be absent)."""
        x = np.asarray(values[-1], dtype=np.float32)[None, :]
        out = self.predict_arrays(jnp.asarray(x))
        return ft.Prediction.make(
            float(out.prediction[0]),
            np.asarray(out.raw_prediction[0]),
            np.asarray(out.probability[0])).value
