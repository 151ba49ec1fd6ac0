"""ModelSelector: the AutoML sweep.

Parity: reference ``core/.../stages/impl/selector/ModelSelector.scala:72-264``
— an Estimator of (label RealNN, features OPVector) -> Prediction that:
splits data (Splitter/Balancer/Cutter), runs the validator over every
(estimator, param-grid) candidate, refits the winner on the prepared
training data, evaluates train + holdout with every evaluator, and emits a
``ModelSelectorSummary``; the fitted stage is a ``SelectedModel`` wrapping
the winning PredictionModel.

TPU-first (SURVEY §2.7 P3): each candidate family trains its whole
hyperparameter grid AND the whole k-fold CV axis as one stacked vmapped
program (``grid_fit_arrays_folds``) — validation scoring and metrics batch
over [k, G]; the (fold x grid) work units shard 2-D over the mesh (rows on
"data", candidates on "model"). Tree families (RF/GBT) stack too (round
8): the grid groups by compiled-program shape and each depth-group's
whole k folds x L lanes batch trains as ONE program over the dataset-level
bin codes (``tree_stack_scores``), with the HBM guard splitting too-wide
groups into lane chunks. Round 9 collapses the remaining host syncs: the
sweep DISPATCHES every family's stacked program first, holding each
``[k, G]`` metric batch as a device future, then SETTLES them all behind
a single ``jax.block_until_ready`` — families overlap on device and the
entire sweep costs ONE blocking host sync (asserted end-to-end via
``SweepCounters.sweep_host_syncs``) — and the winner refit rides the same
machinery: a G=1 full-data program warm-started from the retained stacked
fold parameters (linear/GLM/MLP; trees reuse the dataset-level bin codes
bitwise) with donated init buffers, checkpointed under a shape-keyed
refit entry. A family of several outputs (a softmax, K one-vs-all trees a
member) rides the same path with a class axis in its scores. Custom
subclasses that override the per-fold trainers, and batches that would not
fit HBM at even one lane, fall back to a sequential per-fold loop (compile
once, run k times). No
thread pool, no executor dispatch. See PERF.md "Sweep execution model"
and docs/SWEEP.md.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.evaluators.base import EvaluatorBase
from transmogrifai_tpu.models.base import PredictionModel, Predictor
from transmogrifai_tpu.selector.splitters import DataSplitter
from transmogrifai_tpu.selector.validator import OpCrossValidation
from transmogrifai_tpu.stages.base import Estimator
from transmogrifai_tpu.types import feature_types as ft

__all__ = ["ModelSelector", "SelectedModel", "ModelSelectorSummary",
           "ModelEvaluation"]


@jax.jit
def _gather_rows(X, y, idx):
    return jnp.take(X, idx, axis=0), jnp.take(y, idx, axis=0)


def _take_rows(X, y, idx):
    """The rows ``idx`` of a matrix and its labels, as ONE gather each:
    eager ``X[idx]`` runs the gather and then an identity broadcast of its
    result as a second program, two copies of the split where one is
    needed. The copy is counted where it is made (``sweepOperandBytes``)."""
    from transmogrifai_tpu.utils.profiling import sweep_counters
    Xs, ys = _gather_rows(X, y, idx)
    sweep_counters.count_run(operand_bytes=Xs.nbytes)
    return Xs, ys


#: reason prefix of a family skipped for ``max_wait_s``
_MAX_WAIT_SKIP = "skipped: sweep exceeded max_wait_s="


def _is_ready(a) -> bool:
    """Whether device future ``a`` has finished; a value that cannot say
    (no ``is_ready``, or one that raises: a poisoned program) counts as
    still running, so that the blocking wait surfaces it."""
    try:
        return bool(a.is_ready())
    except Exception:  # noqa: BLE001 failure-ok: block() reports the cause
        return False


def _stamp_device(pending: list, block=None) -> None:
    """Stamp the device interval of each pending sweep program that has
    finished, as a retroactive ``sweep.device`` span.

    One chip runs programs in the order they were enqueued, so walking the
    pending chunks in dispatch order, program *i* ran from
    ``max(ready[i-1], dispatch_end[i])`` to ``ready[i]``. ``ready`` is the
    host's clock right after ``block(a)`` returned; a chunk found ready
    already (``exact=False``) only bounds its end from above, and the
    programs behind it start no earlier in this account. With ``block=None``
    the walk is a poll: it stamps what has finished and stops at the first
    chunk still running (the dispatch phase calls it where it has just
    waited on the device anyway, which keeps those bounds tight). Cost: one
    ``is_ready`` and one clock read a chunk. Entries without ``launched``
    (hand-built in tests) are blocked on and not stamped."""
    from transmogrifai_tpu.utils.tracing import recorder
    prev = 0.0
    for e in pending:
        ready = e.setdefault("ready", [None] * len(e["chunks"]))
        launched = e.get("launched")
        for j, (_c0, _ln, a) in enumerate(e["chunks"]):
            if ready[j] is not None:
                if block is not None:
                    block(a)  # cheap; a poisoned program still raises here
                prev = ready[j]
                continue
            was_ready = _is_ready(a)
            if block is None and not was_ready:
                return
            if block is not None:
                block(a)
            ready[j] = time.time()
            if launched is not None:
                t_dispatch, attrs = launched[j]
                recorder.add("sweep.device",
                             min(max(prev, t_dispatch), ready[j]), ready[j],
                             exact=not was_ready, device_window=True,
                             **attrs)
            prev = ready[j]


@dataclass
class ModelEvaluation:
    model_name: str
    model_uid: str
    model_type: str
    params: dict
    metric_values: dict


@dataclass
class ModelSelectorSummary:
    validation_type: str
    validation_metric: str
    best_model_uid: str
    best_model_name: str
    best_model_type: str
    best_params: dict
    validation_results: list[ModelEvaluation] = field(default_factory=list)
    train_evaluation: dict = field(default_factory=dict)
    holdout_evaluation: dict = field(default_factory=dict)
    data_prep_results: dict = field(default_factory=dict)
    wall_time_s: float = 0.0
    #: candidates that failed or were skipped during the sweep (reference
    #: maxWait/failed-future semantics): [{"modelName":, "reason":}]
    failures: list = field(default_factory=list)

    def to_json(self) -> dict:
        return {
            "validationType": self.validation_type,
            "validationMetric": self.validation_metric,
            "bestModelUID": self.best_model_uid,
            "bestModelName": self.best_model_name,
            "bestModelType": self.best_model_type,
            "bestModelParams": _jsonable(self.best_params),
            "validationResults": [
                {"modelName": r.model_name, "modelUID": r.model_uid,
                 "modelType": r.model_type, "modelParams": _jsonable(r.params),
                 "metricValues": _jsonable(r.metric_values)}
                for r in self.validation_results],
            "trainEvaluation": _jsonable(self.train_evaluation),
            "holdoutEvaluation": _jsonable(self.holdout_evaluation),
            "dataPrepResults": _jsonable(self.data_prep_results),
            "wallTimeSeconds": self.wall_time_s,
            "failures": _jsonable(self.failures),
        }

    @staticmethod
    def from_json(d: dict) -> "ModelSelectorSummary":
        return ModelSelectorSummary(
            validation_type=d.get("validationType", ""),
            validation_metric=d.get("validationMetric", ""),
            best_model_uid=d.get("bestModelUID", ""),
            best_model_name=d.get("bestModelName", ""),
            best_model_type=d.get("bestModelType", ""),
            best_params=d.get("bestModelParams", {}),
            validation_results=[
                ModelEvaluation(
                    model_name=r.get("modelName", ""),
                    model_uid=r.get("modelUID", ""),
                    model_type=r.get("modelType", ""),
                    params=r.get("modelParams", {}),
                    metric_values=r.get("metricValues", {}))
                for r in d.get("validationResults", [])],
            train_evaluation=d.get("trainEvaluation", {}),
            holdout_evaluation=d.get("holdoutEvaluation", {}),
            data_prep_results=d.get("dataPrepResults", {}),
            wall_time_s=d.get("wallTimeSeconds", 0.0),
            failures=d.get("failures", []),
        )


class _FoldStackFallback(Exception):
    """Internal: a family opted into the stacked path but produced no
    batched fold scores (``sweep_folds`` returned None: grid models of
    unlike shapes, an override without a stacked form) — reroute it
    through the per-fold loop instead of recording a failure."""


def _jsonable(x: Any) -> Any:
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, (float, np.floating)):
        # NaN/inf (diverged candidates) would serialize as bare NaN tokens —
        # invalid strict JSON for non-Python manifest consumers
        f = float(x)
        return f if np.isfinite(f) else None
    if isinstance(x, np.ndarray):
        return _jsonable(x.tolist())
    return x


class SelectedModel(PredictionModel):
    """The fitted winner; delegates to the wrapped PredictionModel."""

    def __init__(self, model: Optional[PredictionModel] = None,
                 summary: Optional[ModelSelectorSummary] = None,
                 uid: Optional[str] = None):
        self.model = model
        self.summary = summary
        super().__init__(uid=uid)

    def device_params(self):
        return self.model.device_params()

    def quantize_device_params(self, precision):
        return self.model.quantize_device_params(precision)

    def device_apply(self, params, col):
        return self.model.device_apply(params, col)

    def transform_row(self, *values):
        return self.model.transform_row(*values)

    def config(self):
        return {"model_class": type(self.model).__name__,
                "model_module": type(self.model).__module__,
                "model_config": self.model.config(),
                "summary": self.summary.to_json() if self.summary else None}

    @classmethod
    def from_config(cls, config, uid=None):
        import importlib
        from transmogrifai_tpu.stages.base import STAGE_REGISTRY
        name = config["model_class"]
        if name not in STAGE_REGISTRY:
            # the registry fills on import: try the recorded module first,
            # then every model family shipped in-package (covers manifests
            # whose recorded module was since renamed)
            candidates = ([config["model_module"]]
                          if config.get("model_module") else [])
            candidates += ["transmogrifai_tpu.models.linear",
                           "transmogrifai_tpu.models.trees",
                           "transmogrifai_tpu.models.extras"]
            for mod in candidates:
                try:
                    importlib.import_module(mod)
                except ImportError:
                    continue
                if name in STAGE_REGISTRY:
                    break
            else:
                raise KeyError(
                    f"Unknown model class {name!r}: not found after "
                    f"importing {candidates}; import its module first")
        model_cls = STAGE_REGISTRY[name]
        model = model_cls.from_config(config.get("model_config") or {})
        summary = None
        if config.get("summary"):
            summary = ModelSelectorSummary.from_json(config["summary"])
        return cls(model=model, summary=summary, uid=uid)

    def fitted_state(self):
        return self.model.fitted_state()

    def set_fitted_state(self, state):
        self.model.set_fitted_state(state)


class ModelSelector(Estimator):
    in_types = (ft.RealNN, ft.OPVector)
    out_type = ft.Prediction

    def __init__(self,
                 models_and_grids: Sequence[tuple[Predictor, Sequence[dict]]],
                 validator: Optional[OpCrossValidation] = None,
                 splitter: Optional[DataSplitter] = None,
                 evaluators: Sequence[EvaluatorBase] = (),
                 validation_metric: Optional[str] = None,
                 max_wait_s: Optional[float] = 3600.0,
                 checkpoint_dir: Optional[str] = None,
                 uid: Optional[str] = None):
        if not models_and_grids:
            raise ValueError("ModelSelector needs at least one candidate model")
        self.models_and_grids = [(m, list(g) or [{}]) for m, g in models_and_grids]
        self.validator = validator or OpCrossValidation()
        self.splitter = splitter
        self.evaluators = list(evaluators)
        if not self.evaluators:
            raise ValueError("ModelSelector needs at least one evaluator")
        self.validation_metric = validation_metric or \
            self.evaluators[0].default_metric
        #: sweep wall-clock budget (reference OpValidator.scala:108 maxWait):
        #: once exceeded, remaining candidate families are skipped and
        #: recorded as failures — provided at least one candidate scored
        self.max_wait_s = max_wait_s
        #: restartable sweep (SURVEY §5 failure-detection aux): completed
        #: metric batches persist to ``checkpoint_dir/sweep.json`` — one
        #: per-family key with per-fold value vectors on the fold-stacked
        #: fast path, one (fold, family) key per fold on the fallback loop;
        #: a re-run after a crash skips them (either key layout resumes
        #: under either path).
        #: The file carries a fingerprint of the sweep CONFIG (families,
        #: grids, metric, validator) and entries key on the fold's training
        #: shape — a different configuration ignores the stale file. Point
        #: each distinct dataset at its own directory: same-shaped different
        #: DATA cannot be distinguished from a restart.
        self.checkpoint_dir = checkpoint_dir
        #: degradation-ladder rungs taken this sweep (utils/resources.py):
        #: [{"site", "rung", ...shape}] — persisted into ``sweep.json`` so
        #: a checkpoint records WHICH shapes ran degraded, and a resumed
        #: run's operator can see why replayed values exist at a rung
        self._sweep_degradations: list[dict] = []
        super().__init__(uid=uid)

    # -- sweep checkpointing -------------------------------------------------
    def _ckpt_fingerprint(self) -> str:
        import hashlib
        import json
        spec = {
            "metric": self.validation_metric,
            "validator": type(self.validator).__name__,
            "validator_cfg": {
                k: v for k, v in sorted(vars(self.validator).items())
                if isinstance(v, (int, float, str, bool))},
            "families": [[type(est).__name__, grid]
                         for est, grid in self.models_and_grids],
        }
        return hashlib.sha256(
            json.dumps(spec, sort_keys=True, default=str).encode()
        ).hexdigest()[:16]

    def _ckpt_path(self) -> Optional[str]:
        if not self.checkpoint_dir:
            return None
        import os

        from transmogrifai_tpu.utils.durable import ensure_checkpoint_dir
        if not ensure_checkpoint_dir(self.checkpoint_dir,
                                     "sweep checkpoint"):
            return None
        return os.path.join(self.checkpoint_dir, "sweep.json")

    def _ckpt_load(self) -> dict:
        path = self._ckpt_path()
        if path is None:
            return {}
        import json
        import os
        if not os.path.exists(path):
            return {}
        try:
            with open(path) as fh:
                raw = json.load(fh)
            if raw.get("fingerprint") != self._ckpt_fingerprint():
                return {}  # different sweep config: stale checkpoint
            return {k: [float("nan") if v is None else float(v)
                        for v in vals]
                    for k, vals in raw["entries"].items()}
        except Exception as e:  # noqa: BLE001 — malformed/truncated file
            # must cost a fresh sweep, never a crashed run — but silently
            # eating it would hide real corruption from operators
            import warnings
            warnings.warn(
                f"sweep checkpoint: unreadable state at {path!r} "
                f"({type(e).__name__}: {e}); starting the sweep fresh",
                RuntimeWarning)
            return {}

    def _ckpt_save(self, done: dict) -> None:
        """Best-effort, atomic (``utils.durable``): a checkpoint write
        failure must never fail a sweep whose training succeeded."""
        path = self._ckpt_path()
        if path is None:
            return
        from transmogrifai_tpu.utils.durable import (
            atomic_json_dump, best_effort_checkpoint_write,
        )

        def write() -> None:
            clean = {k: [v if np.isfinite(v) else None for v in vals]
                     for k, vals in done.items()}
            atomic_json_dump({"fingerprint": self._ckpt_fingerprint(),
                              "entries": clean,
                              "degradations":
                                  list(self._sweep_degradations)},
                             path, allow_nan=False)

        best_effort_checkpoint_write(
            write, "sweep checkpoint write failed; continuing without "
                   "checkpointing")

    def _degrade(self, site: str, rung: str,
                 error: Optional[BaseException] = None, **shape) -> None:
        """Take one degradation-ladder rung (utils/resources.py): count +
        flight-recorder event + warning, and append to the sweep's rung
        log so the next checkpoint write records it."""
        from transmogrifai_tpu.utils.resources import record_degradation
        record_degradation(site, rung, error=error, **shape)
        self._sweep_degradations.append({"site": site, "rung": rung,
                                         **shape})

    @staticmethod
    def _oom_ladder(err: BaseException) -> bool:
        """True when ``err`` is an allocation failure AND the ladder is
        on — the condition under which a failing unit retries one rung
        down instead of recording a candidate failure."""
        from transmogrifai_tpu.utils.resources import (
            is_resource_exhausted, ladder_enabled,
        )
        return ladder_enabled() and is_resource_exhausted(err)

    # -- shared pieces -------------------------------------------------------
    def _split_prepare(self, n: int, y) -> tuple[np.ndarray, np.ndarray,
                                                 np.ndarray, dict]:
        """(train_idx, holdout_idx, train weights, prep summary)."""
        if self.splitter is not None:
            # pull the label to host only when the splitter actually needs it
            y_np = np.asarray(y) if getattr(self.splitter, "requires_label",
                                            True) else None
            train_idx, holdout_idx = self.splitter.split_indices(n, y_np)
            train_idx, w_train = self.splitter.prepare_indices(
                train_idx, y_np)
            prep = {self.splitter.summary.splitter:
                    self.splitter.summary.detail} \
                if self.splitter.summary else {}
            return train_idx, holdout_idx, w_train, prep
        return (np.arange(n), np.zeros(0, dtype=np.int64),
                np.ones(n, dtype=np.float32), {})

    # -- sweep ---------------------------------------------------------------
    def _family_name(self, ci: int) -> str:
        return f"{type(self.models_and_grids[ci][0]).__name__}_{ci}"

    @staticmethod
    def _stacked_hbm_budget() -> float:
        """Byte budget for one family's stacked fold batch
        (``utils/devicewatch.py::stacked_hbm_budget``, which the families
        that choose between a gathered and an in-place form read too)."""
        from transmogrifai_tpu.utils.devicewatch import stacked_hbm_budget
        return stacked_hbm_budget()

    def _stacked_fits_memory(self, batch, est, grid) -> bool:
        """HBM guard for one family's fold-stacked unit: what the family
        says it needs beyond the resident training matrix
        (``Predictor.fold_stack_bytes``: nothing matrix-sized where folds
        train as row weights; the gathered folds, their derived copies and
        the validation folds where they are arrays of their own; the
        per-grid-lane intermediates either way) must fit the budget, else
        the sweep falls back to the per-fold loop, whose peak is 1/k of a
        gathered batch."""
        return est.fold_stack_bytes(batch, grid) <= self._stacked_hbm_budget()

    def _sweep(self, Xt, yt, wt, yt_np) -> tuple[list[ModelEvaluation],
                                                 list[tuple[float, int, int]],
                                                 list[dict], dict]:
        """Run every (candidate family, grid point) over the validator's
        fold plan; returns per-candidate evaluations, (mean metric, cand,
        grid) triples, recorded failures, and the refit-reuse state
        (retained warm-start parameters + tree bin plans) for
        ``_finalize``.

        Execution model (docs/SWEEP.md), the same on every backend: the
        sweep is TWO phases. The DISPATCH phase walks the families and
        launches every stacked program — linear/NB/GLM/MLP fold-stacks
        (``Predictor.sweep_folds`` over a ``FoldBatch``) and tree depth-groups
        (``_family_tree_stacked``) alike — handing each family's ``[k, G]``
        metric batch back as a DEVICE FUTURE; no family blocks the host,
        so their programs overlap on device. The SETTLE phase
        (``_settle``) then materializes every future behind a single
        ``jax.block_until_ready`` — the whole sweep costs ONE blocking
        host sync (``SweepCounters.sweep_host_syncs``), not one per
        family/depth-group. The once-per-sweep label statistics (class
        count, tree base-score stats) are pulled up front so no family
        pays a blocking scalar sync at dispatch.

        A family leaves that path for the per-fold loop
        (``_family_fold_loop``) only for a reason the program observes: it
        has no stacked form (``supports_fold_stacking`` /
        ``supports_tree_stacking``, ``_FoldStackFallback``), the evaluator
        has no device fold metric (``metric_batch_scores_folds_device``),
        its unit does not fit the budget (``fold_stack_bytes`` /
        ``tree_stack_bytes`` against ``_stacked_hbm_budget``), or the OOM
        ladder sent it there; each such unit counts once in
        ``sweepLoopFallbacks``, under its reason. The number of outputs is
        no such reason: a softmax family hands on ``[k, G, K, n_va]`` class
        scores and a tree family of K one-vs-all trees a member
        ``[k, L, K, n_va]``, which the multiclass evaluator's device metric
        reduces to the same ``[k, G]`` futures. Work units shard 2-D over
        the mesh (rows on "data", fold/grid candidates on "model").

        Semantics preserved exactly from the per-fold loop: failure
        isolation per family (dispatch-time errors isolate immediately;
        settle-time errors re-settle family by family to isolate the
        poisoned program), the ``max_wait_s`` budget (checked at
        dispatch), checkpoint/restart (stacked families checkpoint one
        per-family key carrying per-fold value vectors, written at
        settle), and non-finite-metric exclusion.
        """
        from transmogrifai_tpu.parallel import mesh as pmesh
        refit_state: dict = {"warm": {}, "bin_plans": {}}
        self._sweep_degradations = []
        n = int(Xt.shape[0])
        d = int(Xt.shape[1])
        try:
            tr_idx, va_idx = self.validator.stacked_splits(n, yt_np)
        except ValueError:
            # custom validator with unequal fold shapes: no fold axis exists
            results, mean_metrics, failures = self._sweep_loop(
                self._fold_arrays_iter(Xt, yt, wt, yt_np))
            return results, mean_metrics, failures, refit_state
        k, n_tr = tr_idx.shape
        n_va = int(va_idx.shape[1])
        # the [k, G] fold metric as a device program: without it nothing
        # can be held as a future, and every family takes the loop
        fold_metrics_dev = getattr(self.evaluators[0],
                                   "metric_batch_scores_folds_device", None)
        per_candidate_scores: dict[tuple[int, int], list[float]] = {}
        failures: list[dict] = []
        pending: list[dict] = []  # device futures awaiting the one settle
        deadline = (time.time() + self.max_wait_s
                    if self.max_wait_s is not None else None)
        done = self._ckpt_load()
        n_tr_pad = pmesh.pad_rows(n_tr)
        tree_cache: dict = {}  # stacked code/label gathers shared by trees

        try:
            self._dispatch(
                Xt, yt, wt, tr_idx, va_idx, k, n_tr, n_va, d, n_tr_pad,
                done, deadline, per_candidate_scores, failures, pending,
                refit_state, fold_metrics_dev, tree_cache)
        except BaseException:
            # mid-sweep crash (KeyboardInterrupt, preemption, ...): settle
            # whatever was already dispatched so completed families reach
            # the checkpoint before the crash propagates (a real SIGKILL
            # can't salvage; it just re-runs those families)
            if pending:
                try:
                    self._settle(pending, done, per_candidate_scores,
                                 failures)
                except Exception:  # noqa: BLE001 failure-ok: salvage is best-effort
                    pass
            raise
        if pending:
            oom_retry: list[int] = []
            self._settle(pending, done, per_candidate_scores, failures,
                         oom_retry=oom_retry)
            # degradation ladder: a family whose stacked program OOMed at
            # settle re-dispatches down the ladder on the per-fold loop
            # (peak HBM 1/k of the stacked batch) instead of recording a
            # candidate failure — completed families' checkpoints are
            # untouched
            for ci in oom_retry:
                est, grid = self.models_and_grids[ci]
                # release the FAILED stacked program's retained fold
                # parameters: they are that program's output buffers —
                # holding them keeps the OOMed program's memory resident
                # through the retry, and a winner refit warm-started
                # from them could materialize a poisoned buffer
                refit_state.get("warm", {}).pop(ci, None)
                from transmogrifai_tpu.utils.profiling import sweep_counters
                from transmogrifai_tpu.utils.tracing import span
                sweep_counters.count_run(loop_fallback="oom")
                with span("resource.degrade", site="sweep.settle",
                          family=self._family_name(ci), rung="fold_loop"):
                    self._family_fold_loop(
                        ci, est, grid, Xt, yt, wt, tr_idx, va_idx, done,
                        deadline, per_candidate_scores, failures,
                        refit_state=refit_state)
            # a winner must survive: ``_deadline_skip`` let the later
            # families go because an earlier one had futures pending. If
            # every one of those failed at the settle, the skipped
            # families run after all, in order, until one scores (on the
            # loop a failed family leaves no scores and the next one runs)
            for ci, (est, grid) in enumerate(self.models_and_grids):
                skips = [f for f in failures
                         if f["modelName"] == self._family_name(ci)
                         and f["reason"].startswith(_MAX_WAIT_SKIP)]
                if per_candidate_scores or not skips:
                    continue
                failures.remove(skips[0])
                self._family_fold_loop(
                    ci, est, grid, Xt, yt, wt, tr_idx, va_idx, done, None,
                    per_candidate_scores, failures, refit_state=refit_state)
        results, mean_metrics, failures = self._collect_results(
            per_candidate_scores, failures)
        return results, mean_metrics, failures, refit_state

    def _dispatch(self, Xt, yt, wt, tr_idx, va_idx, k, n_tr, n_va, d,
                  n_tr_pad, done, deadline, per_candidate_scores, failures,
                  pending, refit_state, fold_metrics_dev,
                  tree_cache) -> None:
        """The sweep's dispatch phase (see ``_sweep``): walk the families,
        replay checkpointed ones, launch every stacked program, and queue
        device metric futures on ``pending``; a family that falls to the
        loop records its values inline."""
        from transmogrifai_tpu.models.base import (
            FoldBatch, supports_fold_stacking, supports_tree_stacking,
        )
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.retry import with_device_retry
        from transmogrifai_tpu.utils.tracing import span
        batch = None  # built on the first stacked-capable family
        tree_stats = None
        # whether the evaluator's fold metric reduces class scores
        # ([k, G, K, n_va]) or a scalar score a row only
        class_axis = bool(getattr(self.evaluators[0], "scores_class_axis",
                                  False))
        with span("sweep.dispatch", families=len(self.models_and_grids)):
            for ci, (est, grid) in enumerate(self.models_and_grids):
                fname = self._family_name(ci)
                skey = f"{ci}:stacked:{k}x{n_tr}x{d}"
                if skey in done and len(done[skey]) == k * len(grid):
                    # restart path: this family's whole fold batch already
                    # scored under the per-family stacked key (fold-major)
                    for f in range(k):
                        for gj in range(len(grid)):
                            per_candidate_scores.setdefault(
                                (ci, gj), []).append(
                                float(done[skey][f * len(grid) + gj]))
                    sweep_counters.count(fname, mode="resumed")
                    continue
                tgroups = (est.tree_stack_groups(grid)
                           if supports_tree_stacking(est) else None)
                if tgroups and self._treestack_replay(ci, tgroups, k, n_tr,
                                                      d, done,
                                                      per_candidate_scores):
                    # restart path: every depth-group of this tree family
                    # already scored under per-group treestack keys —
                    # replays whatever route the family would take now, so
                    # a stacked-written checkpoint resumes under the loop
                    # layout too
                    sweep_counters.count(fname, mode="resumed")
                    continue
                fold_keys = [f"{f}:{ci}:{n_tr_pad}x{d}" for f in range(k)]
                if all(fk in done and len(done[fk]) == len(grid)
                       for fk in fold_keys):
                    # restart path: a previous per-fold-loop run completed
                    # this family fold by fold
                    for fk in fold_keys:
                        for gj, val in enumerate(done[fk]):
                            per_candidate_scores.setdefault(
                                (ci, gj), []).append(float(val))
                    sweep_counters.count(fname, mode="resumed")
                    continue
                if self._deadline_skip(ci, grid, deadline,
                                       per_candidate_scores, failures,
                                       pending, pop=False):
                    continue
                use_stacked = (fold_metrics_dev is not None
                               and supports_fold_stacking(est))
                # why the family leaves the stacked path, if it does
                # (``sweepLoopFallbacks``)
                reason = ("no_device_metric" if fold_metrics_dev is None
                          else "no_stacked_form")
                if use_stacked and batch is None:
                    # the fold plan over the ONE resident training matrix:
                    # a family trains its folds as row weights over it, or
                    # asks it for gathered folds (made once, shared, counted
                    # in sweepOperandBytes)
                    batch = FoldBatch(Xt, yt, wt, tr_idx, va_idx)
                if use_stacked:
                    # the ONE class-count pull every softmax/NB/MLP family
                    # would otherwise block on at dispatch (their guard
                    # reads it too), and the folds' validation labels: the
                    # batch makes each once
                    with compile_telemetry.building("sweep.operands"):
                        n_classes_hint = batch.n_classes_hint()
                        yva_s = batch.validation_labels()
                    use_stacked = self._stacked_fits_memory(batch, est, grid)
                    reason = "budget"
                    # outputs a lane: the label's classes where the fold
                    # metric reduces a class axis, else one scalar score
                    n_out = (n_classes_hint
                             if class_axis and n_classes_hint > 2 else 1)
                if use_stacked:
                    try:
                        with compile_telemetry.building(
                                    f"sweep.family:{fname}", family=fname), \
                                span("sweep.family", family=fname,
                                     mode="fold_stacked", folds=k,
                                     grid=len(grid), nOut=n_out):
                            # fused unit: stacked train + stacked scores in
                            # one call (no per-(fold, grid) model
                            # materialization — the sweep discards models;
                            # the winner refits), retaining the stacked
                            # parameters as the refit's warm-start handle
                            scores, warm = with_device_retry(
                                est.sweep_folds, batch, grid,
                                _n_classes=n_classes_hint, site="sweep.fit")
                            if scores is None:
                                raise _FoldStackFallback()
                            # a class axis before the rows where the
                            # family scores several outputs
                            if scores.ndim == 4 and not class_axis:
                                raise _FoldStackFallback()
                            if warm is not None and est.supports_warm_refit():
                                refit_state["warm"][ci] = warm
                            # the family's [k, G] metric batch: a device
                            # FUTURE, settled once for the whole sweep
                            vals_kg = fold_metrics_dev(
                                yva_s, scores, self.validation_metric)
                    except _FoldStackFallback:
                        use_stacked = False  # no stacked axis: fold loop
                        reason = ("no_scores" if scores is None
                                  else "no_device_metric")
                    except Exception as e:  # noqa: BLE001 — isolation by design
                        from transmogrifai_tpu.utils.faults import (
                            FaultHarnessError,
                        )
                        if isinstance(e, FaultHarnessError):
                            raise  # a preempted process dies, not isolates
                        if self._oom_ladder(e):
                            # degradation ladder: the k-fold stacked batch
                            # exceeded real device memory (the HBM guard's
                            # estimate was optimistic) — retry this family
                            # one rung down on the per-fold loop, whose
                            # peak is 1/k of the stacked gather, instead
                            # of failing the candidate. Any warm handle
                            # the failed unit already retained is the
                            # failed program's output — release it.
                            refit_state["warm"].pop(ci, None)
                            self._degrade(
                                "sweep.stacked", "fold_loop", error=e,
                                family=fname, folds=int(k),
                                grid=len(grid), rows=int(n_tr),
                                cols=int(d))
                            use_stacked = False
                            reason = "oom"
                        else:
                            failures.append({
                                "modelName": fname,
                                "reason": f"stacked sweep: "
                                          f"{type(e).__name__}: "
                                          f"{str(e)[:300]}"})
                            continue
                    else:
                        sweep_counters.count(fname, dispatches=1,
                                             mode="fold_stacked")
                        pending.append({
                            "kind": "stacked", "ci": ci, "fname": fname,
                            "key": skey, "k": k, "grid_len": len(grid),
                            "chunks": [(0, len(grid), vals_kg)],
                            "launched": [(time.time(), {
                                "family": fname, "unitKind": "stacked",
                                "lanes": len(grid), "chunk": 0,
                                "nOut": n_out})]})
                        sweep_counters.count_run(async_families=1)
                        continue
                if tgroups and fold_metrics_dev is not None:
                    if tree_stats is None:
                        # the tree families' (max, mean, clipped-mean)
                        # label pull, once per sweep — each value produced
                        # by the same device expression the per-family
                        # ``_loss_and_nout`` probe runs, so threading it
                        # is bitwise-identical
                        with compile_telemetry.building("sweep.operands"):
                            tree_stats = tuple(np.asarray(jnp.stack(
                                [jnp.max(yt), jnp.mean(yt),
                                 jnp.clip(jnp.mean(yt), 1e-6, 1 - 1e-6)])))
                        # the pull waited for every program enqueued before
                        # it: stamp those that are done while the bound on
                        # their end is tight
                        _stamp_device(pending)
                    # the family's gathers and label pulls build under
                    # "sweep.operands"; its programs open their own sites
                    with compile_telemetry.building("sweep.operands",
                                                    family=fname):
                        # None: handled; else why the family takes the loop
                        reason = self._family_tree_stacked(
                            ci, est, grid, tgroups, Xt, yt, wt, tr_idx,
                            va_idx, done, deadline, per_candidate_scores,
                            failures, tree_cache, pending, fold_metrics_dev,
                            tree_stats=tree_stats, refit_state=refit_state,
                            class_axis=class_axis)
                    if reason is None:
                        continue
                # ---- per-fold fallback loop for this family ----------------
                sweep_counters.count_run(loop_fallback=reason)
                with span("sweep.family", family=fname, mode="fold_loop",
                          folds=k, grid=len(grid), reason=reason):
                    self._family_fold_loop(
                        ci, est, grid, Xt, yt, wt, tr_idx, va_idx, done,
                        deadline, per_candidate_scores, failures,
                        refit_state=refit_state, pending=pending)

    def _settle(self, pending, done, per_candidate_scores,
                failures, oom_retry: Optional[list] = None) -> None:
        """The ONE settle of the sweep: block until every dispatched
        family's metric futures are ready — a single
        ``jax.block_until_ready`` over the whole sweep, counted as ONE
        run-level host sync — then materialize, record, and checkpoint
        each family's values (the per-family ``host_syncs`` counter keeps
        its metric-pull meaning: one per family / per tree lane chunk).

        If the barrier itself raises (an async runtime failure inside
        some family's program), families re-settle one by one so the
        poisoned program isolates into ITS family's failure record — the
        same per-family isolation the dispatch phase applies — at the
        cost of per-family barriers for that (already failing) sweep.
        A settle-time failure classified as an allocation OOM (device
        pressure materialized only when the overlapped programs actually
        ran) collects its family into ``oom_retry`` instead — the caller
        re-dispatches those one rung down the degradation ladder."""
        import jax
        from transmogrifai_tpu.utils import devicewatch
        from transmogrifai_tpu.utils.faults import FaultHarnessError
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        with span("sweep.settle", device_window=True,
                  families=len({e["ci"] for e in pending}),
                  units=sum(len(e["chunks"]) for e in pending)), \
                contextlib.ExitStack() as ledger_stack:
            # the dispatch ledger the hang autopsy inventories: one
            # labeled entry per pending family/depth-group, completed as
            # that family settles (or unconditionally on exit — a
            # poisoned program must not leak a phantom in-flight entry)
            for e in pending:
                e["_dw"] = devicewatch.dispatch_ledger.register(
                    "sweep.pending", family=e["fname"],
                    unitKind=e["kind"], units=len(e["chunks"]))
                ledger_stack.callback(
                    devicewatch.dispatch_ledger.complete, e["_dw"])
            barrier_ok = True
            try:
                # the watchdog arms a stall deadline around the ONE
                # blocking sync; it adds no host syncs of its own (the
                # sweepHostSyncs == 1 contract holds armed, counter-
                # asserted in tests + DEVICEWATCH_OVERHEAD.json), and an
                # exception here — e.g. an OOM retried down the ladder —
                # disarms the deadline on block exit
                with devicewatch.guard(
                        "sweep.settle", site="sweep.settle",
                        families=len({e["ci"] for e in pending}),
                        units=sum(len(e["chunks"]) for e in pending)):
                    # still ONE barrier, walked chunk by chunk in dispatch
                    # order so that each program's device interval is
                    # stamped as it ends (``sweep.device`` spans)
                    _stamp_device(pending, block=jax.block_until_ready)
                sweep_counters.count_run(host_syncs=1)
            except FaultHarnessError:
                raise  # a preempted process dies; it does not isolate
            except Exception:  # noqa: BLE001 — re-settled per family below
                barrier_ok = False
            failed_cis: set[int] = set()
            for e in pending:
                ci = e["ci"]
                if ci in failed_cis:
                    continue
                try:
                    if not barrier_ok:
                        with devicewatch.guard(
                                "sweep.settle", site="sweep.settle",
                                family=e["fname"]):
                            jax.block_until_ready(
                                [a for _c0, _ln, a in e["chunks"]])
                        sweep_counters.count_run(host_syncs=1)
                    if e["kind"] == "stacked":
                        vals = np.asarray(e["chunks"][0][2])
                    else:  # tree depth-group: reassemble lane chunks
                        vals = np.empty((e["k"], len(e["lanes"])),
                                        np.float64)
                        for c0, ln, arr in e["chunks"]:
                            vals[:, c0:c0 + ln] = np.asarray(arr)
                except FaultHarnessError:
                    raise
                except Exception as err:  # noqa: BLE001 — isolation by design
                    failed_cis.add(ci)
                    grid = self.models_and_grids[ci][1]
                    for gj in range(len(grid)):
                        per_candidate_scores.pop((ci, gj), None)
                    if oom_retry is not None and self._oom_ladder(err):
                        # NB: "kind" would collide with emit()'s own
                        # positional — the event attr is unitKind
                        self._degrade(
                            "sweep.settle", "fold_loop", error=err,
                            family=e["fname"], unitKind=e["kind"])
                        oom_retry.append(ci)
                        continue
                    failures.append({
                        "modelName": e["fname"],
                        "reason": f"async settle: {type(err).__name__}: "
                                  f"{str(err)[:300]}"})
                    continue
                flat = [float(v) for v in vals.reshape(-1)]
                if e["kind"] == "stacked":
                    for f in range(e["k"]):
                        for gj in range(e["grid_len"]):
                            per_candidate_scores.setdefault(
                                (ci, gj), []).append(
                                flat[f * e["grid_len"] + gj])
                    sweep_counters.count(e["fname"], host_syncs=1)
                else:
                    self._record_treestack(per_candidate_scores, ci,
                                           e["lanes"], e["k"], flat)
                    sweep_counters.count(e["fname"],
                                         host_syncs=len(e["chunks"]))
                done[e["key"]] = flat
                self._ckpt_save(done)
                # settled: this family's futures are no longer in flight
                devicewatch.dispatch_ledger.complete(e.get("_dw"))

    # -- fold x grid-stacked tree sweep (round 8) ----------------------------
    @staticmethod
    def _treestack_key(ci: int, gi: int, k: int, n_tr: int, d: int,
                       group: dict) -> str:
        """Per-depth-group checkpoint key. Carries the fold plan AND the
        training shape (``n_tr x d``) like the per-fold and linear
        stacked keys do — same config against reshaped data must
        recompute, not replay stale scores."""
        return (f"{ci}:treestack:{gi}:{k}x{n_tr}x{d}:"
                f"{len(group['lanes'])}x{group['max_depth']}")

    @staticmethod
    def _record_treestack(per_candidate_scores, ci: int, lanes, k: int,
                          flat) -> None:
        """Unpack one depth-group's fold-major ``k x L`` value vector
        into per-candidate score lists — the ONE place the checkpoint
        layout is decoded (replay, group resume, and fresh scoring all
        route through here)."""
        L = len(lanes)
        for f in range(k):
            for li, gj in enumerate(lanes):
                per_candidate_scores.setdefault((ci, gj), []).append(
                    float(flat[f * L + li]))

    def _treestack_replay(self, ci, tgroups, k, n_tr, d, done,
                          per_candidate_scores) -> bool:
        """Replay a tree family whose EVERY depth-group checkpointed under
        the per-group treestack keys (fold-major k x L value vectors).
        True when the whole family was replayed."""
        keys = [self._treestack_key(ci, gi, k, n_tr, d, g)
                for gi, g in enumerate(tgroups)]
        if not all(tk in done and len(done[tk]) == k * len(g["lanes"])
                   for tk, g in zip(keys, tgroups)):
            return False
        for tk, g in zip(keys, tgroups):
            self._record_treestack(per_candidate_scores, ci, g["lanes"],
                                   k, done[tk])
        return True

    def _family_tree_stacked(self, ci, est, grid, tgroups, Xt, yt, wt,
                             tr_idx, va_idx, done, deadline,
                             per_candidate_scores, failures,
                             cache: dict, pending: list, fold_metrics_dev,
                             *, tree_stats=None,
                             refit_state: Optional[dict] = None,
                             class_axis: bool = False) -> Optional[str]:
        """One tree family's fold x grid-stacked sweep: every depth-group
        (grid lanes sharing one compiled-program shape) trains all
        k folds x L lanes as ONE compiled program over the stacked gather
        of the dataset-level bin codes (``fold_sweep_plan`` — no
        re-binning), scores its validation folds batched, and queues the
        group's ``[k, L]`` metric block on ``pending`` as device futures
        for the sweep's one settle. A family of ``n_out`` one-vs-all trees
        a member trains and scores them in the same program (class scores
        ``[k, L, n_out, n_va]``). The HBM guard (``tree_stack_bytes``)
        splits a too-wide group into lane chunks (one dispatch each)
        instead of falling all the way back. Returns None when the family
        was fully handled (dispatched, group-resumed, failed-and-isolated,
        or deadline-skipped); else the reason that routes it to the
        per-fold loop untouched (``budget``: a group where not even one
        lane fits — sub-grid loop units can't be expressed, so the loop
        keeps the whole family; ``no_device_metric``: several outputs and
        an evaluator whose fold metric takes no class axis; ``oom``)."""
        from transmogrifai_tpu.parallel import mesh as pmesh
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.retry import with_device_retry
        from transmogrifai_tpu.utils.tracing import span
        fname = self._family_name(ci)
        # the selector's once-per-sweep label stats elide what was ONE
        # blocking family-level sync here
        lnb = est.tree_stack_lnb(yt, _stats=tree_stats)
        n_out = int(lnb[1])
        if n_out > 1 and not class_axis:
            return "no_device_metric"
        k, n_tr = tr_idx.shape
        n_va = int(va_idx.shape[1])
        d = int(Xt.shape[1])
        budget = self._stacked_hbm_budget()
        chunk_sizes = []
        for g in tgroups:
            shared, per_lane = est.tree_stack_bytes(k, n_tr, n_va, d, g,
                                                    n_out)
            max_lanes = (int((budget - shared) // per_lane)
                         if budget > shared and per_lane > 0 else 0)
            if max_lanes < 1:
                return "budget"  # not even one lane fits: loop (peak 1/k)
            chunk_sizes.append(max_lanes)
        jtr = jnp.asarray(tr_idx)
        jva = jnp.asarray(va_idx)
        if "yva" not in cache:
            cache["yva"] = jnp.take(yt, jva, axis=0)
        yva_s = cache["yva"]
        needed = [mb for mb in sorted({g["max_bins"] for g in tgroups})
                  if mb not in cache]
        if needed:
            # bin codes depend only on (X, max_bins), so the dataset-level
            # plan and its stacked gathers are shared across tree families
            # — only missing max_bins pay the quantile sort + searchsorted
            plan = est.fold_sweep_plan(Xt, grid)
            if refit_state is not None:
                # retained for the winner refit: the SAME codes fit_arrays
                # would recompute from the identical full matrix, so the
                # refit's duplicate quantization pass is deleted bitwise
                refit_state["bin_plans"].update(plan)
        for mb in needed:
            # one stacked fold gather of the dataset-level codes per
            # max_bins — int8 when the codes fit (4x fewer gathered
            # bytes); training rows pad+shard 2-D over the mesh (rows
            # on "data", folds on "model" when they divide it);
            # validation codes stay unpadded — metrics must see real
            # rows only
            _, codes, _ = plan[mb]
            if int(mb) <= 127:
                codes = codes.astype(jnp.int8)
            cache[mb] = (pmesh.shard_stacked_training_rows(
                jnp.take(codes, jtr, axis=0),
                jnp.take(yt, jtr, axis=0),
                jnp.take(wt, jtr, axis=0))
                + (jnp.take(codes, jva, axis=0),))
        for gi, g in enumerate(tgroups):
            lanes = g["lanes"]
            L = len(lanes)
            depth = g["max_depth"]
            tk = self._treestack_key(ci, gi, k, n_tr, d, g)
            if tk in done and len(done[tk]) == k * L:
                # restart path: this depth-group already scored
                self._record_treestack(per_candidate_scores, ci, lanes,
                                       k, done[tk])
                continue
            if self._deadline_skip(ci, grid, deadline,
                                   per_candidate_scores, failures,
                                   pending, pop=True):
                return None
            Xb_tr, ytr_s, wtr_s, Xb_va = cache[g["max_bins"]]
            if "fold_means" not in cache:
                # the folds' label means feed the host-computed per-fold
                # base scores (bitwise parity with the loop's per-fold
                # ``_loss_and_nout``); ONE uncounted family-level pull
                # per sweep, shared across tree families — the analog of
                # the loop path's per-fold lnb sync
                cache["fold_means"] = np.asarray(jnp.stack(
                    [jnp.mean(ytr_s[f]) for f in range(k)]))
                _stamp_device(pending)
            chunks: list[tuple[int, int, Any]] = []  # device futures
            launched: list[tuple[float, dict]] = []  # their dispatch ends
            cs_cur = chunk_sizes[gi]  # the OOM ladder may narrow it
            from transmogrifai_tpu.utils.devicewatch import (
                compile_telemetry,
            )
            try:
                with compile_telemetry.building(
                        f"sweep.tree:{fname}", family=fname):
                    c0 = 0
                    while c0 < L:
                        chunk = g["params"][c0:c0 + cs_cur]
                        try:
                            with span("sweep.tree_group", family=fname,
                                      mode="tree_stacked", k=int(k),
                                      lanes=len(chunk), depth=int(depth),
                                      group=gi, nOut=n_out):
                                # fused unit: stacked train + stacked
                                # scores in one compiled program (no
                                # per-(fold, lane) model materialization
                                # — the sweep discards models; the
                                # winner refits)
                                scores = with_device_retry(
                                    est.tree_stack_scores, Xb_tr, ytr_s,
                                    wtr_s, Xb_va, chunk, lnb,
                                    fold_means=cache["fold_means"],
                                    site="sweep.fit")
                                # the chunk's [k, Lc] metric batch: a
                                # device FUTURE, settled once for the
                                # whole sweep
                                vals = fold_metrics_dev(
                                    yva_s, scores, self.validation_metric)
                        except Exception as oom_e:  # noqa: BLE001 — re-raised unless an OOM rung applies
                            from transmogrifai_tpu.utils.faults import (
                                FaultHarnessError,
                            )
                            if isinstance(oom_e, FaultHarnessError):
                                raise
                            if not self._oom_ladder(oom_e) or cs_cur <= 1:
                                raise
                            # degradation ladder: this chunk's k x Lc
                            # stacked program exceeded device memory —
                            # halve the lane-chunk width and retry the
                            # SAME lanes (per-lane values are
                            # vmap-independent: chunk width cannot change
                            # them), leaving every other group/chunk
                            # untouched
                            cs_cur = max(1, cs_cur // 2)
                            self._degrade(
                                "sweep.tree_group",
                                f"lane_chunk_{cs_cur}", error=oom_e,
                                family=fname, group=gi,
                                depth=int(depth), folds=int(k),
                                lanes=len(chunk))
                            continue
                        chunks.append((c0, len(chunk), vals))
                        launched.append((time.time(), {
                            "family": fname, "unitKind": "tree",
                            "depth": int(depth), "lanes": len(chunk),
                            "chunk": len(launched), "group": gi,
                            "nOut": n_out}))
                        sweep_counters.count(
                            fname, dispatches=1, lane_chunks=1,
                            mode="tree_stacked")
                        c0 += len(chunk)
                sweep_counters.count(fname, stacked_groups=1)
            except Exception as e:  # noqa: BLE001 — isolation by design
                from transmogrifai_tpu.utils.faults import FaultHarnessError
                if isinstance(e, FaultHarnessError):
                    raise  # a preempted process dies; it does not isolate
                for gj in range(len(grid)):
                    per_candidate_scores.pop((ci, gj), None)
                if self._oom_ladder(e):
                    # bottom of the stacked rungs: even one lane at a
                    # time OOMs — the whole family falls to the per-fold
                    # loop (peak 1/k). Drop the family's pending futures
                    # so the settle can't double-record it.
                    self._degrade("sweep.tree_group", "fold_loop",
                                  error=e, family=fname, group=gi,
                                  depth=int(depth))
                    pending[:] = [p for p in pending if p["ci"] != ci]
                    return "oom"
                failures.append({
                    "modelName": fname,
                    "reason": f"tree stacked sweep (group {gi}): "
                              f"{type(e).__name__}: {str(e)[:300]}"})
                return None
            if not any(p["ci"] == ci for p in pending):
                sweep_counters.count_run(async_families=1)
            pending.append({"kind": "tree", "ci": ci, "fname": fname,
                            "key": tk, "k": k, "lanes": lanes,
                            "chunks": chunks, "launched": launched})
        return None

    def _deadline_skip(self, ci, grid, deadline, per_candidate_scores,
                       failures, pending=(), *, pop: bool) -> bool:
        """True when the family must be skipped for exceeding the
        ``max_wait_s`` budget (reference maxWait) — never when it is the
        only family with any chance of scoring (a winner must survive):
        another family has a chance when it has recorded scores or
        dispatched programs whose metric futures wait on ``pending`` (if
        those all fail at the settle, ``_sweep`` runs the skipped families
        after all). ``pop`` drops the family's partial scores and its own pending
        futures (a partial-fold mean must not compete against full-fold
        means)."""
        if deadline is None or time.time() <= deadline:
            return False
        if not (any(kk[0] != ci for kk in per_candidate_scores)
                or any(p["ci"] != ci for p in pending)):
            return False
        if pop:
            for gj in range(len(grid)):
                per_candidate_scores.pop((ci, gj), None)
            if pending:
                pending[:] = [p for p in pending if p["ci"] != ci]
        failures.append({
            "modelName": self._family_name(ci),
            "reason": f"{_MAX_WAIT_SKIP}{self.max_wait_s}"})
        return True

    def _run_fold_unit(self, ci, est, grid, fold_i, Xtr, ytr, wtr, Xva, yva,
                       done, deadline, per_candidate_scores, failures,
                       fit_kwargs=None, pending=()) -> bool:
        """One (fold, family) train+score+record unit — the shared body of
        the stacked sweep's fallback loop and the legacy fold-major loop:
        checkpoint replay, the mid-family ``max_wait_s`` check (after
        replay — replaying is free and never skipped), failure isolation,
        counter bookkeeping. ``Xtr``/``ytr``/``wtr`` arrive mesh-sharded.
        Returns False when the family is dropped (failed or past budget) —
        the caller skips its remaining folds."""
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.retry import with_device_retry
        ev0 = self.evaluators[0]
        batch_metrics = getattr(ev0, "metric_batch_scores", None)
        fname = self._family_name(ci)
        ckey = f"{fold_i}:{ci}:{int(Xtr.shape[0])}x{int(Xtr.shape[1])}"
        if ckey in done and len(done[ckey]) == len(grid):
            # restart path: this (fold, family) batch already scored
            for gj, val in enumerate(done[ckey]):
                per_candidate_scores.setdefault((ci, gj), []).append(
                    float(val))
            return True
        if self._deadline_skip(ci, grid, deadline, per_candidate_scores,
                               failures, pending, pop=True):
            return False
        from transmogrifai_tpu.utils.tracing import span
        try:
            with compile_telemetry.building(
                        f"sweep.fold_unit:{fname}", family=fname), \
                    span("sweep.fold_unit", family=fname, fold=fold_i,
                         grid=len(grid)):
                models = with_device_retry(
                    est.grid_fit_arrays, Xtr, ytr, wtr, grid,
                    site="sweep.fit", **(fit_kwargs or {}))
                scores = (est.grid_predict_scores(models, Xva)
                          if batch_metrics is not None else None)
                if scores is not None:
                    # one device program scores + one computes the metric
                    # for the whole grid; a single host sync per
                    # (fold, family)
                    vals = [float(v) for v in batch_metrics(
                        yva, scores, self.validation_metric)]
                    sweep_counters.count(fname, dispatches=1,
                                         host_syncs=1, mode="fold_loop")
                    sweep_counters.count_run(host_syncs=1)
                else:
                    vals = []
                    for model in models:
                        pred = model.predict_arrays(Xva)
                        # summary-only metric: evaluators skip their
                        # deep report families inside the sweep
                        vals.append(ev0.metric_from_arrays(
                            yva, pred, self.validation_metric))
                    sweep_counters.count(fname, dispatches=1,
                                         host_syncs=max(len(grid), 1),
                                         mode="fold_loop")
                    sweep_counters.count_run(
                        host_syncs=max(len(grid), 1))
        except Exception as e:  # noqa: BLE001 — isolation by design
            from transmogrifai_tpu.utils.faults import FaultHarnessError
            if isinstance(e, FaultHarnessError):
                raise  # a preempted process dies; it does not isolate
            for gj in range(len(grid)):
                per_candidate_scores.pop((ci, gj), None)
            failures.append({
                "modelName": fname,
                "reason": f"fold {fold_i}: {type(e).__name__}: "
                          f"{str(e)[:300]}"})
            return False
        # bookkeeping outside the isolation try: a checkpoint I/O problem
        # must not convert a successful fit into a candidate failure
        # (_ckpt_save is best-effort anyway)
        for gj, val in enumerate(vals):
            per_candidate_scores.setdefault((ci, gj), []).append(val)
        done[ckey] = vals
        self._ckpt_save(done)
        return True

    def _family_fold_loop(self, ci, est, grid, Xt, yt, wt, tr_idx, va_idx,
                          done, deadline, per_candidate_scores,
                          failures, refit_state=None, pending=()) -> None:
        """One family's sequential per-fold sweep (the fallback path and
        the home of families without a fold axis — tree ensembles, custom
        subclasses). Tree families still avoid re-binning every fold: a
        ``fold_sweep_plan`` computes dataset-level quantile codes once and
        each fold gathers its rows from them (and the winner refit reuses
        the same codes via ``refit_state``)."""
        import inspect
        from transmogrifai_tpu.parallel import mesh as pmesh
        plan = None
        plan_fn = getattr(est, "fold_sweep_plan", None)
        if (plan_fn is not None and pmesh.current_mesh() is None
                and "_fold_plan" in inspect.signature(
                    est.grid_fit_arrays).parameters):
            plan = plan_fn(Xt, grid)
            if refit_state is not None:
                refit_state["bin_plans"].update(plan)
        for fold_i in range(tr_idx.shape[0]):
            jtr = jnp.asarray(tr_idx[fold_i])
            jva = jnp.asarray(va_idx[fold_i])
            # row-parallel training over the mesh: fold rows padded to the
            # data-axis multiple with weight 0 (validation stays unpadded —
            # metrics must see real rows only)
            Xtr, ytr, wtr = pmesh.shard_training_rows(
                Xt[jtr], yt[jtr], wt[jtr])
            fit_kwargs = ({"_fold_plan": plan, "_fold_rows": jtr}
                          if plan is not None else None)
            if not self._run_fold_unit(
                    ci, est, grid, fold_i, Xtr, ytr, wtr, Xt[jva], yt[jva],
                    done, deadline, per_candidate_scores, failures,
                    fit_kwargs=fit_kwargs, pending=pending):
                return

    def _fold_arrays_iter(self, Xt, yt, wt, yt_np):
        for tr, va in self.validator.splits(int(Xt.shape[0]), yt_np):
            jtr, jva = jnp.asarray(tr), jnp.asarray(va)
            yield Xt[jtr], yt[jtr], wt[jtr], Xt[jva], yt[jva]

    def _sweep_loop(self, fold_arrays) -> tuple[list[ModelEvaluation],
                                                list[tuple[float, int, int]],
                                                list[dict]]:
        """Fold-major sequential sweep over materialized fold arrays — the
        legacy path, kept for workflow-level CV (``fit_with_dag`` refits
        feature stages per fold, so fold features differ and cannot stack)
        and for validators without equal fold shapes. Per-(fold, family)
        semantics live in the shared ``_run_fold_unit``."""
        from transmogrifai_tpu.parallel import mesh as pmesh
        per_candidate_scores: dict[tuple[int, int], list[float]] = {}
        failures: list[dict] = []
        failed_families: set[int] = set()
        deadline = (time.time() + self.max_wait_s
                    if self.max_wait_s is not None else None)
        done = self._ckpt_load()
        for fold_i, (Xtr, ytr, wtr, Xva, yva) in enumerate(fold_arrays):
            Xtr, ytr, wtr = pmesh.shard_training_rows(Xtr, ytr, wtr)
            for ci, (est, grid) in enumerate(self.models_and_grids):
                if ci in failed_families:
                    continue
                if not self._run_fold_unit(
                        ci, est, grid, fold_i, Xtr, ytr, wtr, Xva, yva,
                        done, deadline, per_candidate_scores, failures):
                    failed_families.add(ci)
        return self._collect_results(per_candidate_scores, failures)

    def _collect_results(self, per_candidate_scores, failures
                         ) -> tuple[list[ModelEvaluation],
                                    list[tuple[float, int, int]],
                                    list[dict]]:
        results: list[ModelEvaluation] = []
        mean_metrics: list[tuple[float, int, int]] = []
        for (ci, gj), vals in per_candidate_scores.items():
            est, grid = self.models_and_grids[ci]
            mean = float(np.mean(vals))
            name = f"{type(est).__name__}_{ci}_{gj}"
            results.append(ModelEvaluation(
                model_name=name,
                model_uid=est.uid,
                model_type=type(est).__name__,
                params={**est.params, **grid[gj]},
                metric_values={self.validation_metric: mean}))
            if np.isfinite(mean):
                mean_metrics.append((mean, ci, gj))
            else:
                failures.append({
                    "modelName": name,
                    "reason": "non-finite validation metric (diverged fit)"})
        if not mean_metrics:
            raise RuntimeError(
                "ModelSelector: every candidate failed or diverged; "
                f"failures: {failures}")
        return results, mean_metrics, failures

    # -- winner refit (round 9) ----------------------------------------------
    def _refit_ckpt_paths(self) -> Optional[tuple[str, str]]:
        """(json path, npz path) of the refit checkpoint, or None when
        checkpointing is off/unusable."""
        if not self.checkpoint_dir:
            return None
        import os

        from transmogrifai_tpu.utils.durable import ensure_checkpoint_dir
        if not ensure_checkpoint_dir(self.checkpoint_dir,
                                     "refit checkpoint"):
            return None
        return (os.path.join(self.checkpoint_dir, "refit.json"),
                os.path.join(self.checkpoint_dir, "refit.npz"))

    def _refit_ckpt_save(self, rkey: str, model) -> None:
        """Persist the refitted winner (best-effort, atomic): a run
        preempted AFTER the refit but before/while evaluating resumes
        without retraining the winner. Keyed on the sweep-config
        fingerprint plus a shape-carrying refit key (``{ci}:{gj}:refit:
        {n}x{d}``) — same staleness rules as ``sweep.json``."""
        paths = self._refit_ckpt_paths()
        if paths is None:
            return
        from transmogrifai_tpu.serialization import fitted_stage_record
        from transmogrifai_tpu.utils.durable import (
            atomic_json_dump, best_effort_checkpoint_write,
        )

        def write() -> None:
            rec, arrays = fitted_stage_record(model)
            import os
            import tempfile
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(paths[1]),
                                       suffix=".npz.tmp")
            try:
                # a file OBJECT: np.savez appends ".npz" to bare paths,
                # which would leave the mkstemp file empty
                with os.fdopen(fd, "wb") as fh:
                    np.savez(fh, **arrays)
                os.replace(tmp, paths[1])
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)  # failure-ok: leftover tmp cleanup
            atomic_json_dump({"fingerprint": self._ckpt_fingerprint(),
                              "key": rkey, "record": rec}, paths[0])

        best_effort_checkpoint_write(
            write, "refit checkpoint write failed; continuing without it")

    def _refit_ckpt_load(self, rkey: str):
        """The checkpointed refit winner when fingerprint AND refit key
        match, else None (stale/missing/corrupt files cost a fresh refit,
        never a crash)."""
        paths = self._refit_ckpt_paths()
        if paths is None:
            return None
        import json
        import os
        if not (os.path.exists(paths[0]) and os.path.exists(paths[1])):
            return None
        try:
            with open(paths[0]) as fh:
                doc = json.load(fh)
            if doc.get("fingerprint") != self._ckpt_fingerprint() \
                    or doc.get("key") != rkey:
                return None
            from transmogrifai_tpu.serialization import restore_fitted_stage
            with np.load(paths[1], allow_pickle=False) as npz:
                arrays = {k: npz[k] for k in npz.files}
            return restore_fitted_stage(doc["record"], arrays)
        except Exception as e:  # noqa: BLE001 — corrupt ckpt costs a refit
            import warnings
            warnings.warn(
                f"refit checkpoint: unreadable state at {paths[0]!r} "
                f"({type(e).__name__}: {e}); refitting the winner fresh",
                RuntimeWarning)
            return None

    def _refit(self, best_ci: int, best_gj: int, best_params: dict, Xt,
               yt, wt, refit_state: dict):
        """Train the winner on the full prepared data through the stacked
        refit machinery (round 9): resume from the refit checkpoint when
        one matches; otherwise hand the family its retained warm-start
        handle (the sweep's stacked fold parameters, G=1 lane selected by
        ``best_gj``) and the dataset-level tree bin plans via
        ``refit_winner``. Families without reuse run the exact cold
        ``fit_arrays`` the serial path always ran (bitwise). The
        ``selector.refit`` fault site fires after the checkpoint write —
        the preemption seam the chaos suite resumes across."""
        import contextlib

        from transmogrifai_tpu.parallel import mesh as pmesh
        from transmogrifai_tpu.utils.faults import fault_point
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.retry import with_device_retry
        from transmogrifai_tpu.utils.tracing import span
        best_est = self.models_and_grids[best_ci][0]
        fname = self._family_name(best_ci)
        n, d = int(Xt.shape[0]), int(Xt.shape[1])
        rkey = f"{best_ci}:{best_gj}:refit:{n}x{d}"
        restored = self._refit_ckpt_load(rkey)
        if restored is not None:
            fault_point("selector.refit")
            return restored
        Xs, ys, ws = pmesh.shard_training_rows(Xt, yt, wt)
        warm = refit_state.get("warm", {}).get(best_ci)
        hints = {}
        bin_plans = refit_state.get("bin_plans")
        if bin_plans and int(Xs.shape[0]) == n:
            # mesh padding grows the refit rows past the dataset-level
            # codes; the reuse only holds row-for-row
            hints["bin_plans"] = bin_plans
        stacked_refit = warm is not None or bool(hints)
        cm = (span("selector.refit_stacked", family=fname, lane=best_gj,
                   warm=warm is not None)
              if stacked_refit else contextlib.nullcontext())
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        try:
            with compile_telemetry.building(
                    f"selector.refit:{fname}", family=fname), cm:
                best_model, warm_used = with_device_retry(
                    best_est.refit_winner, Xs, ys, ws, best_params,
                    warm=warm, lane=best_gj, hints=hints or None,
                    site="sweep.fit")
        except Exception as e:  # noqa: BLE001 — re-raised unless an OOM rung applies
            from transmogrifai_tpu.utils.faults import FaultHarnessError
            if isinstance(e, FaultHarnessError) or warm is None \
                    or not self._oom_ladder(e):
                raise
            # degradation ladder: the warm-started refit holds the
            # retained stacked fold parameters live alongside the
            # full-data program's peak — release them and refit COLD
            # (bitwise the pre-round-9 serial refit) instead of dying
            self._degrade("selector.refit", "cold_refit", error=e,
                          family=fname, lane=int(best_gj),
                          rows=int(n), cols=int(d))
            warm = None
            refit_state.get("warm", {}).pop(best_ci, None)
            with compile_telemetry.building(
                    f"selector.refit:{fname}", family=fname):
                best_model, warm_used = with_device_retry(
                    best_est.refit_winner, Xs, ys, ws, best_params,
                    warm=None, lane=best_gj, hints=hints or None,
                    site="sweep.fit")
        if warm_used:
            sweep_counters.count_run(refit_warm_starts=1)
        refit_state["dispatched"] = (time.time(), fname)  # for _stamp_refit
        self._refit_ckpt_save(rkey, best_model)
        fault_point("selector.refit")
        return best_model

    @staticmethod
    def _stamp_refit(best_model, dispatched) -> None:
        """Stamp the winner refit's device interval as a ``refit.device``
        span: from the refit program's dispatch end to its parameters being
        ready. Called once the train-set predict is enqueued behind it and
        just before the evaluation pulls (which wait on both anyway), so
        the wait moves nothing; ``exact=False`` when the refit had finished
        before the walk got here (the predict program's compile overlaps
        it). ``dispatched`` is ``_refit``'s ``(dispatch end, family)``,
        or None when the winner came from the refit checkpoint."""
        import jax
        from transmogrifai_tpu.utils.tracing import recorder
        if dispatched is None:
            return
        t_dispatch, fname = dispatched
        leaves = [a for a in jax.tree_util.tree_leaves(
            best_model.device_params()) if isinstance(a, jax.Array)]
        was_ready = all(_is_ready(a) for a in leaves)
        jax.block_until_ready(leaves)
        recorder.add("refit.device", t_dispatch, time.time(), family=fname,
                     exact=not was_ready, device_window=True)

    def _finalize(self, results, mean_metrics, Xt, yt, wt, Xh, yh,
                  prep_results: dict, t0: float,
                  failures: Optional[list] = None,
                  refit_state: Optional[dict] = None) -> SelectedModel:
        """Refit the winning candidate on the full prepared training data,
        evaluate train + holdout, assemble the summary."""
        ev0 = self.evaluators[0]
        bigger = ev0.larger_is_better(self.validation_metric)
        _, best_ci, best_gj = (max if bigger else min)(
            mean_metrics, key=lambda t: t[0])
        best_est, best_grid = self.models_and_grids[best_ci]
        best_params = {**best_est.params, **best_grid[best_gj]}
        warm_all = (refit_state or {}).get("warm")
        if warm_all:
            # only the winner's handle is ever read — release the losing
            # families' stacked fold parameters before the full-data refit
            # program peaks HBM
            for ci in [c for c in warm_all if c != best_ci]:
                del warm_all[ci]
        refit_state = refit_state or {}
        best_model = self._refit(best_ci, best_gj, best_params, Xt, yt, wt,
                                 refit_state)

        train_eval: dict = {}
        holdout_eval: dict = {}
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        pred_train = best_model.predict_arrays(Xt)
        self._stamp_refit(best_model, refit_state.get("dispatched"))
        for ev in self.evaluators:
            with compile_telemetry.building(
                    f"evaluate:{type(ev).__name__}"):
                train_eval[ev.name] = EvaluatorBase.to_json(
                    ev.evaluate_arrays(yt, pred_train))
        if Xh is not None and int(Xh.shape[0]):
            pred_h = best_model.predict_arrays(Xh)
            for ev in self.evaluators:
                with compile_telemetry.building(
                        f"evaluate:{type(ev).__name__}"):
                    holdout_eval[ev.name] = EvaluatorBase.to_json(
                        ev.evaluate_arrays(yh, pred_h))

        summary = ModelSelectorSummary(
            validation_type=self.validator.name,
            validation_metric=self.validation_metric,
            best_model_uid=best_est.uid,
            best_model_name=f"{type(best_est).__name__}_{best_ci}_{best_gj}",
            best_model_type=type(best_est).__name__,
            best_params=best_params,
            validation_results=results,
            train_evaluation=train_eval,
            holdout_evaluation=holdout_eval,
            data_prep_results=prep_results,
            wall_time_s=time.time() - t0,
            failures=list(failures or []),
        )
        return SelectedModel(model=best_model, summary=summary)

    def fit_model(self, data) -> SelectedModel:
        from transmogrifai_tpu.dag import _plog
        from transmogrifai_tpu.utils.profiling import OpStep, profiler
        from transmogrifai_tpu.utils.tracing import span as _span
        t0 = time.time()
        label_name, feat_name = self.input_names
        # the ingest->sweep handoff (round 14): with fused FE the feature
        # matrix is already an HBM-resident, rows-on-"data"-sharded device
        # column — the sweep consumes it pre-partitioned, no host pull and
        # no resharding device_put. `presharded` makes that assertable.
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        presharded = feat_name in data.device
        with compile_telemetry.building("selector.prepare"):
            with _span("sweep.operands", presharded=presharded,
                       feature=feat_name):
                X = data.device_col(feat_name).values
                y = data.device_col(label_name).values
            n = data.n_rows  # logical rows: device arrays may carry padding

            train_idx, holdout_idx, w_train, prep_results = \
                self._split_prepare(n, y[:n])
            Xt, yt = _take_rows(X, y, jnp.asarray(train_idx))
            wt = jnp.asarray(w_train)
        _plog("selector: split+prepare", t0)

        yt_np = (np.asarray(yt)
                 if getattr(self.validator, "stratify", False) else None)
        t1 = time.time()

        from transmogrifai_tpu.utils.tracing import span
        with profiler.phase(OpStep.CROSS_VALIDATION), \
                span("selector.sweep", hbm=True, stage_uid=self.uid,
                     stage_cls=type(self).__name__, phase="sweep",
                     n_families=len(self.models_and_grids),
                     device_window=True):
            results, mean_metrics, failures, refit_state = \
                self._sweep(Xt, yt, wt, yt_np)
        _plog("selector: CV sweep", t1)
        t1 = time.time()
        with compile_telemetry.building("selector.prepare"):
            Xh, yh = (_take_rows(X, y, jnp.asarray(holdout_idx))
                      if holdout_idx.size else (None, None))
        with profiler.phase(OpStep.MODEL_TRAINING), \
                span("selector.refit", hbm=True, stage_uid=self.uid,
                     stage_cls=type(self).__name__, phase="refit",
                     device_window=True):
            selected = self._finalize(results, mean_metrics, Xt, yt, wt,
                                      Xh, yh, prep_results, t0, failures,
                                      refit_state=refit_state)
        _plog("selector: refit+evaluate", t1)
        return selected

    def fit_with_dag(self, data, during_dag, executor):
        """Leakage-free workflow-level CV (reference ``OpWorkflow.
        withWorkflowCV`` + ``ModelSelector.findBestEstimator`` over the in-CV
        DAG): the label-dependent feature stages in ``during_dag`` are refit
        inside every fold on that fold's training rows only, then the
        candidate sweep runs on the fold-local features.

        Returns ``(selected_model, fitted_during_dag, transformed_data)``
        where ``fitted_during_dag`` was refit on the full prepared training
        rows and ``transformed_data`` is the input data pushed through it
        (all rows, holdout included).
        """
        t0 = time.time()
        label_name, feat_name = self.input_names
        y = data.device_col(label_name).values
        n = data.n_rows  # logical rows: device arrays may carry mesh padding

        train_idx, holdout_idx, w_train, prep_results = \
            self._split_prepare(n, y[:n])
        data_train = data.take(train_idx)
        wt_full = jnp.asarray(w_train)
        yt_np = (np.asarray(y)[train_idx]
                 if getattr(self.validator, "stratify", False) else None)

        def fold_arrays():
            for tr, va in self.validator.splits(train_idx.size, yt_np):
                d_tr = data_train.take(tr)
                d_va = data_train.take(va)
                # scratch executor per fold: the fold's fitted models carry
                # fold-specific static config (vocabs, splits), so their
                # compiled programs must not accumulate in the workflow's
                # long-lived executor cache
                fold_ex = type(executor)()
                d_tr2, fitted = fold_ex.fit_transform(d_tr, during_dag)
                d_va2 = fold_ex.transform(d_va, fitted)
                # validation slices back to logical rows: take() re-pads
                # device columns under a mesh, and metrics must see real
                # rows only (training padding is weight-masked instead)
                n_va = d_va2.n_rows
                yield (d_tr2.device_col(feat_name).values,
                       d_tr2.device_col(label_name).values,
                       wt_full[jnp.asarray(tr)],
                       d_va2.device_col(feat_name).values[:n_va],
                       d_va2.device_col(label_name).values[:n_va])

        # the in-CV DAG refits per fold, so fold features differ and cannot
        # stack: workflow-level CV keeps the fold-major loop
        results, mean_metrics, failures = self._sweep_loop(fold_arrays())

        # refit the in-CV feature DAG on the full prepared training rows,
        # then push ALL rows (train + holdout) through it for downstream use
        _, fitted_during = executor.fit_transform(data_train, during_dag)
        full_data = executor.transform(data, fitted_during)
        X = full_data.device_col(feat_name).values
        y_full = full_data.device_col(label_name).values
        Xt = X[jnp.asarray(train_idx)]
        yt = y_full[jnp.asarray(train_idx)]
        Xh = X[jnp.asarray(holdout_idx)] if holdout_idx.size else None
        yh = y_full[jnp.asarray(holdout_idx)] if holdout_idx.size else None
        selected = self._finalize(results, mean_metrics, Xt, yt, wt_full,
                                  Xh, yh, prep_results, t0, failures)
        selected._inputs = self._inputs
        selected._output = self.get_output()
        return selected, fitted_during, full_data
