"""Workflow: DAG assembly, training, scoring.

Parity: reference ``core/src/main/scala/com/salesforce/op/{OpWorkflow,
OpWorkflowCore,OpWorkflowModel}.scala`` — ``set_result_features`` back-traces
lineage; ``train()`` generates raw data through the reader, fits the leveled
DAG, and returns a ``WorkflowModel`` whose ``score()`` replays the fitted
transformer DAG (layer-fused jit programs), ``evaluate()`` runs evaluators,
``save()``/``load_model()`` round-trip the fitted pipeline, and
``score_function()`` compiles the Spark-free local scoring closure
(reference ``local/OpWorkflowModelLocal``).
"""

from __future__ import annotations

import functools

import json
import os
from typing import Any, Callable, Iterable, Optional, Sequence

import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.dag import Dag, DagExecutor, compute_dag
from transmogrifai_tpu.features.feature import FeatureLike
from transmogrifai_tpu.pipeline_data import PipelineData
from transmogrifai_tpu.readers.base import CustomReader, DataReader
from transmogrifai_tpu.selector.model_selector import SelectedModel
from transmogrifai_tpu.types import feature_types as ft

__all__ = ["Workflow", "WorkflowModel", "load_model"]


class Workflow:
    def __init__(self):
        self.reader: Optional[DataReader] = None
        self.result_features: tuple[FeatureLike, ...] = ()
        self._raw_feature_filter = None
        self._workflow_cv = False
        self._model_stage_overrides: dict[str, Any] = {}
        #: fingerprint-keyed device-frame cache (round 14): repeated
        #: train() over identical host columns — and the trained model's
        #: first score over the training frame — reuse the HBM-resident
        #: columns instead of re-transferring. Shared into WorkflowModel.
        from transmogrifai_tpu.ingest_fusion import DeviceFrameCache
        self._frame_cache = DeviceFrameCache()

    def with_workflow_cv(self, enabled: bool = True) -> "Workflow":
        """Leakage-free workflow-level CV (reference ``withWorkflowCV``):
        label-dependent feature stages feeding the ModelSelector are refit
        inside each CV fold instead of once on the full training data."""
        self._workflow_cv = enabled
        return self

    # -- inputs --------------------------------------------------------------
    def set_reader(self, reader: DataReader) -> "Workflow":
        self.reader = reader
        return self

    def set_input_frame(self, frame: fr.HostFrame) -> "Workflow":
        self.reader = CustomReader(frame=frame)
        return self

    def set_input_records(self, records: Iterable[Any],
                          key_fn: Optional[Callable] = None) -> "Workflow":
        self.reader = CustomReader(records=records, key_fn=key_fn)
        return self

    def set_result_features(self, *features: FeatureLike) -> "Workflow":
        if not features:
            raise ValueError("need at least one result feature")
        self.result_features = tuple(features)
        return self

    def with_raw_feature_filter(self, rff) -> "Workflow":
        """Attach a RawFeatureFilter applied before training (blocklisting
        low-quality raw features and rewiring the DAG)."""
        self._raw_feature_filter = rff
        return self

    def with_model_stages(self, model: "WorkflowModel") -> "Workflow":
        """Resume training with already-fitted stages (reference
        ``OpWorkflow.withModelStages:468-472``): any stage in this
        workflow's DAG whose output feature matches one fitted in ``model``
        is reused as-is instead of refit."""
        for layer in model.dag:
            for t in layer:
                out = t.get_output()
                if out is not None:
                    self._model_stage_overrides[out.uid] = t
        return self

    def _substitute_fitted(self, dag: Dag,
                           extra: Optional[dict] = None) -> Dag:
        """Replace stages whose output feature is already fitted — by
        ``with_model_stages`` or (``extra``) a restored train checkpoint —
        the replay seam resumable training grafts onto. An explicit
        ``with_model_stages`` override WINS over a checkpoint restore: the
        user handed us a newer fitted stage on purpose; the on-disk copy
        may be stale."""
        overrides = self._model_stage_overrides
        if extra:
            overrides = {**extra, **overrides}
        if not overrides:
            return dag
        return [[overrides.get(s.get_output().uid, s)
                 for s in layer] for layer in dag]

    def validate(self, sample_frame: Optional[fr.HostFrame] = None) -> dict:
        """Pre-train stage validation — the TPU analog of the reference's
        ``checkSerializable`` + ``checkCtorUIDs`` (``OpWorkflow.scala:
        280-324``): where the reference verifies closures can ship to Spark
        executors, the compiled-program equivalent verifies each stage (a)
        has distinct uids and wired inputs (raised inside ``compute_dag``),
        (b) can serialize (``config``/``fitted_state`` don't raise — a saved
        model will round-trip), and (c) for device transformers, TRACES
        under abstract shapes (``jax.eval_shape`` on a sample frame): a
        stage with data-dependent Python control flow fails here with its
        uid named, instead of deep inside a fused layer compile.

        Returns {"unserializable": {uid: reason}, "untraceable":
        {uid: reason}, "layer_failures": [reason]} — a layer that cannot
        even APPLY on the sample is itself a finding (and stops deeper
        tracing). Raises only on structural problems (duplicate uids).
        Training is NOT blocked by findings — saving a model with
        unserializable stages raises at save time, as always.
        """
        from transmogrifai_tpu.stages.base import (
            DeviceTransformer, Estimator,
        )
        report: dict = {"unserializable": {}, "untraceable": {},
                        "layer_failures": []}
        dag = self._substitute_fitted(compute_dag(self.result_features))
        stages = [s for layer in dag for s in layer]
        for s in stages:
            try:
                s.config()
                if hasattr(s, "fitted_state"):
                    s.fitted_state()
            except Exception as e:  # noqa: BLE001 — report, don't raise
                report["unserializable"][s.uid] = (
                    f"{type(s).__name__}: {e}")
        if sample_frame is not None:
            import jax
            data = PipelineData.from_host(sample_frame)
            for layer in dag:
                fitted = []
                for s in layer:
                    if isinstance(s, Estimator):
                        try:
                            s = s.fit(data)
                        except Exception as e:  # noqa: BLE001 — recorded in the report
                            report["untraceable"][s.uid] = (
                                f"{type(s).__name__} fit on sample: {e}")
                            continue
                    fitted.append(s)
                for t in fitted:
                    if not isinstance(t, DeviceTransformer):
                        continue
                    try:
                        cols = [data.device_col(n)
                                for n in t.runtime_input_names()]
                        params = t.device_params()
                        jax.eval_shape(
                            lambda p, c, _t=t: _t.device_apply(p, *c),
                            params, cols)
                    except Exception as e:  # noqa: BLE001 — recorded in the report
                        report["untraceable"][t.uid] = (
                            f"{type(t).__name__}: {e}")
                try:
                    data = DagExecutor().apply_layer(data, fitted)
                except Exception as e:  # noqa: BLE001 — recorded; stops below
                    # a silently-clean report for a workflow that cannot
                    # run would be a false all-clear: record + stop (the
                    # downstream layers lack inputs now)
                    report["layer_failures"].append(
                        f"layer [{', '.join(t.uid for t in fitted)}] "
                        f"failed to apply on the sample: "
                        f"{type(e).__name__}: {e}")
                    break
        return report

    def compute_data_up_to(self, feature: FeatureLike) -> fr.HostFrame:
        """Materialize the data with all transformations applied up to (and
        including) ``feature`` (reference ``OpWorkflow.computeDataUpTo``) —
        fitting whatever estimators the path needs. Returns every feature
        generated along the way (raws + intermediates + the target)."""
        if self.reader is None:
            raise ValueError("set a reader or input frame first")
        raw = [f for f in feature.raw_features()] or [feature]
        frame = self.reader.generate_frame(raw)
        data = PipelineData.from_host(frame)
        dag = self._substitute_fitted(compute_dag([feature]))
        data, _ = DagExecutor().fit_transform(data, dag)
        return _frame_up_to(data, raw, dag)

    # -- lineage -------------------------------------------------------------
    def raw_features(self) -> list[FeatureLike]:
        seen: dict[str, FeatureLike] = {}
        for f in self.result_features:
            for r in f.raw_features():
                seen.setdefault(r.uid, r)
        return sorted(seen.values(), key=lambda f: f.name)

    # -- train ---------------------------------------------------------------
    def train(self, checkpoint_dir: Optional[str] = None) -> "WorkflowModel":
        """Fit the workflow. With ``checkpoint_dir``, training is RESUMABLE:
        each fitted DAG layer persists as it completes (``checkpoint.
        TrainCheckpoint``) and any unconfigured ModelSelector checkpoints
        its sweep into the same directory — after a crash or preemption,
        calling ``train`` again with the same directory replays completed
        layers (and completed sweep units) from disk instead of refitting.
        See docs/ROBUSTNESS.md."""
        if self.reader is None:
            raise ValueError("set a reader or input frame before train()")
        if not self.result_features:
            raise ValueError("set result features before train()")
        from transmogrifai_tpu.utils.profiling import OpStep, profiler
        from transmogrifai_tpu.utils.tracing import now, recorder, span
        raw = self.raw_features()
        filter_results = None
        with profiler.phase(OpStep.DATA_READING_AND_FILTERING), \
                span("workflow.ingest", reader=type(self.reader).__name__,
                     n_raw=len(raw)):
            frame = self.reader.generate_frame(raw)
            blocklist: list[str] = []
            result = self.result_features
            if self._raw_feature_filter is not None:
                frame, blocklist = self._raw_feature_filter.filter_frame(
                    frame, raw)
                filter_results = self._raw_feature_filter.results
                if blocklist:
                    result = _apply_blocklist(result, set(blocklist))
                    if not result:
                        raise ValueError(
                            "RawFeatureFilter blocked every path to the "
                            f"result features (blocklist: {blocklist})")
                    raw = [f for f in raw if f.name not in set(blocklist)]
            # ALWAYS replace workflow-applied per-key map exclusions —
            # a filterless retrain must clear a previous filtered run's
            # exclusions, not silently keep dropping healthy keys
            self._apply_map_key_blocklist(
                result, filter_results.map_key_blocklist
                if filter_results is not None else {})
        data = PipelineData.from_host(frame)
        from transmogrifai_tpu.ingest_fusion import frame_cache_enabled
        if frame_cache_enabled():
            data = self._frame_cache.adopt(frame, data)
        executor = DagExecutor()
        ckpt = None
        ckpt_overrides: dict[str, Any] = {}
        t_plan = now()
        full_dag = compute_dag(result)
        if checkpoint_dir:
            from transmogrifai_tpu.checkpoint import (
                TrainCheckpoint, train_fingerprint,
            )
            from transmogrifai_tpu.selector.model_selector import (
                ModelSelector,
            )
            ckpt = TrainCheckpoint(
                checkpoint_dir,
                train_fingerprint(full_dag, frame.n_rows,
                                  [f.name for f in raw]))
            ckpt_overrides = ckpt.restore_overrides(full_dag)
            # compose with the sweep checkpoint: a mid-CV crash resumes
            # both the fitted before-DAG layers AND the partially-done
            # sweep from the same directory. Patched selectors are
            # restored after training — the directory belongs to THIS
            # train call, not to the selector (a later train() with a
            # different/no checkpoint_dir must not keep using it)
            patched_selectors = [
                s for layer in full_dag for s in layer
                if isinstance(s, ModelSelector) and s.checkpoint_dir is None]
            for s in patched_selectors:
                s.checkpoint_dir = checkpoint_dir
        else:
            patched_selectors = []
        cut = None
        if self._workflow_cv:
            from transmogrifai_tpu.dag import cut_dag
            cut = cut_dag(result)
            if cut.selector is None or not cut.during:
                cut = None  # nothing label-dependent to protect: plain fit
            elif cut.selector.get_output().uid in {
                    **self._model_stage_overrides, **ckpt_overrides}:
                # the selector itself is already fitted (with_model_stages
                # or a train checkpoint): nothing to sweep, the plain path
                # reuses it as-is
                cut = None
        try:
            if cut is not None:
                # the selector was NOT restored, so CV will actually run:
                # checkpoint-restored during-DAG stages must NOT be
                # substituted — they were fitted on the FULL training data
                # (saved after a completed sweep), and replaying them here
                # would disable the per-fold refit that keeps label
                # information out of fold validation features. They refit
                # per fold as CV requires; the checkpoint entries only
                # replay once the selector itself is restored (cut=None).
                during_uids = {s.get_output().uid
                               for layer in cut.during for s in layer}
                cv_overrides = {k: v for k, v in ckpt_overrides.items()
                                if k not in during_uids}
                cut.before = self._substitute_fitted(cut.before,
                                                     cv_overrides)
                cut.during = self._substitute_fitted(cut.during,
                                                     cv_overrides)
                cut.after = self._substitute_fitted(cut.after,
                                                    cv_overrides)
                recorder.add("workflow.plan", t_plan, now(),
                             layers=len(full_dag), workflowCv=True)
                fitted = self._fit_workflow_cv(data, cut, executor, ckpt)
            else:
                dag = self._substitute_fitted(full_dag, ckpt_overrides)
                recorder.add("workflow.plan", t_plan, now(),
                             layers=len(full_dag), workflowCv=False)
                with profiler.phase(OpStep.FEATURE_ENGINEERING):
                    _, fitted = self._fit_layers(
                        executor, data, dag, ckpt,
                        keep={f.name for f in result})
        finally:
            for s in patched_selectors:
                s.checkpoint_dir = None
        with span("workflow.model", rows=frame.n_rows):
            model = WorkflowModel(
                result_features=result,
                raw_features=raw, dag=fitted, executor=executor,
                blocklisted=blocklist,
                label_distribution=_label_distribution(frame, raw),
                raw_filter_results=filter_results)
        # the model scores through the same device-frame cache: a
        # train-then-score session over the training frame (holdout
        # evaluation, insights) never re-uploads identical host columns
        model._frame_cache = self._frame_cache
        return model

    @staticmethod
    def _apply_map_key_blocklist(result, map_key_blocklist: dict) -> None:
        """Reference ``OpWorkflow.scala:118-167`` setBlocklist per-key map
        exclusions: rewire every map vectorizer consuming a flagged map
        feature so the excluded keys never expand into columns.

        Workflow-applied exclusions are REPLACED per train(), never
        accumulated: they live in the stage's separate
        ``wf_block_keys_by_feature`` dict (consulted alongside the
        user-owned ``block_keys_by_feature``, which is never touched), so
        keys that are healthy again on refreshed data come back while user
        config — including edits between trains — is always preserved."""
        from transmogrifai_tpu.ops.vectorizers.maps import _MapVectorizerBase
        stages = {s for f in result for s in f.parent_stages()}
        for stage in stages:
            if not isinstance(stage, _MapVectorizerBase):
                continue
            stage.wf_block_keys_by_feature = {
                name: tuple(sorted(map_key_blocklist[name]))
                for name in stage.input_names
                if map_key_blocklist.get(name)}

    @staticmethod
    def _fit_layers(executor: DagExecutor, data: PipelineData, dag: Dag,
                    ckpt=None, layer_offset: int = 0,
                    keep: Optional[set] = None
                    ) -> tuple[PipelineData, Dag]:
        """Layer-at-a-time ``fit_transform`` with resume accounting and
        per-layer checkpointing. A layer whose estimators were all replaced
        by checkpoint-restored models counts as resumed (replayed, not
        refit); every other completed layer is fitted and — when a
        checkpoint is active — persisted before the next layer starts, so
        a crash loses at most the in-flight layer. ``fault_point
        ("train.layer")`` fires at each layer start: the deterministic
        preemption site the chaos suite kills training at.

        Note on FE fusion (round 14): this loop deliberately feeds
        ``fit_transform`` ONE layer at a time — the per-layer fault-point
        and checkpoint granularity is the chaos/resume contract — so
        cross-layer fusion here is bounded to within a layer. The
        multi-layer fused programs fire where whole fitted DAGs replay:
        ``executor.transform`` (scoring, CV validation transforms) and
        the selector's per-fold during-DAG ``fit_transform`` over the
        full multi-layer cut (``fit_with_dag``).

        With ``keep`` (the names the caller still reads afterwards) a
        column that a stage of this DAG produced is let go as soon as no
        later layer reads it: the blocks a wide vector was combined from,
        then the un-checked vector once the checked one exists."""
        from transmogrifai_tpu.stages.base import Estimator
        from transmogrifai_tpu.utils.faults import fault_point
        from transmogrifai_tpu.utils.profiling import run_counters
        from transmogrifai_tpu.utils.tracing import span
        fitted_dag: Dag = []
        read_later: list[set] = []   # names layers after li still read
        if keep is not None:
            needed = set(keep)
            for layer in reversed(dag):
                read_later.append(set(needed))
                for s in layer:
                    needed.update(s.input_names)
            read_later.reverse()
        produced: set = set()
        for li, layer in enumerate(dag):
            fault_point("train.layer")
            resumed = (not any(isinstance(s, Estimator) for s in layer)
                       and any(getattr(s, "_from_checkpoint", False)
                               for s in layer))
            data, fl = executor.fit_transform(data, [layer])
            fitted_dag.extend(fl)
            if keep is not None:
                produced.update(s.get_output().name for s in layer)
                dead = produced - read_later[li]
                if dead:
                    # waits for the programs that still read them
                    with span("workflow.release", columns=len(dead)):
                        data = data.without(dead)
                    produced -= dead
            if resumed:
                run_counters.layers_resumed += 1
            else:
                run_counters.layers_fitted += 1
                if ckpt is not None:
                    ckpt.save_layer(layer_offset + li, fl[0])
        return data, fitted_dag

    def _fit_workflow_cv(self, data: PipelineData, cut, executor,
                         ckpt=None) -> Dag:
        """Reference ``OpWorkflow.scala:408-449``: fit the pre-CV DAG once,
        run the selector with the in-CV (label-dependent) DAG refit per
        fold, then fit whatever remains downstream. With ``ckpt``, the
        before-DAG layers checkpoint as they complete (the selector's own
        sweep checkpoints through ``sweep.json``), and the full-data-refit
        during layers + selector + tail checkpoint after selection."""
        from transmogrifai_tpu.utils.profiling import OpStep, profiler
        with profiler.phase(OpStep.FEATURE_ENGINEERING):
            data, fitted_before = self._fit_layers(
                executor, data, cut.before, ckpt)
        with profiler.phase(OpStep.CROSS_VALIDATION):
            selected, fitted_during, data = cut.selector.fit_with_dag(
                data, cut.during, executor)
        n_before = len(cut.before)
        if ckpt is not None:
            for i, layer in enumerate(fitted_during):
                ckpt.save_layer(n_before + i, layer)
        with profiler.phase(OpStep.FEATURE_ENGINEERING):
            _, fitted_tail = self._fit_layers(
                executor, data, [[selected]] + cut.after, ckpt,
                layer_offset=n_before + len(fitted_during))
        return fitted_before + fitted_during + fitted_tail


class WorkflowModel:
    def __init__(self, result_features: Sequence[FeatureLike],
                 raw_features: Sequence[FeatureLike], dag: Dag,
                 executor: Optional[DagExecutor] = None,
                 blocklisted: Sequence[str] = (),
                 label_distribution: Optional[dict] = None,
                 raw_filter_results=None):
        self.result_features = tuple(result_features)
        self.raw_features = list(raw_features)
        self.dag = dag
        self.executor = executor or DagExecutor()
        self.blocklisted = list(blocklisted)
        #: bounded-bin label histogram of the training label (ModelInsights):
        #: a dict, or the function that makes it on first read
        self._label_distribution = label_distribution
        #: RawFeatureFilterResults (or None) — exclusion reasons incl.
        #: per-key map blocklists, surfaced in summary/ModelInsights
        self.raw_filter_results = raw_filter_results
        #: device-frame cache shared from the training Workflow (or a
        #: fresh one for loaded models): identical host frames skip the
        #: host->device re-transfer at scoring time
        from transmogrifai_tpu.ingest_fusion import DeviceFrameCache
        self._frame_cache = DeviceFrameCache()

    @property
    def label_distribution(self) -> Optional[dict]:
        if callable(self._label_distribution):
            self._label_distribution = self._label_distribution()
        return self._label_distribution

    # -- scoring -------------------------------------------------------------
    def _ingest_frame(self, reader_or_frame) -> fr.HostFrame:
        """HOST half of ingest: raw-feature frame generation only (no jax
        work) — safe to run on the streaming prefetch thread while the
        device executes the previous batch's FE program."""
        if isinstance(reader_or_frame, fr.HostFrame):
            reader: DataReader = CustomReader(frame=reader_or_frame)
        else:
            reader = reader_or_frame
        available = reader.available_columns()
        raw = list(self.raw_features)
        if available is not None:
            # The name-presence guard applies to features read by COLUMN
            # NAME. A predictor with a custom extract_fn computes its value
            # from the whole record, so its name is not a source column by
            # design (reference FeatureGeneratorStage) — exempt, UNLESS the
            # data is a bare frame (columns are all there is to extract
            # from). Responses stay name-ruled by default: they are
            # optional at scoring time and an extractor run against
            # label-less records would crash scoring that should work —
            # EXCEPT an extractor-backed response the caller explicitly
            # requested as a result feature (reference aggregate readers
            # compute response windows at score time on request,
            # JoinsAndAggregates.scala), which must run to be returned.
            frame_backed = isinstance(reader, CustomReader) \
                and reader.frame is not None
            requested = {f.name for f in self.result_features}

            def column_read(f) -> bool:
                if frame_backed:
                    return True
                if getattr(f.origin_stage, "extract_fn", None) is None:
                    return True
                return f.is_response and f.name not in requested

            missing_required = sorted(
                f.name for f in raw
                if not f.is_response and column_read(f)
                and f.name not in available)
            if missing_required:
                raise KeyError(
                    f"Scoring data lacks predictor columns {missing_required}")
            raw = [f for f in raw
                   if not column_read(f) or f.name in available]
        return reader.generate_frame(raw)

    def _ingest(self, reader_or_frame) -> PipelineData:
        return self._wrap_frame(self._ingest_frame(reader_or_frame))

    def _wrap_frame(self, frame: fr.HostFrame) -> PipelineData:
        """DEVICE half of ingest: wrap a generated host frame, consulting
        the device-frame cache so identical host columns reuse their
        resident device arrays. Scoring consults via the O(columns)
        identity memo only (``register=False``): the train-then-score
        flow hits (the training frame's column objects are registered at
        ``train()``), while a stream of distinct micro-batches never pays
        the O(rows) content hash."""
        from transmogrifai_tpu.ingest_fusion import frame_cache_enabled
        data = PipelineData.from_host(frame)
        if frame_cache_enabled():
            data = self._frame_cache.adopt(frame, data, register=False)
        return data

    def transform(self, reader_or_frame) -> PipelineData:
        from transmogrifai_tpu.utils.tracing import span
        with span("workflow.ingest",
                  reader=type(reader_or_frame).__name__):
            data = self._ingest(reader_or_frame)
        with span("workflow.transform", n_layers=len(self.dag)):
            return self.executor.transform(data, self.dag)

    def score(self, reader_or_frame, keep_raw_features: bool = False,
              keep_intermediate_features: bool = False) -> fr.HostFrame:
        """Run the fitted DAG; returns a host frame of result features
        (+ key), optionally with raw/intermediate columns."""
        data = self.transform(reader_or_frame)
        return self._score_frame(data, keep_raw_features,
                                 keep_intermediate_features)

    def _score_frame(self, data, keep_raw_features: bool = False,
                     keep_intermediate_features: bool = False) -> fr.HostFrame:
        names = [f.name for f in self.result_features]
        if keep_raw_features:
            names = [f.name for f in self.raw_features
                     if data.has(f.name)] + names
        if keep_intermediate_features:
            names = [n for n in list(data.host.names()) + list(data.device)
                     if n not in names] + names
        cols = {n: data.host_col(n) for n in dict.fromkeys(names)}
        return fr.HostFrame(cols, data.host.key)

    def evaluate(self, reader_or_frame, evaluator,
                 label: Optional[FeatureLike] = None,
                 prediction: Optional[FeatureLike] = None):
        data = self.transform(reader_or_frame)
        return self._evaluate_data(data, evaluator, label, prediction)

    def _evaluate_data(self, data, evaluator,
                       label: Optional[FeatureLike] = None,
                       prediction: Optional[FeatureLike] = None):
        pred_f = prediction or self._prediction_feature()
        label_f = label or self._label_feature(pred_f)
        return evaluator.evaluate(data, label_f.name, pred_f.name)

    def score_and_evaluate(self, reader_or_frame, evaluator, **kw):
        data = self.transform(reader_or_frame)
        return (self._score_frame(data, **kw),
                self._evaluate_data(data, evaluator))

    def _prediction_feature(self) -> FeatureLike:
        preds = [f for f in self.result_features
                 if issubclass(f.ftype, ft.Prediction)]
        if not preds:
            raise ValueError("No Prediction-typed result feature")
        return preds[0]

    def _label_feature(self, pred_f: FeatureLike) -> FeatureLike:
        for p in pred_f.origin_stage.input_features:
            if p.is_response:
                return p
        resp = [f for f in self.raw_features if f.is_response]
        if resp:
            return resp[0]
        raise ValueError("No response feature found for evaluation")

    # -- introspection -------------------------------------------------------
    def stages(self) -> list:
        return [t for layer in self.dag for t in layer]

    def selector_summary(self):
        for t in self.stages():
            if isinstance(t, SelectedModel) and t.summary is not None:
                return t.summary
        return None

    def summary_json(self) -> dict:
        from transmogrifai_tpu.utils.version import VersionInfo
        s = self.selector_summary()
        out = {
            "versionInfo": VersionInfo.to_json(),
            "resultFeatures": [f.name for f in self.result_features],
            "rawFeatures": [f.name for f in self.raw_features],
            "blocklistedFeatures": self.blocklisted,
            "stages": [{"uid": t.uid, "operation": t.operation_name}
                       for t in self.stages()],
        }
        if s is not None:
            out["selectedModel"] = s.to_json()
        if self.raw_filter_results is not None:
            out["rawFeatureFilterResults"] = self.raw_filter_results.to_json()
        return out

    def summary_pretty(self) -> str:
        s = self.selector_summary()
        lines = [f"Fitted workflow with {len(self.stages())} stages"]
        if s:
            lines.append(f"Selected model: {s.best_model_name} "
                         f"({s.validation_metric}={_best_metric(s):.4f} "
                         f"over {s.validation_type})")
            for name, m in (s.holdout_evaluation or {}).items():
                lines.append(f"Holdout [{name}]: " + ", ".join(
                    f"{k}={v:.4f}" for k, v in m.items()
                    if isinstance(v, (int, float))))
        return "\n".join(lines)

    def summary(self) -> str:
        return json.dumps(self.summary_json(), indent=2, default=str)

    def model_insights(self, prediction: Optional[FeatureLike] = None):
        """Merged explainability report (reference modelInsights(feature))."""
        from transmogrifai_tpu.insights.model_insights import ModelInsights
        return ModelInsights.from_workflow(self, prediction)

    def record_insights(self, reader_or_frame, top_k: int = 20):
        """Per-row LOCO insights for the scored data (reference
        RecordInsightsLOCO applied to the model's feature vector)."""
        from transmogrifai_tpu.insights.loco import RecordInsightsLOCO
        pred_f = self._prediction_feature()
        sel = pred_f.origin_stage
        feat_name = None
        for t in self.stages():
            if t.get_output() == pred_f:
                feat_name = t.runtime_input_names()[-1]
                model = t
        data = self.transform(reader_or_frame)
        loco = RecordInsightsLOCO(model=model, top_k=top_k)
        col = data.host_col(feat_name)
        return loco.host_apply(col).values

    def compute_data_up_to(self, feature: FeatureLike,
                           reader_or_frame) -> fr.HostFrame:
        """Materialize data through the FITTED stages up to ``feature``
        (reference ``OpWorkflowModel.computeDataUpTo``). Returns every
        feature generated along the way (raws + intermediates + target)."""
        data = self._ingest(reader_or_frame)
        # fitted models carry their own uids; ancestry matches on the
        # output feature nodes, which fit() shares with the estimators
        needed_outputs = {s.get_output().uid
                          for s in feature.parent_stages()} | {feature.uid}
        dag = [[t for t in layer if t.get_output().uid in needed_outputs]
               for layer in self.dag]
        dag = [l for l in dag if l]
        if not feature.is_raw and not any(
                t.get_output().uid == feature.uid
                for layer in dag for t in layer):
            raise KeyError(
                f"Feature {feature.name!r} is not produced by this fitted "
                "model's DAG")
        data = self.executor.transform(data, dag)
        return _frame_up_to(data, feature.raw_features(), dag)

    def score_stream(self, streaming_reader, write_batch=None):
        """Micro-batch continuous scoring (reference StreamingScore): yields
        one scored HostFrame per batch from the streaming reader."""
        from transmogrifai_tpu.readers.streaming import stream_score
        return stream_score(self, streaming_reader, write_batch)

    # -- persistence ---------------------------------------------------------
    def save(self, path: str, overwrite: bool = True) -> None:
        from transmogrifai_tpu.serialization import save_model
        save_model(self, path, overwrite=overwrite)

    # -- local serving -------------------------------------------------------
    def score_function(self, strict: bool = False):
        from transmogrifai_tpu.local.scoring import make_score_function
        return make_score_function(self, strict=strict)

    def serving_server(self, **kw):
        """Online micro-batched scoring server over the compiled DAG
        (``serving/``): ``submit(row) -> Future``, backpressure, graceful
        degradation to the row path. See ``docs/SERVING.md``."""
        from transmogrifai_tpu.serving import ScoringServer
        return ScoringServer(self, **kw)


def _frame_up_to(data, raw_features, dag) -> fr.HostFrame:
    """Raws + every stage output materialized by ``dag``, as a HostFrame."""
    names = [f.name for f in raw_features] + \
        [s.get_output().name for layer in dag for s in layer]
    cols = {n: data.host_col(n) for n in dict.fromkeys(names)
            if data.has(n)}
    return fr.HostFrame(cols, data.host.key)


def _label_distribution(frame: fr.HostFrame, raw_features):
    """Bounded-memory label histogram (reference: StreamingHistogram fed by
    the regression label; here for any numeric response), as a function
    that makes it from the label's values taken now: a histogram of a
    real-valued label takes a second per million rows, and a train whose
    model is never asked for it does not pay that."""
    for f in raw_features:
        if not f.is_response or f.name not in frame.columns:
            continue
        col = frame.columns[f.name]
        try:
            vals = np.asarray(col.values, np.float64)
        except (TypeError, ValueError):
            return None
        mask = getattr(col, "mask", None)
        if mask is not None:
            vals = vals[np.asarray(mask, bool)]
        return functools.partial(_label_histogram, f.name, vals.copy())
    return None


def _label_histogram(name: str, vals: np.ndarray) -> dict:
    from transmogrifai_tpu.utils.streaming_histogram import StreamingHistogram
    d = StreamingHistogram(max_bins=100).update_all(vals).to_json()
    d["name"] = name
    d["count"] = int(np.isfinite(vals).sum())
    if d["count"]:
        d["mean"] = float(np.nanmean(vals))
        d["min"] = float(np.nanmin(vals))
        d["max"] = float(np.nanmax(vals))
    return d


def _apply_blocklist(result_features: Sequence[FeatureLike],
                     blocked: set[str]) -> tuple[FeatureLike, ...]:
    """Rewire the DAG dropping blocklisted raw features (reference
    ``OpWorkflow.setBlocklist:118-167`` semantics): variadic stages lose the
    blocked inputs; fixed-arity stages with a blocked input become blocked
    themselves and the block propagates to their consumers. Mutates stage
    wiring in place (the pre-training graph is the only owner)."""
    blocked_uids: set[str] = set()

    def is_blocked(f: FeatureLike) -> bool:
        return (f.is_raw and f.name in blocked) or f.uid in blocked_uids

    for layer in compute_dag(result_features):
        for stage in layer:
            new_in = tuple(p for p in stage.input_features if not is_blocked(p))
            if len(new_in) == len(stage.input_features):
                continue
            min_arity = len(stage.in_types) if not stage.variadic \
                else len(stage.in_types)  # variadic: fixed prefix + >=1
            ok = (stage.variadic and len(new_in) >= min_arity) or \
                 (not stage.variadic and len(new_in) == len(stage.in_types))
            if ok:
                stage._inputs = new_in
                out = stage._output
                if out is not None:
                    out._parents = new_in
            else:
                blocked_uids.add(stage.get_output().uid)
    return tuple(f for f in result_features if not is_blocked(f))


def _best_metric(s) -> float:
    for r in s.validation_results:
        if r.model_name == s.best_model_name:
            return float(r.metric_values.get(s.validation_metric, float("nan")))
    return float("nan")


def load_model(path: str) -> WorkflowModel:
    from transmogrifai_tpu.serialization import load_model as _load
    return _load(path)


# attach for API parity: Workflow.load_model(path)
Workflow.load_model = staticmethod(load_model)
