"""DAG compilation and layer-fused execution.

Parity: reference ``core/.../utils/stages/FitStagesUtil.scala:96-369`` —
``computeDAG`` levels stages by max distance-to-result; ``fitAndTransformDAG``
folds over layers fitting estimators then bulk-applying transformers;
``applyOpTransformations`` fuses all row-level transformers of a layer into
one pass.

TPU-first: the per-layer fusion target is a single jitted XLA program over
device columns (params passed as a pytree so recompilation is shape-keyed
only); host transformers run eagerly before it. Compiled programs are cached
per (layer stage uids) on the executor, so repeated scoring reuses them.

Round 14 extends fusion past the single layer: a maximal run of consecutive
ALL-device DAG levels compiles as ONE jitted program
(``fuse_dag_program``) — intermediate columns live only inside the program
(XLA register/VMEM residency, no HBM round-trip between levels), and when
every level is fusable the whole ingest->features pipeline feeding the
ModelSelector is a single device dispatch. Gated by
``TRANSMOGRIFAI_FE_FUSED=1|0`` (default on); with the gate off the
pre-fusion per-layer path runs byte-for-byte (counter-asserted in tests and
the committed ``INGEST_FE_FUSION.json``). An OOM inside a fused segment
takes the resource ladder's ``ingest.fuse`` rung: the segment re-applies
stage-by-stage (peak memory ~ one stage's block, not the whole segment's
intermediates) and the run completes.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

_PROFILE = os.environ.get("TRANSMOGRIFAI_PROFILE") == "1"


def _plog(msg: str, t0: float) -> None:
    if _PROFILE:
        print(f"[profile] {msg}: {time.time() - t0:.2f}s", file=sys.stderr)

from transmogrifai_tpu.features.feature import FeatureLike
from transmogrifai_tpu.pipeline_data import PipelineData
from transmogrifai_tpu.stages.base import (
    Estimator, PipelineStage, Transformer,
)
from transmogrifai_tpu.utils.devicewatch import compile_telemetry
from transmogrifai_tpu.utils.tracing import device_scope, span

__all__ = ["compute_dag", "cut_dag", "CutDag", "DagExecutor", "Dag",
           "fuse_layer_program", "fuse_dag_program", "fe_fused_enabled",
           "fusable_segments"]

Dag = list  # list[list[PipelineStage]], execution order


def fe_fused_enabled() -> bool:
    """Master gate for multi-layer FE fusion (``TRANSMOGRIFAI_FE_FUSED``,
    default on). Off = the pre-round-14 per-layer execution path,
    byte-for-byte."""
    return os.environ.get("TRANSMOGRIFAI_FE_FUSED", "1") != "0"


def _layer_fusable(layer) -> bool:
    """A DAG level joins a fused segment when every stage is a device
    transformer (host/string stages force eager materialization)."""
    return bool(layer) and all(
        isinstance(s, Transformer) and s.is_device for s in layer)


def fusable_segments(dag: Dag):
    """Partition a fitted DAG into execution segments: ``("fused",
    [layer, ...])`` for each maximal run of consecutive all-device levels,
    ``("layer", layer)`` for everything else. Segment order preserves DAG
    order, so replaying segments is exactly replaying the DAG."""
    run: list = []
    for layer in dag:
        if _layer_fusable(layer):
            run.append(layer)
            continue
        if run:
            yield ("fused", run)
            run = []
        yield ("layer", layer)
    if run:
        yield ("fused", run)


def compute_dag(result_features: Sequence[FeatureLike]) -> Dag:
    """Level the ancestor stages of the result features by max distance to
    any result; farthest layer executes first. Raw feature generators are
    excluded (they run at ingest, inside the readers)."""
    dist: dict[PipelineStage, int] = {}
    for f in result_features:
        for stage, d in f.parent_stages().items():
            if stage.is_raw_generator:
                continue
            if stage not in dist or dist[stage] < d:
                dist[stage] = d
    if not dist:
        return []
    _check_distinct_uids(dist)
    max_d = max(dist.values())
    layers: list[list[PipelineStage]] = [[] for _ in range(max_d + 1)]
    for stage, d in dist.items():
        layers[max_d - d].append(stage)
    # stable order within a layer: by uid for determinism
    for layer in layers:
        layer.sort(key=lambda s: s.uid)
    return [l for l in layers if l]


class CutDag:
    """The DAG cut around the ModelSelector for leakage-free workflow CV.

    Parity: reference ``FitStagesUtil.cutDAG`` (``FitStagesUtil.scala:
    302-355``) — splits the workflow DAG into:
      - ``before``: stages safe to fit once on the full training data
      - ``during``: label-dependent feature stages (and everything at or
        after them on the selector's ancestor path) that must be refit
        inside every CV fold to avoid leaking label information
      - ``after``: stages downstream of the selector or of any during stage
    """

    def __init__(self, selector, before: Dag, during: Dag, after: Dag):
        self.selector = selector
        self.before = before
        self.during = during
        self.after = after


def cut_dag(result_features: Sequence[FeatureLike]) -> CutDag:
    from transmogrifai_tpu.selector.model_selector import ModelSelector

    dag = compute_dag(result_features)
    selectors = [s for layer in dag for s in layer
                 if isinstance(s, ModelSelector)]
    if not selectors:
        return CutDag(None, dag, [], [])
    if len(selectors) > 1:
        raise ValueError(
            f"Workflow can contain at most 1 ModelSelector, found "
            f"{len(selectors)}: {selectors}")
    ms = selectors[0]

    # the selector's ancestor DAG, least-deep layer last (selector excluded)
    ms_dag = compute_dag([ms.get_output()])
    ms_dag = [[s for s in layer if s is not ms] for layer in ms_dag]
    ms_dag = [l for l in ms_dag if l]

    # first layer containing a label-dependent stage (inputs mix response
    # and predictors): everything from there on refits inside each fold
    def label_dependent(stage) -> bool:
        ins = stage.input_features
        return (any(f.is_response for f in ins)
                and any(not f.is_response for f in ins))

    first = next((i for i, layer in enumerate(ms_dag)
                  if any(label_dependent(s) for s in layer)), None)
    during_layers = ms_dag[first:] if first is not None else []
    during_set = {s for layer in during_layers for s in layer}

    def ancestors(stage) -> set:
        out: set = set()
        for f in stage.input_features:
            out.update(f.parent_stages().keys())
        return out

    before: Dag = []
    after: Dag = []
    for layer in dag:
        b_layer, a_layer = [], []
        for s in layer:
            if s is ms or s in during_set:
                continue
            anc = ancestors(s)
            if ms in anc or (anc & during_set):
                a_layer.append(s)
            else:
                b_layer.append(s)
        if b_layer:
            before.append(b_layer)
        if a_layer:
            after.append(a_layer)
    return CutDag(ms, before, during_layers, after)


def _check_distinct_uids(dist) -> None:
    seen: dict[str, PipelineStage] = {}
    for stage in dist:
        other = seen.get(stage.uid)
        if other is not None and other is not stage:
            raise ValueError(
                f"Duplicate stage uid {stage.uid} for distinct stage objects "
                "(reference checkDistinctUIDs)")
        seen[stage.uid] = stage


class DagExecutor:
    """Fits/applies a leveled DAG over PipelineData with per-layer fusion
    (and, round 14, cross-layer fusion of all-device level runs)."""

    def __init__(self):
        self._fused_cache: dict[tuple[str, ...], Any] = {}
        #: cross-layer fused programs, keyed by the segment's stage uids
        self._fused_dag_cache: dict[tuple[str, ...], Any] = {}

    # -- fit -----------------------------------------------------------------
    def fit_transform(self, data: PipelineData, dag: Dag
                      ) -> tuple[PipelineData, Dag]:
        """Fold over layers: fit estimators, then apply the whole layer.
        Returns transformed data + the fitted DAG (estimators replaced by
        their models). With FE fusion on, consecutive estimator-free
        all-device layers DEFER application and flush as one fused device
        program at the next materialization point (an estimator fit, a
        host layer, or the end of the DAG) — the whole-pipeline fusion the
        fitted-DAG replay path gets unconditionally."""
        fuse = fe_fused_enabled()
        fitted_dag: Dag = []
        pending: list = []  # deferred all-device fitted layers

        def flush(d: PipelineData) -> PipelineData:
            if not pending:
                return d
            t0 = time.time()
            d = self.apply_fused(d, list(pending))
            _plog(f"apply fused segment ({len(pending)} layers)", t0)
            pending.clear()
            return d

        for layer in dag:
            has_estimator = any(isinstance(s, Estimator) for s in layer)
            if fuse and not has_estimator and _layer_fusable(layer):
                pending.append(layer)
                fitted_dag.append(list(layer))
                continue
            data = flush(data)
            fitted_layer: list[Transformer] = []
            for stage in layer:
                if isinstance(stage, Estimator):
                    t0 = time.time()
                    with compile_telemetry.building(
                                f"stage.fit:{type(stage).__name__}"), \
                            span("stage.fit", hbm=True, stage_uid=stage.uid,
                                 stage_cls=type(stage).__name__,
                                 op=stage.operation_name, phase="fit"):
                        fitted_layer.append(stage.fit(data))
                    _plog(f"fit {stage.operation_name}", t0)
                elif isinstance(stage, Transformer):
                    fitted_layer.append(stage)
                else:
                    raise TypeError(f"Cannot execute stage {stage!r}")
            t0 = time.time()
            if fuse and _layer_fusable(fitted_layer):
                data = self.apply_fused(data, [fitted_layer])
            else:
                data = self.apply_layer(data, fitted_layer)
            _plog(f"apply layer [{', '.join(t.operation_name for t in fitted_layer)}]",
                  t0)
            fitted_dag.append(fitted_layer)
        data = flush(data)
        return data, fitted_dag

    # -- transform -----------------------------------------------------------
    def transform(self, data: PipelineData, dag: Dag) -> PipelineData:
        if not fe_fused_enabled():
            # the pre-fusion path, byte-for-byte (counter-asserted: no
            # fused segment programs run with the gate off)
            for layer in dag:
                data = self.apply_layer(data, layer)
            return data
        for kind, seg in fusable_segments(dag):
            if kind == "fused":
                data = self.apply_fused(data, seg)
            else:
                data = self.apply_layer(data, seg)
        return data

    @compile_telemetry.building("fe.layer")  # host stages open their own
    def apply_layer(self, data: PipelineData,
                    transformers: Sequence[Transformer]) -> PipelineData:
        host_ts = [t for t in transformers if not t.is_device]
        dev_ts = [t for t in transformers if t.is_device]
        if host_ts:
            # host transformers run eagerly one at a time — each gets its
            # own stage span (the "which vectorizer is slow" answer)
            new_host, new_dev = {}, {}
            for t in host_ts:
                with compile_telemetry.building(
                            f"stage.transform:{type(t).__name__}"), \
                        span("stage.transform", hbm=True, stage_uid=t.uid,
                             stage_cls=type(t).__name__,
                             op=t.operation_name, phase="transform"):
                    out = t.device_output_column(data)
                    if out is None:
                        new_host[t.get_output().name] = t.output_column(data)
                    else:
                        new_dev[t.get_output().name] = out
            data = data.with_host_cols(new_host)
            if new_dev:
                data = data.with_device_cols(new_dev)
        if dev_ts:
            from transmogrifai_tpu.utils.retry import with_device_retry
            fused = self._fused_program(dev_ts)
            params = {t.uid: t.device_params() for t in dev_ts}
            in_cols = {n: data.device_col(n)
                       for t in dev_ts for n in t.runtime_input_names()}
            # the fused layer program is the training/scoring hot path's
            # device dispatch: transient device errors (a flaky runtime, and
            # the chaos suite's injected faults) retry with backoff instead
            # of killing a run a checkpoint would otherwise have to resume
            with span("layer.apply_device", n_stages=len(dev_ts),
                      stages=",".join(t.operation_name for t in dev_ts)):
                outs = with_device_retry(fused, params, in_cols,
                                         site="dag.apply_layer")
            data = data.with_device_cols(outs)
            # record fitted vector metadata OUTSIDE the traced program
            # (ModelInsights' fallback reads the last stage's out_meta;
            # mutating self inside device_apply would tie freshness to jit
            # cache behavior)
            for t in dev_ts:
                m = getattr(outs.get(t.get_output().name), "metadata", None)
                if m is not None:
                    t.out_meta = m
        return data

    def _fused_program(self, dev_ts: Sequence[Transformer]):
        key = tuple(t.uid for t in dev_ts)
        cached = self._fused_cache.get(key)
        if cached is not None:
            return cached
        compiled = _positional_program([list(dev_ts)])
        self._fused_cache[key] = compiled
        return compiled

    # -- cross-layer fusion (round 14) ---------------------------------------
    @compile_telemetry.building("fe.fused")  # the stagewise rung: fe.layer
    def apply_fused(self, data: PipelineData,
                    layers: Sequence[Sequence[Transformer]]) -> PipelineData:
        """Apply a run of consecutive all-device layers as ONE jitted
        program. Intermediate level outputs never materialize in HBM
        between levels; every stage output still lands in the returned
        PipelineData (downstream layers, host pulls and keep-intermediate
        scoring read them exactly as before).

        Failure ladder: an OOM inside the fused program (the whole
        segment's intermediates are live at once) takes the
        ``ingest.fuse`` rung — re-apply the segment stage by stage, the
        smallest-peak execution order — instead of killing a run the
        per-layer path would have completed."""
        from transmogrifai_tpu.utils.faults import fault_point
        from transmogrifai_tpu.utils.profiling import ingest_counters
        from transmogrifai_tpu.utils.retry import with_device_retry
        stages = [t for layer in layers for t in layer]
        key = tuple(t.uid for t in stages)
        prog = self._fused_dag_cache.get(key)
        if prog is None:
            prog = self._fused_dag_cache[key] = _positional_program(layers)
        params = {t.uid: t.device_params() for t in stages}
        produced = {t.get_output().name for t in stages}
        in_names = [n for t in stages for n in t.runtime_input_names()
                    if n not in produced]
        try:
            fault_point("ingest.fuse")
            in_cols = {n: data.device_col(n) for n in dict.fromkeys(in_names)}
            with span("fe.fused", n_stages=len(stages), n_layers=len(layers),
                      stages=",".join(t.operation_name for t in stages)):
                outs = with_device_retry(prog, params, in_cols,
                                         site="dag.apply_layer")
        except Exception as err:
            from transmogrifai_tpu.utils import resources
            from transmogrifai_tpu.utils.faults import FaultHarnessError
            if isinstance(err, FaultHarnessError):
                raise
            if not (resources.ladder_enabled()
                    and resources.is_resource_exhausted(err)):
                raise
            resources.record_degradation(
                "ingest.fuse", "stagewise", error=err,
                nStages=len(stages), nLayers=len(layers),
                nRows=data.n_rows)
            ingest_counters.fe_host_fallbacks += 1
            ingest_counters.fe_host_rows += data.n_rows * len(stages)
            return self._apply_stagewise(data, layers)
        ingest_counters.fe_fused_programs += 1
        ingest_counters.fe_fused_stages += len(stages)
        ingest_counters.fe_fused_rows += data.n_rows * len(stages)
        data = data.with_device_cols(outs)
        for t in stages:
            m = getattr(outs.get(t.get_output().name), "metadata", None)
            if m is not None:
                t.out_meta = m
        return data

    def _apply_stagewise(self, data: PipelineData,
                         layers: Sequence[Sequence[Transformer]]
                         ) -> PipelineData:
        """The ``ingest.fuse`` OOM rung: one stage = one small jitted
        program (``apply_layer`` over single-stage layers), intermediates
        materialized (and droppable) between stages — peak memory is a
        single stage's blocks. Staying on the jitted path keeps the rung
        bitwise-identical to the fused program (eager per-primitive
        execution codegens trig differently at the ULP level)."""
        for layer in layers:
            for t in layer:
                data = self.apply_layer(data, [t])
        return data


def fuse_layer_program(dev_ts: Sequence[Transformer], donate: bool = False,
                       precision: str = "f32"):
    """One jitted XLA program applying every device transformer of a layer.

    Signature: ``fused(params, donate_cols, keep_cols) -> {out name: col}``
    where the two column dicts together hold every runtime input. With
    ``donate=True`` the ``donate_cols`` buffers are donated to XLA (the
    online-serving steady state: per-batch input uploads whose last consumer
    is this layer are spent, halving resident batch memory); callers must
    not touch a donated column afterwards. Batch scoring passes everything
    in ``keep_cols`` — columns live in the executor's PipelineData and are
    reread by later layers and host pulls."""
    return fuse_dag_program([list(dev_ts)], donate=donate,
                            precision=precision)


def fuse_dag_program(layers: Sequence[Sequence[Transformer]],
                     donate: bool = False, precision: str = "f32"):
    """One jitted XLA program applying a run of consecutive ALL-device DAG
    levels — the round-14 generalization of :func:`fuse_layer_program`
    (which is the single-level special case and shares this builder, so
    serving's per-layer programs and the executor's segment programs are
    one code path).

    Signature and donation semantics match ``fuse_layer_program``; the
    returned dict holds EVERY stage output across the fused levels.
    Level-to-level intermediates flow through the traced program directly:
    a later level's stage reads an earlier level's output column from the
    in-program environment, never from HBM.

    ``precision`` selects the ladder rung the program computes at. The
    default ``"f32"`` rung traces exactly the pre-ladder program (no
    casts staged out at all). Non-f32 rungs cast float input leaves and
    per-stage float params to the rung's compute dtype in-trace
    (``QuantizedTensor`` weights dequantize, ``ExactTensor`` leaves keep
    their stored dtype) and cast float output leaves back to f32, so
    callers always see f32 results regardless of rung."""
    return jax.jit(_fe_fused_fn(layers, precision),
                   donate_argnums=(1,) if donate else ())


def _fe_fused_fn(layers: Sequence[Sequence[Transformer]], precision: str):
    """The traceable body of :func:`fuse_dag_program`."""
    from transmogrifai_tpu.utils.precision import (
        cast_float_leaves, compute_dtype, materialize_tree)
    layer_list = [list(layer) for layer in layers]
    comp = compute_dtype(precision)

    def fe_fused(params, donate_cols, keep_cols):  # program jit_fe_fused
        env = {**donate_cols, **keep_cols}
        if comp is not None:
            env = cast_float_leaves(env, comp)
            params = cast_float_leaves(params, comp)
            params = materialize_tree(params, comp)
        out = {}
        for ts in layer_list:
            produced = {}
            for t in ts:
                cols = [env[n] for n in t.runtime_input_names()]
                # per-stage named scope: ops staged out here carry the
                # stage's operation name + uid in their XLA metadata, so
                # profiler-trace device slices attribute to stages, not
                # just layers/segments
                with device_scope(f"{t.operation_name}[{t.uid}]"):
                    produced[t.get_output().name] = t.device_apply(
                        params[t.uid], *cols)
            # a level's outputs become visible to LATER levels only
            # (within a level, stages are independent by construction)
            env.update(produced)
            out.update(produced)
        if comp is not None:
            out = cast_float_leaves(out, jnp.float32)
        return out

    return fe_fused


def _positional_program(layers: Sequence[Sequence[Transformer]]):
    """The training executor's form of :func:`fuse_dag_program`:
    ``run(params, in_cols) -> {out name: col}`` over ONE jitted program
    whose arguments and results are positional. Stage uids and the column
    names built from them are new for every ``Workflow``; as dictionary
    keys of a jitted function they are written into the module
    (``jax.arg_info`` / ``jax.result_info``) and so into the persistent
    cache's key, and every train compiled its feature-engineering programs
    anew. Here they stay in the tree structure, on the host."""
    body = _fe_fused_fn(layers, "f32")
    programs: dict = {}

    def run(params, in_cols):
        leaves, tree = jax.tree_util.tree_flatten((params, in_cols))
        entry = programs.get(tree)
        if entry is None:
            box: dict = {}

            def fe_fused(*flat):  # program jit_fe_fused
                p, cols = jax.tree_util.tree_unflatten(tree, flat)
                flat_out, box["tree"] = jax.tree_util.tree_flatten(
                    body(p, {}, cols))
                return flat_out

            entry = programs[tree] = (jax.jit(fe_fused), box)
        program, box = entry
        flat_out = program(*leaves)  # the first call traces, and fills box
        return jax.tree_util.tree_unflatten(box["tree"], flat_out)

    return run
