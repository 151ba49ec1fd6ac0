"""Workflow runner: CLI-style train/score/evaluate entry point.

Parity: reference ``core/.../OpWorkflowRunner.scala`` / ``OpApp.scala`` —
run types Train / Score / Evaluate / Features driven by an OpParams json,
writing model/metrics/scores to configured locations and reporting a result
json; `python -m transmogrifai_tpu.runner --run-type train --params p.json`
mirrors the spark-submit surface.
"""

from __future__ import annotations

import os
import argparse
import json
import sys
import time
from typing import Any, Callable, Optional

from transmogrifai_tpu.params import OpParams
from transmogrifai_tpu.utils.profiling import OpStep, profiler
from transmogrifai_tpu.workflow import Workflow, WorkflowModel, load_model

__all__ = ["WorkflowRunner", "RunTypes"]


class RunTypes:
    TRAIN = "train"
    SCORE = "score"
    STREAMING_SCORE = "streaming-score"
    SERVE = "serve"
    SCALEOUT = "scaleout"
    CONTINUOUS = "continuous"
    EVALUATE = "evaluate"
    FEATURES = "features"
    ALL = (TRAIN, SCORE, STREAMING_SCORE, SERVE, SCALEOUT, CONTINUOUS,
           EVALUATE, FEATURES)


class WorkflowRunner:
    """Wraps a workflow + evaluator + reader factory for parameterized runs."""

    def __init__(self, workflow: Workflow,
                 evaluator=None,
                 scoring_reader_factory: Optional[Callable[[OpParams], Any]] = None):
        self.workflow = workflow
        self.evaluator = evaluator
        self.scoring_reader_factory = scoring_reader_factory
        self.on_end_handlers: list[Callable[[dict], None]] = []

    def run(self, run_type: str, params: OpParams,
            checkpoint_dir: Optional[str] = None,
            trace_out: Optional[str] = None) -> dict:
        """Execute one parameterized run. ``checkpoint_dir`` (TRAIN only)
        enables resumable training: fitted DAG layers and the selector
        sweep checkpoint there, and re-running the same command after a
        crash/preemption resumes instead of refitting (the run result's
        ``appMetrics.runCounters.layersResumed`` reports how much work the
        resume skipped). ``trace_out`` profiles the whole run (one
        ``jax.profiler`` trace when the backend supports it) and writes a
        Perfetto/chrome://tracing JSON merging the host span tree with the
        device timeline there (docs/OBSERVABILITY.md)."""
        t0 = time.time()
        trace_dir = None
        if trace_out:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="transmogrifai_trace_")
        profiler.reset(app_name=f"transmogrifai_tpu.{run_type}",
                       trace_dir=trace_dir)
        applied = params.apply_to_stages(
            [s for f in self.workflow.result_features
             for s in f.parent_stages()])
        reader_applied = params.apply_to_reader(self.workflow.reader)
        #: custom params ride on the workflow for app/stage code (reference
        #: OpParams.customParams passthrough)
        self.workflow.op_params = params
        result: dict = {"runType": run_type, "stageOverrides": applied,
                        "readerOverrides": reader_applied}
        if params.custom_params:
            result["customParams"] = dict(params.custom_params)
        try:
            if run_type == RunTypes.TRAIN:
                with profiler.phase(OpStep.MODEL_TRAINING):
                    model = self.workflow.train(
                        checkpoint_dir=checkpoint_dir)
                if checkpoint_dir:
                    result["checkpointDir"] = checkpoint_dir
                if params.model_location:
                    with profiler.phase(OpStep.RESULTS_SAVING):
                        model.save(params.model_location)
                    result["modelLocation"] = params.model_location
                result["summary"] = model.summary_json()
            elif run_type == RunTypes.STREAMING_SCORE:
                # reference OpWorkflowRunner StreamingScore: score every
                # micro-batch as it lands, writing per-batch score files
                from transmogrifai_tpu.readers.streaming import (
                    StreamingReader, stream_score,
                )
                if params.model_location is None:
                    raise ValueError(f"{run_type} requires modelLocation")
                model = load_model(params.model_location)
                reader = (self.scoring_reader_factory(params)
                          if self.scoring_reader_factory
                          else self.workflow.reader)
                if not isinstance(reader, StreamingReader):
                    raise ValueError(
                        "streaming-score requires a StreamingReader (got "
                        f"{type(reader).__name__})")

                def write_batch(frame, i):
                    if not params.score_location:
                        return
                    from transmogrifai_tpu.readers.avro import save_avro
                    os.makedirs(params.score_location, exist_ok=True)
                    # idempotent per-source naming: a checkpoint-resumed
                    # stream that REPLAYS the in-flight batch overwrites
                    # the same score file instead of duplicating rows;
                    # non-file sources fall back to the stream index
                    src = getattr(reader, "current_file", None)
                    if src:
                        import hashlib
                        # short path hash: distinct sources sharing a
                        # basename stem (day1.csv vs day1.avro, same-named
                        # files in sibling dirs) must not collide
                        tag = hashlib.sha1(
                            src.encode()).hexdigest()[:8]
                        stem = (os.path.splitext(os.path.basename(src))[0]
                                + "_" + tag)
                    else:
                        stem = f"batch_{i:06d}"
                    out = os.path.join(params.score_location,
                                       f"scores_{stem}.avro")
                    tmp = out + ".tmp"
                    save_avro(frame, tmp)   # atomic: no truncated .avro
                    os.replace(tmp, out)    # survives a crash mid-write

                n_rows = n_batches = 0
                with profiler.phase(OpStep.SCORING):
                    for frame in stream_score(model, reader, write_batch):
                        n_batches += 1
                        n_rows += frame.n_rows
                result["nBatches"] = n_batches
                result["nRows"] = n_rows
            elif run_type == RunTypes.CONTINUOUS:
                # closed-loop continuous AutoML: stream ingest + drift
                # detection + checkpoint-resumed retrain + zero-downtime
                # hot-swap, one long-running supervised process
                # (docs/CONTINUOUS.md). The runner's workflow is the
                # retrain template; customParams.streamDir names the
                # watched directory and checkpoint_dir (or
                # customParams.stateDir) the durable resume root.
                self._run_continuous(params, result, checkpoint_dir)
            elif run_type == RunTypes.SCALEOUT:
                # multi-process serving scale-out replay: spin the
                # router + N replica worker subprocesses and drive the
                # reader's rows through the HTTP front (docs/SERVING.md
                # "Scale-out"). customParams: modelDir (required),
                # replicas, defaultModel (replay target), stateDir
                # (default --checkpoint-dir)
                self._run_scaleout(params, result, checkpoint_dir)
            elif run_type == RunTypes.SERVE and \
                    (params.custom_params or {}).get("modelDir"):
                # fleet replay: customParams.modelDir registers every
                # saved model under a directory into a FleetServer and
                # replays the reader against customParams.defaultModel
                # (docs/SERVING.md "Serving fleet")
                self._serve_fleet(params, result)
            elif run_type == RunTypes.SERVE:
                # online-serving replay: every reader row becomes one
                # submit() through the micro-batched server (admission,
                # batching, degradation all exercised), metrics reported
                # in the result json (see docs/SERVING.md)
                if params.model_location is None:
                    raise ValueError(f"{run_type} requires modelLocation")
                from transmogrifai_tpu.serving import ScoringServer
                model = load_model(params.model_location)
                reader = (self.scoring_reader_factory(params)
                          if self.scoring_reader_factory
                          else self.workflow.reader)
                # requests carry predictors only — the online contract
                predictors = [f for f in model.raw_features
                              if not f.is_response]
                frame = reader.generate_frame(predictors)
                cp = dict(params.custom_params or {})
                timeout_ms = cp.get("timeoutMs")
                queue_capacity = int(cp.get("queueCapacity", 1024))
                server = ScoringServer(
                    model,
                    max_batch=int(cp.get("maxBatch", 256)),
                    max_wait_ms=float(cp.get("maxWaitMs", 2.0)),
                    queue_capacity=queue_capacity,
                    default_timeout_ms=(float(timeout_ms)
                                        if timeout_ms is not None else None),
                    strict=bool(cp.get("strict", True)),
                    retries=int(cp.get("retries", 2)))
                out_fh = out_path = tmp = None
                if params.score_location:
                    os.makedirs(params.score_location, exist_ok=True)
                    out_path = os.path.join(params.score_location,
                                            "scores_serve.jsonl")
                    tmp = out_path + ".tmp"
                    out_fh = open(tmp, "w")
                n_rows = n_errors = 0
                window: list = []

                def _drain_window() -> None:
                    # a failed/expired request reports in ITS slot; it
                    # must not discard the rest of the replay. Draining
                    # per queue_capacity window keeps memory bounded —
                    # the admission queue's bound means nothing if the
                    # replay holds every row/future/score at once
                    nonlocal n_rows, n_errors
                    for f in window:
                        try:
                            s = f.result()
                        except Exception as e:  # noqa: BLE001 — reported in the result slot
                            s = {"error": f"{type(e).__name__}: {e}"}
                            n_errors += 1
                        n_rows += 1
                        if out_fh is not None:
                            out_fh.write(json.dumps(s, default=str) + "\n")
                    window.clear()

                with profiler.phase(OpStep.SCORING):
                    row_iter = frame.iter_rows()
                    first = next(row_iter, None)
                    server.start(warmup_row=first)
                    try:
                        if first is not None:
                            import itertools
                            for row in itertools.chain([first], row_iter):
                                window.append(server.submit_blocking(row))
                                if len(window) >= queue_capacity:
                                    _drain_window()
                        _drain_window()
                    finally:
                        server.stop()
                if out_fh is not None:
                    out_fh.close()
                    os.replace(tmp, out_path)
                    result["scoreLocation"] = out_path
                result["nRows"] = n_rows
                result["nErrors"] = n_errors
                # the replay is already inside a SCORING phase: don't let
                # the snapshot mirror the dispatch wall in a second time
                result["servingMetrics"] = server.snapshot(
                    mirror_to_profiler=False)
            elif run_type in (RunTypes.SCORE, RunTypes.EVALUATE,
                              RunTypes.FEATURES):
                if params.model_location is None:
                    raise ValueError(f"{run_type} requires modelLocation")
                model = load_model(params.model_location)
                reader = (self.scoring_reader_factory(params)
                          if self.scoring_reader_factory
                          else self.workflow.reader)
                if run_type == RunTypes.FEATURES:
                    with profiler.phase(OpStep.FEATURE_ENGINEERING):
                        frame = model.score(reader, keep_raw_features=True,
                                            keep_intermediate_features=True)
                    result["nRows"] = frame.n_rows
                    result["columns"] = frame.names()
                else:
                    with profiler.phase(OpStep.SCORING):
                        scores = model.score(reader)
                    result["nRows"] = scores.n_rows
                    if params.score_location:
                        # reference OpWorkflowRunner writes scores to the
                        # configured location. scoreLocation is a DIRECTORY
                        # in every run type (streaming writes batch files
                        # into it; score writes scores.avro) — one param,
                        # one meaning
                        with profiler.phase(OpStep.RESULTS_SAVING):
                            from transmogrifai_tpu.readers.avro import (
                                save_avro,
                            )
                            os.makedirs(params.score_location, exist_ok=True)
                            out_path = os.path.join(params.score_location,
                                                    "scores.avro")
                            save_avro(scores, out_path)
                        result["scoreLocation"] = out_path
                    if run_type == RunTypes.EVALUATE:
                        if self.evaluator is None:
                            raise ValueError("evaluate requires an evaluator")
                        with profiler.phase(OpStep.EVALUATION):
                            metrics = model.evaluate(reader, self.evaluator)
                        from transmogrifai_tpu.evaluators.base import EvaluatorBase
                        result["metrics"] = EvaluatorBase.to_json(metrics)
                        if params.metrics_location:
                            with open(params.metrics_location, "w") as fh:
                                json.dump(result["metrics"], fh, indent=2)
            else:
                raise ValueError(
                    f"Unknown run type {run_type!r}; one of {RunTypes.ALL}")
            result["status"] = "success"
        except Exception as e:  # report failure like the reference runner
            result["status"] = "failure"
            result["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            result["wallSeconds"] = time.time() - t0
            metrics = profiler.finalize()
            if trace_out:
                try:
                    result["trace"] = metrics.export_chrome_trace(trace_out)
                    result["traceOut"] = trace_out
                except Exception as e:  # noqa: BLE001 — a failed trace export must not fail the run
                    result["traceError"] = f"{type(e).__name__}: {e}"
            if trace_dir:
                import shutil
                # the XSpace protos are parsed at finalize(); only the
                # merged chrome trace is the artifact — repeated profiled
                # runs must not accumulate proto dirs in /tmp
                shutil.rmtree(trace_dir, ignore_errors=True)
            result["appMetrics"] = metrics.to_json()
            # host-pressure snapshot at run end (utils/resources.py):
            # pairs with appMetrics.resourceCounters so a result json
            # shows both WHAT rungs the run took and the pressure state
            # it finished under
            from transmogrifai_tpu.utils.resources import pressure_state
            result["resourcePressure"] = pressure_state(
                checkpoint_dir or ".")
            for h in self.on_end_handlers:
                h(result)
        return result

    def _run_continuous(self, params: OpParams, result: dict,
                        checkpoint_dir: Optional[str]) -> None:
        """CONTINUOUS: drive a ``continuous.ContinuousLoop`` from
        OpParams. ``customParams``: ``streamDir`` (required), ``pattern``,
        ``stateDir`` (default: ``--checkpoint-dir``), ``modelId``,
        ``windowBatches``, ``maxBufferBatches``, ``maxWindows``,
        ``timeoutS``, ``pollIntervalS``, drift knobs (``driftMetric``,
        ``jsThreshold``, ``psiThreshold``, ``fillDeltaThreshold``,
        ``labelDeltaThreshold``, ``consecutiveWindows``,
        ``cooldownWindows``), ``shadowTolerance``, ``stalenessBoundS``,
        ``metricsPort``, ``accessLogSample`` (sampled http.access
        events), ``sloConfig`` (objectives JSON path), ``eventsSpill``
        (durable flight-recorder JSONL under the state dir, default
        on). ``modelLocation`` loads the initial serving
        model; without it the loop bootstraps from the first window.
        ``referencePath`` names a batch file sampling that model's
        training data to pin the drift reference (else the first stream
        window is adopted)."""
        from transmogrifai_tpu.continuous import ContinuousLoop, DriftConfig
        cp = dict(params.custom_params or {})
        stream_dir = cp.get("streamDir")
        if not stream_dir:
            raise ValueError("continuous requires customParams.streamDir")
        state_dir = cp.get("stateDir") or checkpoint_dir
        if not state_dir:
            raise ValueError(
                "continuous requires a durable state root: pass "
                "--checkpoint-dir or customParams.stateDir")
        initial_model = (load_model(params.model_location)
                         if params.model_location else None)
        drift = DriftConfig(
            metric=cp.get("driftMetric", "js"),
            js_threshold=float(cp.get("jsThreshold", 0.25)),
            psi_threshold=float(cp.get("psiThreshold", 0.25)),
            fill_delta_threshold=float(cp.get("fillDeltaThreshold", 0.25)),
            label_delta_threshold=float(cp.get("labelDeltaThreshold",
                                               0.25)),
            consecutive_windows=int(cp.get("consecutiveWindows", 2)),
            cooldown_windows=int(cp.get("cooldownWindows", 2)))
        loop = ContinuousLoop(
            self.workflow, stream_dir, state_dir,
            model_id=cp.get("modelId", "live"),
            pattern=cp.get("pattern", "*"),
            initial_model=initial_model,
            reference_path=cp.get("referencePath"),
            drift=drift,
            window_batches=int(cp.get("windowBatches", 4)),
            max_buffer_batches=int(cp.get("maxBufferBatches", 8)),
            poll_interval_s=float(cp.get("pollIntervalS", 1.0)),
            timeout_s=(float(cp["timeoutS"]) if "timeoutS" in cp
                       else None),
            max_windows=(int(cp["maxWindows"]) if "maxWindows" in cp
                         else None),
            max_retrain_attempts=int(cp.get("maxRetrainAttempts", 3)),
            shadow_tolerance=float(cp.get("shadowTolerance", 1.0)),
            staleness_bound_s=(float(cp["stalenessBoundS"])
                               if "stalenessBoundS" in cp else None),
            metrics_port=(int(cp["metricsPort"]) if "metricsPort" in cp
                          else None),
            access_log_sample=float(cp.get("accessLogSample", 0.0)),
            slo=cp.get("sloConfig"),
            events_spill=bool(cp.get("eventsSpill", True)))
        result["continuous"] = loop.run()
        result["stateDir"] = state_dir

    def _run_scaleout(self, params: OpParams, result: dict,
                      checkpoint_dir: Optional[str]) -> None:
        """SCALEOUT: replay the reader's rows through a live
        router + replica-worker stack over HTTP — every row takes the
        full multi-process path (router hash/spill, replica admission,
        micro-batched compiled scoring). The reader materializes ONE
        model's predictor columns, so ``customParams.defaultModel``
        names the replay target when more than one model is
        registered (same contract as the SERVE fleet replay)."""
        import http.client

        from transmogrifai_tpu.scaleout.stack import ScaleoutStack
        cp = dict(params.custom_params or {})
        model_dir = cp.get("modelDir")
        if not model_dir:
            raise ValueError("scaleout requires customParams.modelDir")
        state_dir = cp.get("stateDir") or checkpoint_dir
        if not state_dir:
            raise ValueError("scaleout requires a state root: pass "
                             "--checkpoint-dir or customParams.stateDir")
        stack = ScaleoutStack(
            model_dir, state_dir,
            replicas=int(cp.get("replicas", 2)),
            spill=int(cp.get("spill", 2)),
            worker_args=["--max-batch", str(cp.get("maxBatch", 64)),
                         "--queue-capacity",
                         str(cp.get("queueCapacity", 256))])
        ids = sorted(
            d for d in os.listdir(model_dir)
            if os.path.isdir(os.path.join(model_dir, d))
            and not d.startswith("_"))
        target = cp.get("defaultModel") or \
            (ids[0] if len(ids) == 1 else None)
        if target is None:
            raise ValueError(
                f"modelDir holds {len(ids)} models ({', '.join(ids)}); "
                "customParams.defaultModel must name the replay target")
        from transmogrifai_tpu.workflow import load_model
        from transmogrifai_tpu.serialization import MODEL_JSON
        tdir = os.path.join(model_dir, target)
        if not os.path.exists(os.path.join(tdir, MODEL_JSON)):
            versions = sorted(v for v in os.listdir(tdir)
                              if os.path.exists(os.path.join(
                                  tdir, v, MODEL_JSON)))
            if not versions:
                raise ValueError(f"no saved model under {tdir!r}")
            tdir = os.path.join(tdir, versions[0])
        ref = load_model(tdir)
        reader = (self.scoring_reader_factory(params)
                  if self.scoring_reader_factory else self.workflow.reader)
        predictors = [f for f in ref.raw_features if not f.is_response]
        frame = reader.generate_frame(predictors)
        n_rows = n_errors = 0
        #: whole-replay wall bound: a fleet that never becomes routable
        #: (every replica crash-looping) must fail the run loudly, not
        #: retry one row forever
        replay_deadline = time.monotonic() + float(
            cp.get("replayTimeoutS", 600.0))
        with profiler.phase(OpStep.SCORING):
            stack.start()
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", stack.port, timeout=60)
                for row in frame.iter_rows():
                    body = json.dumps(row, default=str)
                    while True:
                        if time.monotonic() > replay_deadline:
                            raise RuntimeError(
                                "scaleout replay exceeded "
                                f"{cp.get('replayTimeoutS', 600.0)}s "
                                f"(replicas: {stack.router.replicas()})"
                            )
                        try:
                            conn.request(
                                "POST", f"/score/{target}", body,
                                {"Content-Type": "application/json"})
                            resp = conn.getresponse()
                            resp.read()
                        except OSError:
                            conn.close()
                            time.sleep(0.05)
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", stack.port, timeout=60)
                            continue
                        if resp.status == 503:
                            # router-level shed: wait out the hint and
                            # retry the SAME row — reporting load as an
                            # error slot would misread shed as loss
                            time.sleep(min(float(resp.headers.get(
                                "Retry-After", 0.05)), 0.5))
                            continue
                        break
                    n_rows += 1
                    if resp.status != 200:
                        n_errors += 1
                conn.close()
            finally:
                result["scaleout"] = stack.status()
                stack.stop()
        result["nRows"] = n_rows
        result["nErrors"] = n_errors
        result["rowsByModel"] = {target: n_rows}

    def _serve_fleet(self, params: OpParams, result: dict) -> None:
        """SERVE with ``customParams.modelDir``: replay the reader's rows
        through a multi-model ``FleetServer`` against
        ``customParams.defaultModel`` (required when more than one model
        is registered). The reader materializes exactly the target
        model's predictor columns, so per-row routing keys can't exist
        in this frame — per-request routing is the CLI's and the HTTP
        endpoint's job; the runner replay exercises one model's lane
        inside a live fleet (shared cache, neighbors registered)."""
        from transmogrifai_tpu.serving import FleetServer
        cp = dict(params.custom_params or {})
        queue_capacity = int(cp.get("queueCapacity", 1024))
        fleet = FleetServer(
            max_batch=int(cp.get("maxBatch", 256)),
            max_wait_ms=float(cp.get("maxWaitMs", 2.0)),
            queue_capacity=queue_capacity,
            strict=bool(cp.get("strict", True)),
            retries=int(cp.get("retries", 2)))
        entries = fleet.register_dir(cp["modelDir"])
        if not entries:
            raise ValueError(
                f"no saved models under modelDir {cp['modelDir']!r}")
        ids = fleet.registry.model_ids()
        target = cp.get("defaultModel") or \
            (ids[0] if len(ids) == 1 else None)
        if target is None:
            raise ValueError(
                f"modelDir holds {len(ids)} models ({', '.join(ids)}); "
                "customParams.defaultModel must name the replay target")
        ref = fleet.registry.get(target).model
        reader = (self.scoring_reader_factory(params)
                  if self.scoring_reader_factory else self.workflow.reader)
        predictors = [f for f in ref.raw_features if not f.is_response]
        frame = reader.generate_frame(predictors)
        n_rows = n_errors = 0
        window: list = []

        def _drain() -> None:
            nonlocal n_rows, n_errors
            for item in window:
                if isinstance(item, Exception):
                    n_errors += 1
                else:
                    try:
                        item.result()
                    except Exception:  # noqa: BLE001 — reported per slot below
                        n_errors += 1
                n_rows += 1
            window.clear()

        with profiler.phase(OpStep.SCORING):
            fleet.start()
            try:
                for row in frame.iter_rows():
                    try:
                        window.append(fleet.submit_blocking(target, row))
                    except KeyError as e:  # strict admission reject
                        window.append(e)
                    if len(window) >= queue_capacity:
                        _drain()
                _drain()
            finally:
                # snapshot BEFORE stop: stop() drops the lanes (and
                # their per-model metrics) so a restart builds fresh ones
                result["fleetMetrics"] = fleet.snapshot()
                fleet.stop()
        result["nRows"] = n_rows
        result["nErrors"] = n_errors
        result["rowsByModel"] = {target: n_rows}


def main(argv=None):
    ap = argparse.ArgumentParser("transmogrifai_tpu runner")
    ap.add_argument("--run-type", required=True, choices=RunTypes.ALL)
    ap.add_argument("--params", required=True, help="OpParams json path")
    ap.add_argument("--workflow", required=True,
                    help="import path to a module:attr WorkflowRunner")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="resumable training: fitted DAG layers + the "
                         "selector sweep checkpoint here; re-running after "
                         "a crash resumes instead of refitting (train only)")
    ap.add_argument("--trace-out", default=None,
                    help="profile the run and write a Perfetto/"
                         "chrome://tracing JSON (host span tree + device "
                         "timeline) here")
    args = ap.parse_args(argv)
    import importlib
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    mod, _, attr = args.workflow.partition(":")
    runner: WorkflowRunner = getattr(importlib.import_module(mod), attr)
    result = runner.run(args.run_type, OpParams.from_file(args.params),
                        checkpoint_dir=args.checkpoint_dir,
                        trace_out=args.trace_out)
    print(json.dumps(result, indent=2, default=str))
    return 0 if result.get("status") == "success" else 1


if __name__ == "__main__":
    sys.exit(main())
