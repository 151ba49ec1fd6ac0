"""Observability-overhead microbench: what does span tracing cost?

Runs the SAME fixture pipeline (synthetic binary AutoML: numeric +
categorical features, logistic grid through the ModelSelector, then a
full scoring pass) three ways:

- ``base``   — span recorder disabled: every instrumented call costs one
  attribute check.
- ``spans``  — recorder enabled (the default production state): the full
  hierarchical span tree records through ingest, every DAG stage, the
  sweep, and the fused layer dispatches.
- ``export`` — spans + a ``jax.profiler`` device trace around the run
  (``profiler.reset(trace_dir=...)``: the ``TRACE_ANCHOR`` annotation, the
  phase drains) + ``finalize()`` reading it back (``trace_device_events``
  -> phase ``device_s``, the ``(module, scope)`` table ``device_scopes``,
  the device-window spans' ``device_s``) + the merged chrome-trace JSON
  export (``AppMetrics.export_chrome_trace``) — the ``--trace-out`` /
  ``cli profile`` configuration.

What is on in ``spans`` and ``export`` alike (always-on, host clock): the
span tree (``stage.fit``, ``fe.fused``, ``selector.sweep`` /
``sweep.dispatch`` / ``sweep.family`` / ``sweep.tree_group`` /
``sweep.settle`` / ``selector.refit`` ...), the ``sweep.device`` and
``refit.device`` stamps (one ``is_ready`` + one clock read a sweep
program), and the one ``jax.monitoring`` listener
(``devicewatch.CompileTelemetry``) recording ``compile.program:<site>`` /
``compile.cache_load:<site>`` spans and the ``SweepCounters`` compile
counts. docs/OBSERVABILITY.md names each.

The three configurations run INTERLEAVED for ``TRIALS`` rounds after one
shared warmup (the warmup pays all XLA compiles; fused layer programs
and model fits are jit-cache hits afterwards), and the MIN wall per
configuration is kept: span cost is deterministic host work, so the
noise-free floors are the honest comparison — medians of ~0.2s samples
on a shared box swing more than the effect being measured (single-run
medians here showed a *negative* "overhead" for the heavier config).
The acceptance bound lives in ``scripts/check_artifacts.py``: the
committed artifact's ``spans_overhead_pct`` must stay <= 5%.

Writes ``benchmarks/OBSERVABILITY.json`` (atomic), prints one JSON line.
Run: ``python benchmarks/bench_observability.py``. Knobs: OBS_ROWS,
OBS_TRIALS.
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

ROWS = int(os.environ.get("OBS_ROWS", 4000))
TRIALS = int(os.environ.get("OBS_TRIALS", 7))


def _build_pipeline():
    import numpy as np

    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(11)
    x1 = rng.normal(size=ROWS)
    x2 = rng.normal(size=ROWS)
    x3 = rng.exponential(size=ROWS)
    cat = rng.choice(["a", "b", "c", "d"], size=ROWS)
    logit = 1.2 * x1 - 0.7 * x2 + 0.3 * x3 + (cat == "a") * 1.0
    y = (rng.uniform(size=ROWS) < 1 / (1 + np.exp(-logit))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
        "x3": (ft.Real, x3.tolist()),
        "cat": (ft.PickList, cat.tolist()),
    })

    def run_once() -> None:
        feats = FeatureBuilder.from_frame(frame, response="y")
        label = feats.pop("y")
        features = transmogrify(list(feats.values()), min_support=1)
        sel = BinaryClassificationModelSelector.with_train_validation_split(
            seed=5, models_and_parameters=[
                (OpLogisticRegression(max_iter=25),
                 [{"reg_param": r} for r in (0.0, 0.01)])])
        pred = label.transform_with(sel, features)
        model = (Workflow().set_input_frame(frame)
                 .set_result_features(pred, features).train())
        model.score(frame)

    return run_once


def _measure_interleaved(run_once, configs: dict) -> dict[str, float]:
    """``configs``: name -> (configure, teardown | None). Runs one trial
    of every configuration per round (interleaving decorrelates slow
    machine drift from the config being measured) and keeps each
    configuration's minimum wall."""
    walls: dict[str, list[float]] = {name: [] for name in configs}
    for _ in range(TRIALS):
        for name, (configure, teardown) in configs.items():
            configure()
            t0 = time.perf_counter()
            run_once()
            walls[name].append(time.perf_counter() - t0)
            if teardown is not None:
                teardown()
    return {name: min(w) for name, w in walls.items()}


def main() -> int:
    import jax

    from transmogrifai_tpu.utils.profiling import profiler
    from transmogrifai_tpu.utils.tracing import recorder

    platform = jax.devices()[0].platform
    run_once = _build_pipeline()

    # shared warmup: pay every XLA compile before any measured trial
    recorder.enable(False)
    run_once()

    trace_dir = tempfile.mkdtemp(prefix="obs_bench_trace_")
    trace_out = os.path.join(trace_dir, "trace.json")
    span_counts: list[int] = []
    trial_ix = {"n": 0}

    def spans_on():
        recorder.enable(True)
        profiler.reset(app_name="bench_observability")

    def spans_teardown():
        span_counts.append(len(recorder.spans))

    def export_on():
        # a FRESH trace dir per trial: each trial reads back its own
        # trace file and nothing of an earlier trial's
        trial_ix["n"] += 1
        recorder.enable(True)
        profiler.reset(app_name="bench_observability",
                       trace_dir=os.path.join(trace_dir,
                                              f"xplane_{trial_ix['n']}"))

    def export_teardown():
        metrics = profiler.finalize()
        metrics.export_chrome_trace(trace_out)

    import shutil
    try:
        floors = _measure_interleaved(run_once, {
            "base": (lambda: recorder.enable(False), None),
            "spans": (spans_on, spans_teardown),
            "export": (export_on, export_teardown),
        })
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    base_s, spans_s, export_s = (floors["base"], floors["spans"],
                                 floors["export"])
    span_count = max(span_counts)
    recorder.enable(True)

    def pct(wall: float) -> float:
        return round((wall / base_s - 1.0) * 100.0, 2)

    artifact = {
        "metric": "observability_overhead",
        "platform": platform,
        "rows": ROWS,
        "trials": TRIALS,
        "base_wall_s": round(base_s, 4),
        "spans_wall_s": round(spans_s, 4),
        "export_wall_s": round(export_s, 4),
        "spans_overhead_pct": pct(spans_s),
        "export_overhead_pct": pct(export_s),
        "span_count": span_count,
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out = os.path.join(HERE, "OBSERVABILITY.json")
    tmp = out + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out)
    print(json.dumps(artifact))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
