"""Benchmark: end-to-end AutoML wall-clock on a HIGGS-shaped task.

North star (BASELINE.json): transmogrify + sanityCheck + 3-fold
BinaryClassificationModelSelector on HIGGS-11M, one TPU chip vs a 32-vCPU
Spark reference. HIGGS itself is not fetchable here (zero egress), so the
bench runs the same pipeline shape on synthetic HIGGS-like data (28 numeric
features, binary label, nonlinear signal).

The sweep is the DEFAULT binary candidate set (selector/factories.py):
8-point LR grid + 4-point linear SVC + RandomForest (50 trees, depth 6/12)
+ GBT (50 rounds, depth 3/6).

On success prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "s", "vs_baseline": null,
   "platform": "tpu", "device_kind": "...", "device_count": N,
   "device_time_breakdown": {...}, "scaling_curve": [...], ...}

value        = wall seconds for the full AutoML pipeline at N_ROWS on the TPU.
vs_baseline  = null: no baseline measured on the same clock exists yet
               (ROADMAP A1 rebuilds this file as a set of cells).
device_time_breakdown = per-OpStep wall + device-busy seconds parsed from a
               jax.profiler trace of the run (utils/profiling.py), plus
               analytic training FLOPs for the linear and tree trainers.

Process model: the parent NEVER imports jax (a parent that touched jax
would hold the chip its child needs); each measurement runs in ONE child
subprocess at a time. A child that is not on a TPU, fails, or times out
makes this script exit non-zero and print no result — a measurement path
that finds no chip fails; it does not fall back to the CPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

N_ROWS = int(os.environ.get("BENCH_ROWS", 4_000_000))
CHILD_TIMEOUT = int(os.environ.get("BENCH_CHILD_TIMEOUT", 3000))
#: extra row counts for the scaling curve ("" disables)
CURVE = [int(x) for x in
         os.environ.get("BENCH_CURVE", "1000000,2000000").split(",") if x]
#: "full" = default candidate set (LR+SVC+RF+GBT); "lr" = LR-only smoke
MODELS = os.environ.get("BENCH_MODELS", "full")
D = 28
HERE = os.path.dirname(os.path.abspath(__file__))
#: where the measuring child's watchdog writes its stall autopsies
INCIDENT_DIR = os.path.join(HERE, ".bench_incidents")


def make_data(n: int, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, D)).astype("float32")
    logits = (1.2 * X[:, 0] - 0.7 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
              + 0.8 * np.sin(X[:, 4]) - 0.4 * (X[:, 5] ** 2 - 1.0))
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype("float64")
    return X, y


def _candidates():
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    if MODELS == "lr":
        return [(OpLogisticRegression(),
                 [{"reg_param": r, "elastic_net_param": e}
                  for r in (0.0, 0.01, 0.1, 0.2) for e in (0.0, 0.5)])]
    if MODELS == "gbt":  # tree-family isolation
        from transmogrifai_tpu.models.trees import OpGBTClassifier
        return [(OpGBTClassifier(),
                 [{"num_rounds": 50, "max_depth": d} for d in (3, 6)])]
    if MODELS == "rf":
        from transmogrifai_tpu.models.trees import OpRandomForestClassifier
        return [(OpRandomForestClassifier(),
                 [{"num_trees": 50, "max_depth": d} for d in (6, 12)])]
    return None  # factories default: LR + SVC + RF + GBT


def run_pipeline(n_rows: int, trace: bool = False,
                 checkpoint_dir: str | None = None) -> dict:
    """Full pipeline: frame ingest -> transmogrify -> sanity check ->
    3-fold default-candidate sweep -> winner refit + holdout evaluation.
    Returns the measurement dict plus the fitted ``model`` and the input
    ``frame`` (wall excludes data synthesis). Also driven by
    ``chip_smoke.py`` — there is no second copy of this pipeline — which
    passes a fresh ``checkpoint_dir`` to read the per-fold metric vectors
    the sweep checkpoints there."""
    import jax
    import numpy as np
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter,
    )
    from transmogrifai_tpu.utils import flops
    from transmogrifai_tpu.utils.profiling import profiler, sweep_counters
    from transmogrifai_tpu.workflow import Workflow
    from transmogrifai_tpu.types import feature_types as ft

    device = jax.devices()[0]  # forces backend init up front

    X, y = make_data(n_rows)
    cols = {f"f{i}": fr.HostColumn(ft.Real, X[:, i].astype(np.float64),
                                   np.ones(n_rows, bool))
            for i in range(D)}
    cols["label"] = fr.HostColumn(ft.RealNN, y, np.ones(n_rows, bool))
    frame = fr.HostFrame(cols)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    flops.reset()
    metrics = profiler.reset(app_name="bench", trace_dir=trace_dir)

    t0 = time.time()
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    features = transmogrify(list(feats.values()))
    checked = label.transform_with(SanityChecker(), features)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=42, models_and_parameters=_candidates(),
        splitter=DataSplitter(reserve_test_fraction=0.1, seed=42),
        checkpoint_dir=checkpoint_dir)
    pred = label.transform_with(selector, checked)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred).train())
    wall = time.time() - t0
    profiler.finalize()

    s = model.selector_summary()
    holdout = s.holdout_evaluation.get("binary classification", {})
    auroc = float(holdout.get("au_roc", float("nan")))
    phases = {
        k: {"wall_s": round(p.wall_s, 3),
            "device_s": round(p.device_s, 3), "count": p.count}
        for k, p in metrics.phases.items()}
    print(f"# rows={n_rows} wall={wall:.1f}s platform={device.platform} "
          f"holdout_auROC={auroc:.4f} best={s.best_model_name}",
          file=sys.stderr)
    if trace:
        print(f"# phases: {json.dumps(phases)}", file=sys.stderr)
    return {"wall": wall, "auroc": auroc, "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
            "best": s.best_model_name, "phases": phases,
            "flops": flops.totals(),
            "peak_flops": flops.peak_flops_per_s(),
            "sweep_counters": sweep_counters.to_json(),
            "sweep_run_counters": sweep_counters.run_to_json(),
            "model": model, "frame": frame}


def _child_main() -> int:
    import jax
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"# bench child: jax.default_backend() is {platform!r}, not "
              "'tpu'; refusing to measure", file=sys.stderr)
        return 3
    # measurement children run under the dispatch watchdog: a stalled
    # settle/collective/dispatch autopsies itself (thread stacks, pending
    # dispatches, HBM census) into .bench_incidents/ before the parent's
    # timeout fires — a hang produces a diagnosis, not a dead window
    from transmogrifai_tpu.utils import devicewatch
    devicewatch.configure(incident_dir=INCIDENT_DIR)
    result = run_pipeline(int(os.environ["_BENCH_CHILD_ROWS"]),
                          trace=os.environ.get("_BENCH_TRACE") == "1")
    del result["model"], result["frame"]
    print("BENCH_CHILD_RESULT " + json.dumps(result))
    return 0


def _run_child(rows: int, label: str, trace: bool = False) -> dict | None:
    """Run one measurement in a subprocess. Returns the result dict, or
    None when the child failed, timed out or printed no result."""
    env = dict(os.environ, _BENCH_CHILD="1", _BENCH_CHILD_ROWS=str(rows),
               **({"_BENCH_TRACE": "1"} if trace else {}))
    child_t0 = time.time()
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=CHILD_TIMEOUT, cwd=HERE)
    except subprocess.TimeoutExpired:
        print(f"# [{label}] timed out after {CHILD_TIMEOUT}s",
              file=sys.stderr)
        # only incidents written by THIS child (mtime >= its start):
        # .bench_incidents persists across runs, and a stale dump
        # misattributed to this hang would send the operator to the
        # wrong stall site
        inc_dir = os.path.join(INCIDENT_DIR, "incidents")
        try:
            fresh = [f for f in sorted(os.listdir(inc_dir))
                     if os.path.getmtime(os.path.join(inc_dir, f))
                     >= child_t0]
        except OSError:
            fresh = []
        if fresh:
            print(f"# [{label}] devicewatch incident: "
                  f"{os.path.join(inc_dir, fresh[-1])}", file=sys.stderr)
        return None
    sys.stderr.write(out.stderr[-3000:])
    for line in out.stdout.splitlines():
        if line.startswith("BENCH_CHILD_RESULT "):
            return json.loads(line[len("BENCH_CHILD_RESULT "):])
    print(f"# [{label}] rc={out.returncode}, no result", file=sys.stderr)
    return None


def _device_breakdown(accel: dict) -> dict:
    """Assemble the result's device_time_breakdown from a measured child
    result: per-phase wall/device seconds + achieved FLOP/s attribution."""
    phases = accel.get("phases") or {}
    fl = accel.get("flops") or {}
    out: dict = {"phases": phases}
    train_device = sum(p.get("device_s", 0.0) for k, p in phases.items()
                      if k in ("CrossValidation", "ModelTraining"))
    total_device = sum(p.get("device_s", 0.0) for p in phases.values())
    out["total_device_s"] = round(total_device, 3)
    out["train_device_s"] = round(train_device, 3)
    out["train_flops_estimate"] = {k: round(v) for k, v in fl.items()}
    if train_device > 0 and fl:
        achieved = sum(fl.values()) / train_device
        out["achieved_train_flops_per_s"] = round(achieved)
        peak = accel.get("peak_flops")
        if peak:
            out["mfu_vs_bf16_peak"] = round(achieved / peak, 5)
    if accel.get("sweep_counters"):
        # per-family sweep observability (utils/profiling.SweepCounters):
        # mode (fold_stacked vs fold_loop), compiles, device dispatches,
        # host syncs — the fast path reads hostSyncs == 1 per family
        out["sweep"] = accel["sweep_counters"]
    return out


def main() -> int:
    if os.environ.get("_BENCH_CHILD"):
        return _child_main()

    accel = _run_child(N_ROWS, "measurement", trace=True)
    if accel is None:
        return 1
    curve = [{"rows": N_ROWS, "wall_s": round(accel["wall"], 2)}]
    for rows in CURVE:
        if rows == N_ROWS:
            continue
        r = _run_child(rows, f"curve {rows}")
        if r is None:
            return 1
        curve.append({"rows": rows, "wall_s": round(r["wall"], 2)})
    curve.sort(key=lambda c: c["rows"])

    print(json.dumps({
        "metric": f"automl_higgs_shape_{N_ROWS // 1_000_000}m_wall",
        "value": round(accel["wall"], 2), "unit": "s", "vs_baseline": None,
        "platform": accel["platform"],
        "device_kind": accel["device_kind"],
        "device_count": accel["device_count"],
        "holdout_auroc": round(accel["auroc"], 4),
        "best_model": accel["best"], "models": MODELS,
        "device_time_breakdown": _device_breakdown(accel),
        "scaling_curve": curve}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
