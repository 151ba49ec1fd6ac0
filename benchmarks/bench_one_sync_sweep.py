"""One-sync sweep microbench (host-fetch fenced, whole-train walls).

Times a full AutoML ``train()`` — transmogrify + k-fold CV sweep over two
stacked linear families + winner refit + train/holdout evaluation — three
ways (round 9):

- ``per_family_settle`` — ``TRANSMOGRIFAI_SWEEP_ASYNC=0``: every family's
  metric batch is pulled as soon as it dispatches (the r08 behavior; one
  blocking host sync per family), cold refit.
- ``one_sync``          — the async dispatch/settle collapse: every
  family's stacked program launches before the first host sync, the whole
  sweep settles behind a single ``jax.block_until_ready``; cold refit.
- ``one_sync_warm``     — one-sync plus the stacked warm-started winner
  refit (fold-averaged init through the donated-buffer program).

The structural claims ride in the artifact and are schema-asserted by
``scripts/check_artifacts.py``: ``total_host_syncs.one_sync == 1`` (vs one
per family on the per-family path) from ``SweepCounters.run_to_json``, and
``refit_parity`` — the max |warm - cold| train/holdout metric delta —
within 1e-5 (the sweep is a converged convex regression, where the warm
init lands on the same optimum). The headline wall win is dispatch/settle
latency (families overlap on device; not measured on the attached
chip); on CPU the three walls are expected close.

Writes ``benchmarks/ONE_SYNC_SWEEP.json`` and prints one JSON line. Run:
``python benchmarks/bench_one_sync_sweep.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

ROWS = int(os.environ.get("SWEEP_ROWS", 60_000))
FOLDS = int(os.environ.get("SWEEP_FOLDS", 3))
D = int(os.environ.get("SWEEP_COLS", 8))       # raw feature columns
N_GRID = int(os.environ.get("SWEEP_GRID", 8))  # LinReg reg_param points
#: enough Adam steps that cold and fold-averaged-warm inits both converge
#: to the optimum of the (convex) squared loss — the refit-parity bound
#: in the artifact depends on it
MAX_ITER = int(os.environ.get("SWEEP_MAX_ITER", 400))
REPEATS = int(os.environ.get("SWEEP_REPEATS", 1))


def _build(frame_cls, ft, seed=0):
    rng = np.random.default_rng(seed)
    cols = {}
    X = rng.normal(size=(ROWS, D)).astype(np.float32)
    beta = rng.normal(size=D).astype(np.float32)
    y = X @ beta + 0.05 * rng.normal(size=ROWS).astype(np.float32)
    for j in range(D):
        cols[f"x{j}"] = (ft.Real, X[:, j].tolist())
    cols["label"] = (ft.RealNN, y.tolist())
    return frame_cls.from_dict(cols)


def _train_once(frame):
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.extras import (
        OpGeneralizedLinearRegression,
    )
    from transmogrifai_tpu.models.linear import OpLinearRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        DataSplitter, RegressionModelSelector,
    )
    from transmogrifai_tpu.uid import UID
    from transmogrifai_tpu.workflow import Workflow
    UID.reset()
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    vec = transmogrify(list(feats.values()), min_support=1)
    sel = RegressionModelSelector.with_cross_validation(
        n_folds=FOLDS, seed=1,
        models_and_parameters=[
            (OpLinearRegression(max_iter=MAX_ITER),
             [{"reg_param": r}
              for r in np.linspace(0.0, 0.2, N_GRID).round(6)]),
            (OpGeneralizedLinearRegression(max_iter=MAX_ITER),
             [{"reg_param": r} for r in (0.0, 0.1)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    pred = label.transform_with(sel, vec)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred).train())
    return model.selector_summary()


def _flat_metrics(summary) -> dict:
    out = {}
    for block in ("train_evaluation", "holdout_evaluation"):
        for ev_name, metrics in getattr(summary, block).items():
            for m, v in metrics.items():
                if isinstance(v, (int, float)) and v is not None:
                    out[f"{block}.{ev_name}.{m}"] = float(v)
    return out


def main() -> int:
    import jax
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.profiling import sweep_counters

    platform = jax.devices()[0].platform
    os.environ["TRANSMOGRIFAI_SWEEP_STACKED"] = "1"
    frame = _build(fr.HostFrame, ft)

    modes = {
        "per_family_settle": {"TRANSMOGRIFAI_SWEEP_ASYNC": "0",
                              "TRANSMOGRIFAI_REFIT_WARM": "0"},
        "one_sync": {"TRANSMOGRIFAI_SWEEP_ASYNC": "1",
                     "TRANSMOGRIFAI_REFIT_WARM": "0"},
        "one_sync_warm": {"TRANSMOGRIFAI_SWEEP_ASYNC": "1",
                          "TRANSMOGRIFAI_REFIT_WARM": "1"},
    }
    _train_once(frame)  # warmup: burn every mode-shared compile

    walls, syncs, summaries, runs = {}, {}, {}, {}
    for mode, env in modes.items():
        for k, v in env.items():
            os.environ[k] = v
        ts = []
        for _ in range(REPEATS):
            sweep_counters.reset()
            t0 = time.perf_counter()
            summaries[mode] = _train_once(frame)
            ts.append(time.perf_counter() - t0)
            runs[mode] = sweep_counters.run_to_json()
        walls[mode] = float(np.median(ts))
        syncs[mode] = runs[mode]["sweepHostSyncs"]
        for k in env:
            del os.environ[k]

    # parity: the sweep's validation metrics must be identical across
    # modes; the warm refit's train/holdout metrics within 1e-5 of cold
    val = {}
    for mode, s in summaries.items():
        val[mode] = {r.model_name: dict(r.metric_values)
                     for r in s.validation_results}
    v_par = 0.0
    for name in val["per_family_settle"]:
        for m in val["per_family_settle"][name]:
            for mode in ("one_sync", "one_sync_warm"):
                v_par = max(v_par, abs(val[mode][name][m]
                                       - val["per_family_settle"][name][m]))
    cold = _flat_metrics(summaries["one_sync"])
    warm = _flat_metrics(summaries["one_sync_warm"])
    r_par = max((abs(warm[k] - cold[k]) for k in cold), default=0.0)

    result = {
        "metric": "one_sync_sweep",
        "unit": "s",
        "platform": platform,
        "rows": ROWS, "cols": D, "folds": FOLDS,
        "grid_points": N_GRID + 2, "families": 2,
        "max_iter": MAX_ITER,
        "per_family_settle_s": round(walls["per_family_settle"], 3),
        "one_sync_s": round(walls["one_sync"], 3),
        "one_sync_warm_refit_s": round(walls["one_sync_warm"], 3),
        "speedup_vs_per_family": round(
            walls["per_family_settle"] / walls["one_sync"], 3),
        "total_host_syncs": {mode: int(s) for mode, s in syncs.items()},
        "async_families": runs["one_sync"]["asyncFamilies"],
        "refit_warm_starts": runs["one_sync_warm"]["refitWarmStarts"],
        "validation_parity": v_par,
        "refit_parity": r_par,
        "winner": summaries["one_sync"].best_model_name,
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "ONE_SYNC_SWEEP.json")
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
