"""Fold x grid-stacked TREE sweep microbench (host-fetch fenced).

Times one tree-family (fold x grid) CV sweep unit — train every grid
lane on every fold from the dataset-level bin codes, score the
validation folds, pull the metric batch — at ``SWEEP_ROWS`` x
``SWEEP_COLS`` x ``SWEEP_BINS``, three ways:

- ``per_point``    — per-fold loop with sequential per-grid-point fits
  and per-model scoring + metric pulls: the base ``Predictor`` contract
  (no batching at all; k x L dispatches and k x L host syncs).
- ``per_fold``     — per-fold loop with the family's bin-once
  ``grid_fit_arrays`` and the same-shape batched scorer + one metric
  sync per fold: the pre-round-8 tree sweep (k dispatches, k syncs).
- ``tree_stacked`` — this PR: the whole k folds x L lanes depth-group as
  ONE compiled program (``tree_stack_scores``) + the fold-batched
  metric: one dispatch and ONE host sync for the group.

Writes ``benchmarks/TREE_STACKED_SWEEP.json`` and prints one JSON line.
The stacked path's headline win is dispatch/host-sync latency (k x L
fewer host syncs — not measured on the attached chip); the recorded
``host_syncs``/``dispatches`` blocks are the structural counts at the
selector's accounting granularity (``SweepCounters``), which is what
the gating default is argued from. The CPU default only flips ON if
``speedup_vs_per_fold`` measures >= 1.0 here. Run:
``python benchmarks/bench_tree_stacked_sweep.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

ROWS = int(os.environ.get("SWEEP_ROWS", 100_000))
FOLDS = int(os.environ.get("SWEEP_FOLDS", 3))
D = int(os.environ.get("SWEEP_COLS", 28))
BINS = int(os.environ.get("SWEEP_BINS", 64))
ROUNDS = int(os.environ.get("SWEEP_ROUNDS", 10))
DEPTH = int(os.environ.get("SWEEP_DEPTH", 6))
#: one depth-group of same-shape lanes (the default AutoML tree grids
#: vary learning_rate/reg_lambda inside a depth far more often than
#: depth itself once grouped)
N_GRID = int(os.environ.get("SWEEP_GRID", 4))
REPEATS = int(os.environ.get("SWEEP_REPEATS", 1))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.evaluators.binary import (
        OpBinaryClassificationEvaluator,
    )
    from transmogrifai_tpu.models.trees import OpGBTClassifier
    from transmogrifai_tpu.selector.validator import OpCrossValidation

    platform = jax.devices()[0].platform
    grid = [{"learning_rate": lr, "reg_lambda": rl}
            for lr in (0.1, 0.3) for rl in (0.5, 1.0)][:N_GRID]
    est = OpGBTClassifier(num_rounds=ROUNDS, max_depth=DEPTH,
                          max_bins=BINS)
    ev = OpBinaryClassificationEvaluator()

    rng = np.random.default_rng(0)
    Xh = rng.normal(size=(ROWS, D)).astype(np.float32)
    logits = 1.2 * Xh[:, 0] - 0.7 * Xh[:, 1] + 0.5 * Xh[:, 2] * Xh[:, 3]
    yh = (rng.uniform(size=ROWS) < 1.0 / (1.0 + np.exp(-logits))
          ).astype(np.float32)
    X = jnp.asarray(Xh)
    y = jnp.asarray(yh)
    w = jnp.ones(ROWS, jnp.float32)
    tr_idx, va_idx = OpCrossValidation(n_folds=FOLDS).stacked_splits(ROWS)
    jtr, jva = jnp.asarray(tr_idx), jnp.asarray(va_idx)

    plan = est.fold_sweep_plan(X, grid)
    _, codes, _ = plan[BINS]
    if BINS <= 127:
        codes = codes.astype(jnp.int8)
    lnb = est.tree_stack_scalar_lnb(y)
    group = est.tree_stack_groups(grid)[0]

    def per_point():
        """Per-fold loop, base-contract sequential per-point fits with
        per-model scoring + metric pulls (k x L syncs)."""
        vals = []
        for f in range(FOLDS):
            Xtr, ytr, wtr = X[jtr[f]], y[jtr[f]], w[jtr[f]]
            fold = []
            for g in grid:
                m = est.fit_arrays(Xtr, ytr, wtr, {**est.params, **g})
                pred = m.predict_arrays(X[jva[f]])
                fold.append(ev.metric_from_arrays(y[jva[f]], pred, "auPR"))
            vals.append(fold)
        return np.asarray(vals)

    def per_fold():
        """Per-fold loop, bin-once grid trainer + same-shape batched
        scorer + one metric sync per fold (the r06 tree sweep)."""
        vals = []
        for f in range(FOLDS):
            Xtr, ytr, wtr = X[jtr[f]], y[jtr[f]], w[jtr[f]]
            models = est.grid_fit_arrays(Xtr, ytr, wtr, grid,
                                         _fold_plan=plan,
                                         _fold_rows=jtr[f])
            scores = est.grid_predict_scores(models, X[jva[f]])
            vals.append(ev.metric_batch_scores(y[jva[f]], scores, "auPR"))
        return np.stack(vals)

    def tree_stacked():
        """This PR: one fused stacked train+score for the whole depth-
        group + one fold-batched metric pull (the selector fast path's
        exact unit)."""
        scores = est.tree_stack_scores(
            jnp.take(codes, jtr, axis=0), jnp.take(y, jtr, axis=0),
            jnp.take(w, jtr, axis=0), jnp.take(codes, jva, axis=0),
            group["params"], lnb)
        return np.asarray(ev.metric_batch_scores_folds(
            jnp.take(y, jva, axis=0), scores, "auPR"))

    def timed(fn):
        out0 = fn()  # warmup/compile burn; metric pulls fence the device
        ts = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), out0

    t_stacked, m_stacked = timed(tree_stacked)
    t_fold, m_fold = timed(per_fold)
    t_point, m_point = timed(per_point)
    parity = float(np.max(np.abs(m_stacked - np.asarray(m_fold))))
    parity_exact = float(np.max(np.abs(m_stacked - m_point)))

    result = {
        "metric": "tree_stacked_sweep",
        "unit": "s",
        "platform": platform,
        "rows": ROWS, "cols": D, "bins": BINS, "folds": FOLDS,
        "grid_points": len(grid), "rounds": ROUNDS, "depth": DEPTH,
        "groups": 1,
        "tree_stacked_s": round(t_stacked, 3),
        "per_fold_s": round(t_fold, 3),
        "per_point_s": round(t_point, 3),
        "speedup_vs_per_fold": round(t_fold / t_stacked, 2),
        "speedup_vs_per_point": round(t_point / t_stacked, 2),
        "metric_parity_stacked_vs_per_fold": parity,
        "metric_delta_stacked_vs_exact_per_point": parity_exact,
        # structural counts at the SweepCounters accounting granularity
        "dispatches": {"tree_stacked": 1, "per_fold": FOLDS,
                       "per_point": FOLDS * len(grid)},
        "host_syncs": {"tree_stacked": 1, "per_fold": FOLDS,
                       "per_point": FOLDS * len(grid)},
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "TREE_STACKED_SWEEP.json")
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
