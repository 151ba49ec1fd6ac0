"""Fold-stacked ModelSelector sweep microbench (host-fetch fenced).

Times one linear-family (fold x grid) CV sweep unit — train every grid
point on every fold, score the validation folds, pull the metric batch —
at ``SWEEP_ROWS`` x 28, three ways:

- ``per_point``   — per-fold loop with sequential per-grid-point fits:
  the base ``Predictor.grid_fit_arrays`` contract (no batching at all).
- ``per_fold``    — per-fold loop with the family's grid-vmapped trainer
  and one metric host sync per fold: the pre-fold-stacking ``_sweep``
  fast path (r05 behavior).
- ``fold_stacked`` — this PR: all k folds x |grid| points as ONE compiled
  program via ``grid_fit_arrays_folds`` + the fold-batched metric, one
  dispatch and ONE host sync for the whole family.

Writes ``benchmarks/FOLD_STACKED_SWEEP.json`` and prints one JSON line.
The stacked path's headline win is dispatch/host-sync latency (k x fewer
host syncs — not measured on the attached chip); on CPU the win comes from
batching the per-point programs, so the honest CPU ratio to watch is
``speedup_vs_per_point`` (the unbatched estimator contract). Run:
``python benchmarks/bench_fold_stacked_sweep.py``.
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
#: transmogrified feature width — one-hot/hashed expansions land real
#: AutoML matrices near this, and it is where the per-point loop's
#: repeated X reads dominate (at the HIGGS bench's raw d=28 the loop is
#: bound by per-candidate intermediates instead and the gap narrows)
D = int(os.environ.get("SWEEP_COLS", 128))
REPEATS = int(os.environ.get("SWEEP_REPEATS", 1))
#: a 16-point elastic-net LR sweep: L1 grid points take the first-order
#: Adam path (the Newton shortcut covers only pure-L2 binary), so every
#: point trains the full ``max_iter`` scan — the shape where the
#: fold x grid batching matters and a real AutoML elastic-net sweep runs
N_GRID = int(os.environ.get("SWEEP_GRID", 16))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.evaluators.binary import (
        OpBinaryClassificationEvaluator,
    )
    from transmogrifai_tpu.models.base import Predictor
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.selector.validator import OpCrossValidation

    platform = jax.devices()[0].platform
    grid = [{"reg_param": r, "elastic_net_param": 0.5}
            for r in np.linspace(0.0, 0.2, N_GRID).round(6)]
    est = OpLogisticRegression()  # default max_iter=200
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

    def per_point():
        """Per-fold loop, base-contract sequential per-point fits."""
        vals = []
        for f in range(FOLDS):
            Xtr, ytr, wtr = X[jtr[f]], y[jtr[f]], w[jtr[f]]
            models = Predictor.grid_fit_arrays(est, Xtr, ytr, wtr, grid)
            scores = est.grid_predict_scores(models, X[jva[f]])
            vals.append(ev.metric_batch_scores(y[jva[f]], scores, "auPR"))
        return np.stack(vals)

    def per_fold():
        """Per-fold loop, grid-vmapped family trainer (r05 fast path)."""
        vals = []
        for f in range(FOLDS):
            Xtr, ytr, wtr = X[jtr[f]], y[jtr[f]], w[jtr[f]]
            models = est.grid_fit_arrays(Xtr, ytr, wtr, grid)
            scores = est.grid_predict_scores(models, X[jva[f]])
            vals.append(ev.metric_batch_scores(y[jva[f]], scores, "auPR"))
        return np.stack(vals)

    def fold_stacked():
        """This PR: one fused stacked train+score + one fold-batched
        metric pull (the selector fast path's exact unit)."""
        Xtr = jnp.take(X, jtr, axis=0)
        ytr = jnp.take(y, jtr, axis=0)
        wtr = jnp.take(w, jtr, axis=0)
        scores = est.grid_scores_folds(Xtr, ytr, wtr, grid,
                                       jnp.take(X, jva, axis=0))
        return ev.metric_batch_scores_folds(jnp.take(y, jva, axis=0),
                                            scores, "auPR")

    def timed(fn):
        out0 = fn()  # warmup/compile burn; metric pulls fence the device
        ts = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), out0

    t_stacked, m_stacked = timed(fold_stacked)
    t_fold, m_fold = timed(per_fold)
    t_point, m_point = timed(per_point)
    parity = float(np.max(np.abs(np.asarray(m_stacked) - m_fold)))

    result = {
        "metric": f"linear_fold_grid_sweep_{ROWS}",
        "unit": "s",
        "platform": platform,
        "rows": ROWS, "cols": D, "folds": FOLDS, "grid_points": len(grid),
        "fold_stacked_s": round(t_stacked, 3),
        "per_fold_s": round(t_fold, 3),
        "per_point_s": round(t_point, 3),
        "speedup_vs_per_fold": round(t_fold / t_stacked, 2),
        "speedup_vs_per_point": round(t_point / t_stacked, 2),
        "metric_parity_stacked_vs_per_fold": parity,
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "FOLD_STACKED_SWEEP.json")
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
