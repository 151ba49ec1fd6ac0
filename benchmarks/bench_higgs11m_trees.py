"""HIGGS-11M-scale single-chip tree-fit probe (host-fetch fenced).

The north star (BASELINE.json) is the full AutoML pipeline on HIGGS-11M
on a v5e-8; this rig exposes ONE chip, so the headline bench runs at 4M
(bench.py). This probe supplies the scale evidence the curve cannot:
one OpGBTClassifier (50 rounds, depth 6) and one OpRandomForestClassifier
(50 trees, depth 12) fit at HIGGS row count x 28 features on the single
chip, through the real estimator surface (auto-selected sorted engine,
chunked ingest). Writes ``benchmarks/HIGGS11M_TREES.json``.

Run: python benchmarks/bench_higgs11m_trees.py  (HIGGS_ROWS overrides)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

ROWS = int(os.environ.get("HIGGS_ROWS", 11_000_000))
D = 28


def main() -> int:
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.models.trees import (
        OpGBTClassifier, OpRandomForestClassifier,
    )
    from transmogrifai_tpu.pipeline_data import _upload_rows

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    X = rng.normal(size=(ROWS, D)).astype(np.float32)
    logits = (1.2 * X[:, 0] - 0.7 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
              + 0.8 * np.sin(X[:, 4]))
    y = (rng.uniform(size=ROWS) < 1.0 / (1.0 + np.exp(-logits))
         ).astype(np.float64)

    from _timing import fence

    t0 = time.time()
    Xj = _upload_rows(X)          # chunked transfer (the 4M crash fix)
    yj = _upload_rows(y)
    w = jnp.ones(ROWS)
    fence((Xj, yj))
    upload_s = time.time() - t0

    results = {"metric": "higgs11m_single_chip_tree_fits", "rows": ROWS,
               "features": D, "platform": platform,
               "upload_s": round(upload_s, 1),
               "fencing": "block_until_ready", "fits": []}
    for est, label in ((OpGBTClassifier(num_rounds=50, max_depth=6),
                        "gbt_50x_d6"),
                       (OpRandomForestClassifier(num_trees=50, max_depth=12),
                        "rf_50x_d12")):
        t0 = time.time()
        model = est.fit_arrays(Xj, yj, w, est.params)
        fence(model.trees)
        wall = time.time() - t0
        results["fits"].append({"model": label,
                                "wall_s": round(wall, 1)})
        print(f"# {label}: {wall:.1f}s", file=sys.stderr)

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "HIGGS11M_TREES.json")
    with open(out, "w") as fh:
        json.dump(results, fh, indent=2)
    print(json.dumps(results))
    return 0


if __name__ == "__main__":
    sys.exit(main())
