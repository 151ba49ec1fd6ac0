"""Tree histogram-engine decision microbench.

Times one full ``grow_tree`` per engine at ``HIST_ROWS`` x 28 x 64 for
depths 6 and 12, on whatever backend is live:

- ``scatter``   — flat-index scatter-add (GSPMD-safe mesh path)
- ``sorted``    — sorted-block layout + XLA einsum contraction
- ``sorted+pallas`` — same layout, fused VMEM kernel
  (ops/sorted_hist_pallas.py)

and writes ``benchmarks/HIST_ENGINES.json`` — the artifact behind the
engine defaults in ``models/trees.py`` (``_hist_mode_for`` /
``_sorted_engine_default``). Replaces the round-2..4 PALLAS_HIST.json,
whose numbers were enqueue-time artifacts (unfenced walls).

Run on the chip: ``python benchmarks/bench_hist_engines.py``
(CPU runs measure the interpret/einsum paths and are labeled as such).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

ROWS = int(os.environ.get("HIST_ROWS", 1_000_000))
D = 28
B = 64
DEPTHS = (6, 12)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from _timing import med_fetch
    from transmogrifai_tpu.models.trees import (
        bin_data, grow_tree, quantile_bin_edges,
    )

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(0)
    X = rng.normal(size=(ROWS, D)).astype(np.float32)
    edges = quantile_bin_edges(X, B)
    Xb = jnp.asarray(bin_data(jnp.asarray(X), jnp.asarray(edges)))
    mask = jnp.ones(D, jnp.float32)
    kw = dict(n_bins=B, reg_lambda=jnp.float32(1.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0))

    def gh_variants(k=4):
        return [(jnp.asarray(rng.normal(size=ROWS).astype(np.float32)),
                 jnp.asarray(rng.uniform(0.2, 1.0, size=ROWS)
                             .astype(np.float32))) for _ in range(k)]

    engines = [("scatter", dict(hist="scatter")),
               ("sorted", dict(hist="sorted", sorted_engine="einsum")),
               ("sorted+pallas", dict(hist="sorted",
                                      sorted_engine="pallas"))]
    results = []
    for depth in DEPTHS:
        row = {"depth": depth}
        for name, opts in engines:
            def one(g, h, depth=depth, opts=opts):
                f, b, l, gn, pr = grow_tree(Xb, g, h, mask,
                                            max_depth=depth, **kw, **opts)
                return l
            t = med_fetch(one, gh_variants())
            row[name.replace("+", "_") + "_ms"] = round(t * 1e3, 1)
            print(f"# d{depth} {name}: {row[name.replace('+', '_') + '_ms']}"
                  " ms", file=sys.stderr)
        results.append(row)

    artifact = {
        "metric": "tree_hist_engine_microbench",
        "rows": ROWS, "features": D, "bins": B,
        "platform": platform,
        "fencing": "host-fetch (benchmarks/_timing.py)",
        "trees": results,
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "HIST_ENGINES.json")
    with open(out_path, "w") as fh:
        json.dump(artifact, fh, indent=2)
    print(json.dumps(artifact))
    return 0


if __name__ == "__main__":
    sys.exit(main())
