"""HIGGS-shaped data: a copy of ``bench.make_data``, seeded.

``n_real`` standard-normal columns and a binary label with a nonlinear
signal. Kept here so that no later PR can change the yardstick.
"""

from __future__ import annotations

import numpy as np

from chipbench.data import Table, seeded


def make(n: int, seed: int, spec: dict, stream: int = 0) -> Table:
    d = int(spec["n_real"])
    rng = seeded(seed, stream)
    X = rng.normal(size=(n, d)).astype("float32")
    logits = (1.2 * X[:, 0] - 0.7 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
              + 0.8 * np.sin(X[:, 4]) - 0.4 * (X[:, 5] ** 2 - 1.0))
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))
         ).astype("float64")
    return Table(nums={f"f{i}": X[:, i].astype(np.float64)
                       for i in range(d)}, label=y)
