"""Boston housing prices — regression helloworld flow.

Parity: reference ``helloworld/.../OpBoston.scala`` — numeric housing
features (+ chas as PickList, mirroring ``BostonFeatures.scala``)
vectorized automatically, regression model selection, RMSE/R² evaluation.
Uses the REAL dataset shipped with the reference (``helloworld/src/main/
resources/BostonDataset/housingData.csv``, 333 rows) when present; falls
back to a synthesized price signal otherwise.

Run: python examples/op_boston.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
from transmogrifai_tpu import dsl  # noqa: F401
from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.ops.transmogrifier import transmogrify
from transmogrifai_tpu.selector import RegressionModelSelector
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.workflow import Workflow

COLUMNS = ("crim", "zn", "indus", "nox", "rm", "age", "dis", "rad", "tax",
           "ptratio", "lstat")


def boston_frame(n: int = 506, seed: int = 11) -> fr.HostFrame:
    rng = np.random.default_rng(seed)
    rm = rng.normal(6.3, 0.7, n)            # rooms
    lstat = np.abs(rng.normal(12, 7, n))    # % lower status
    nox = rng.uniform(0.4, 0.9, n)
    dis = np.abs(rng.normal(3.8, 2.0, n))
    crim = np.abs(rng.normal(3, 8, n))
    medv = (22 + 6.0 * (rm - 6.3) - 0.45 * (lstat - 12)
            - 12.0 * (nox - 0.65) + 0.4 * dis - 0.08 * crim
            + rng.normal(0, 2.0, n))
    cols = {
        "medv": (ft.RealNN, np.clip(medv, 5, 50).tolist()),
        "crim": (ft.Real, crim.tolist()),
        "zn": (ft.Real, rng.uniform(0, 100, n).tolist()),
        "indus": (ft.Real, rng.uniform(0, 28, n).tolist()),
        "nox": (ft.Real, nox.tolist()),
        "rm": (ft.Real, rm.tolist()),
        "age": (ft.Real, rng.uniform(2, 100, n).tolist()),
        "dis": (ft.Real, dis.tolist()),
        "rad": (ft.Integral, rng.integers(1, 25, n).tolist()),
        "tax": (ft.Real, rng.uniform(180, 720, n).tolist()),
        "ptratio": (ft.Real, rng.uniform(12, 22, n).tolist()),
        "lstat": (ft.Real, lstat.tolist()),
    }
    return fr.HostFrame.from_dict(cols)


#: the reference's copy (rowId, crim, zn, indus, chas, nox, rm, age, dis,
#: rad, tax, ptratio, b, lstat, medv) — BostonHouse.scala field order;
#: falls back to the committed fixture reconstruction (same format/stats,
#: scripts/gen_test_fixtures.py) so the quality gates run without the
#: reference checkout
_BOSTON_REFERENCE = ("/root/reference/helloworld/src/main/resources/"
                     "BostonDataset/housingData.csv")
_BOSTON_FIXTURE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "tests", "fixtures", "housingData.csv"))
BOSTON_CSV = _BOSTON_REFERENCE if os.path.exists(_BOSTON_REFERENCE) \
    else _BOSTON_FIXTURE
BOSTON_COLUMNS = ("crim", "zn", "indus", "chas", "nox", "rm", "age", "dis",
                  "rad", "tax", "ptratio", "b", "lstat")


def boston_frame_real(path: str = BOSTON_CSV) -> fr.HostFrame:
    rows = [line.strip().split(",")
            for line in open(path) if line.strip()]
    col = {name: [r[i + 1] for r in rows]
           for i, name in enumerate(BOSTON_COLUMNS + ("medv",))}
    cols = {"medv": (ft.RealNN, [float(v) for v in col["medv"]]),
            "chas": (ft.PickList, col["chas"]),
            "rad": (ft.Integral, [int(float(v)) for v in col["rad"]])}
    for name in BOSTON_COLUMNS:
        if name not in ("chas", "rad"):
            cols[name] = (ft.Real, [float(v) for v in col[name]])
    return fr.HostFrame.from_dict(cols)


def main(n: int = 506) -> int:
    enable_compile_cache()
    if os.path.exists(BOSTON_CSV):
        frame = boston_frame_real()
        columns = BOSTON_COLUMNS
    else:
        frame = boston_frame(n)
        columns = COLUMNS
    feats = FeatureBuilder.from_frame(frame, response="medv")
    features = transmogrify([feats[c] for c in columns])
    selector = RegressionModelSelector.with_cross_validation(
        n_folds=3, seed=42)
    prediction = feats["medv"].transform_with(selector, features)

    model = (Workflow()
             .set_input_frame(frame)
             .set_result_features(prediction, features)
             .train())
    print(model.summary_pretty())
    return 0


if __name__ == "__main__":
    sys.exit(main())
