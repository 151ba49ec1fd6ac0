"""Iris species — multiclass helloworld flow.

Parity: reference ``helloworld/.../OpIris.scala`` — a text label indexed to
class ids, automatic vectorization of the four measurements, multiclass
model selection. Uses the REAL UCI Iris dataset shipped with the reference
(``helloworld/src/main/resources/IrisDataset/iris.csv``, 150 rows) when
present; falls back to synthesized Gaussian species clusters otherwise.

Run: python examples/op_iris.py
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
from transmogrifai_tpu.selector import MultiClassificationModelSelector
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.workflow import Workflow

SPECIES = ("setosa", "versicolor", "virginica")
#: cluster means per species: sepal len/width, petal len/width
MEANS = np.array([[5.0, 3.4, 1.5, 0.25],
                  [5.9, 2.8, 4.3, 1.3],
                  [6.6, 3.0, 5.6, 2.0]])
STD = np.array([0.35, 0.35, 0.3, 0.2])


def iris_frame(n: int = 450, seed: int = 7) -> fr.HostFrame:
    rng = np.random.default_rng(seed)
    cls = rng.integers(0, 3, size=n)
    X = MEANS[cls] + rng.normal(size=(n, 4)) * STD
    return fr.HostFrame.from_dict({
        "species": (ft.Text, [SPECIES[c] for c in cls]),
        "sepal_length": (ft.Real, X[:, 0].tolist()),
        "sepal_width": (ft.Real, X[:, 1].tolist()),
        "petal_length": (ft.Real, X[:, 2].tolist()),
        "petal_width": (ft.Real, X[:, 3].tolist()),
    })


#: the reference's copy of the classic UCI data (id, 4 measurements, label);
#: falls back to the committed fixture reconstruction (same format/stats,
#: scripts/gen_test_fixtures.py) so the quality gates run without the
#: reference checkout
_IRIS_REFERENCE = ("/root/reference/helloworld/src/main/resources/"
                   "IrisDataset/iris.csv")
_IRIS_FIXTURE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "tests", "fixtures", "iris.csv"))
IRIS_CSV = _IRIS_REFERENCE if os.path.exists(_IRIS_REFERENCE) \
    else _IRIS_FIXTURE


def iris_frame_real(path: str = IRIS_CSV) -> fr.HostFrame:
    rows = [line.strip().split(",")
            for line in open(path) if line.strip()]
    return fr.HostFrame.from_dict({
        "species": (ft.Text, [r[5].replace("Iris-", "") for r in rows]),
        "sepal_length": (ft.Real, [float(r[1]) for r in rows]),
        "sepal_width": (ft.Real, [float(r[2]) for r in rows]),
        "petal_length": (ft.Real, [float(r[3]) for r in rows]),
        "petal_width": (ft.Real, [float(r[4]) for r in rows]),
    })


def main(n: int = 450) -> int:
    enable_compile_cache()
    frame = iris_frame_real() if os.path.exists(IRIS_CSV) else iris_frame(n)
    feats = FeatureBuilder.from_frame(frame, response="species")
    label = feats["species"].index_string()
    features = transmogrify([feats[c] for c in (
        "sepal_length", "sepal_width", "petal_length", "petal_width")])
    selector = MultiClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=42)
    prediction = label.transform_with(selector, features)

    model = (Workflow()
             .set_input_frame(frame)
             .set_result_features(prediction, features)
             .train())
    print(model.summary_pretty())
    return 0


if __name__ == "__main__":
    sys.exit(main())
