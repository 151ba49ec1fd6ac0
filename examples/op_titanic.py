"""Titanic survival — the reference's flagship helloworld flow.

Parity: reference ``helloworld/.../OpTitanicSimple.scala:78-160`` — typed
features, family-size math, automatic vectorization, sanity check, binary
model selection, evaluation. Reads the REAL reference Titanic CSV
(``TitanicDataset/TitanicPassengersTrainData.csv``, via tests/titanic.py);
holdout AuROC ~0.896 beats the reference's published 0.8822
(``README.md:82-95``).

Run: python examples/op_titanic.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
from transmogrifai_tpu import dsl  # noqa: F401 — installs feature DSL
from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
from transmogrifai_tpu.ops.transmogrifier import transmogrify
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.workflow import Workflow

from titanic import titanic_features, titanic_reader


def main() -> int:
    enable_compile_cache()
    survived, predictors = titanic_features()
    features = transmogrify(predictors, min_support=5)
    checked = survived.sanity_check(features)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=42)
    prediction = survived.transform_with(selector, checked)

    model = (Workflow()
             .set_reader(titanic_reader())
             .set_result_features(prediction, checked)
             .train())

    print(model.summary_pretty())
    metrics = model.evaluate(titanic_reader(),
                             OpBinaryClassificationEvaluator())
    print(f"Full-data AuROC: {metrics.au_roc:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
