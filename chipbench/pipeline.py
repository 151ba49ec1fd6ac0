"""The system under test, assembled from a configuration file.

The only module of the benchmark that builds ``transmogrifai_tpu`` objects:
raw host table -> ``FeatureBuilder`` -> ``transmogrify`` -> ``SanityChecker``
-> k-fold model selector -> winner refit -> holdout evaluation, as
``bench.run_pipeline`` assembles it. Everything that differs between
configurations (the selector, the zoo's estimators, grids, folds) comes from
the configuration's ``pipeline`` block, each class under its
``module:Class`` path: nothing here names a selector or a family.
"""

from __future__ import annotations

import importlib

import numpy as np


def resolve(path: str):
    """The object a ``module:attribute`` path names."""
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)


def to_frame(table):
    """The raw ``HostFrame`` of a :class:`chipbench.data.Table`."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.types import feature_types as ft
    n = table.n_rows
    cols = {name: fr.HostColumn(ft.Real, np.asarray(v, np.float64),
                                np.ones(n, bool))
            for name, v in table.nums.items()}
    frame = fr.HostFrame(cols)
    extra = {name: (ft.Text, v) for name, v in table.cats.items()}
    extra["label"] = (ft.RealNN, table.label)
    more = fr.HostFrame.from_dict(extra)
    return fr.HostFrame({**{n_: frame[n_] for n_ in frame.names()},
                         **{n_: more[n_] for n_ in more.names()}})


def candidates(pipeline: dict):
    """``models_and_parameters`` from the configuration's ``zoo``."""
    out = []
    for fam in pipeline["zoo"]:
        est = resolve(fam["estimator"])(**fam.get("params", {}))
        out.append((est, [dict(g) for g in fam["grid"]]))
    return out


def build_workflow(frame, pipeline: dict):
    """A fresh ``Workflow`` over ``frame``; returns ``(workflow, handles)``
    where ``handles`` names the features the comparison reads back."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.selector import DataSplitter
    from transmogrifai_tpu.workflow import Workflow

    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    vec = transmogrify(list(feats.values()),
                       **pipeline.get("transmogrify", {}))
    checked = label.transform_with(SanityChecker(), vec)
    selector = resolve(pipeline["selector"]).with_cross_validation(
        n_folds=int(pipeline["n_folds"]), seed=int(pipeline["split_seed"]),
        models_and_parameters=candidates(pipeline),
        splitter=DataSplitter(
            reserve_test_fraction=float(pipeline["holdout_fraction"]),
            seed=int(pipeline["split_seed"])))
    pred = label.transform_with(selector, checked)
    wf = Workflow().set_input_frame(frame).set_result_features(pred)
    return wf, {"vector": vec, "checked": checked, "prediction": pred,
                "label": label}
