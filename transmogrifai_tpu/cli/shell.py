"""Interactive framework shell (reference ``repl/`` module analog).

The reference build declares a ``repl`` project that drops users into a
Spark shell with the TransmogrifAI imports preloaded. The TPU-native
equivalent is a Python REPL with the whole public surface ready: feature
builders, the transmogrifier, selectors, evaluators, workflow, readers,
testkit generators, and the feature DSL installed — plus a banner stating
the backend (TPU/CPU) and device count.

``python -m transmogrifai_tpu.cli shell``
(uses IPython when available, stdlib ``code.interact`` otherwise).
"""

from __future__ import annotations

__all__ = ["make_namespace", "banner", "run_shell"]


def make_namespace() -> dict:
    """The preloaded REPL namespace — everything a session needs, named
    exactly as the docs/examples use them."""
    import numpy as np

    from transmogrifai_tpu import dsl  # noqa: F401 — installs DSL methods
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.evaluators import (
        OpBinaryClassificationEvaluator, OpMultiClassificationEvaluator,
        OpRegressionEvaluator,
    )
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.filters import RawFeatureFilter
    from transmogrifai_tpu.local import (
        import_sklearn, import_xgboost_json, make_score_function,
    )
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators import SanityChecker
    from transmogrifai_tpu.readers import DataReaders
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter,
        MultiClassificationModelSelector, RegressionModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow, load_model

    ns = dict(
        np=np, fr=fr, ft=ft, dsl=dsl,
        FeatureBuilder=FeatureBuilder, transmogrify=transmogrify,
        SanityChecker=SanityChecker, RawFeatureFilter=RawFeatureFilter,
        DataReaders=DataReaders, Workflow=Workflow, load_model=load_model,
        BinaryClassificationModelSelector=BinaryClassificationModelSelector,
        MultiClassificationModelSelector=MultiClassificationModelSelector,
        RegressionModelSelector=RegressionModelSelector,
        DataSplitter=DataSplitter,
        OpBinaryClassificationEvaluator=OpBinaryClassificationEvaluator,
        OpMultiClassificationEvaluator=OpMultiClassificationEvaluator,
        OpRegressionEvaluator=OpRegressionEvaluator,
        make_score_function=make_score_function,
        import_sklearn=import_sklearn,
        import_xgboost_json=import_xgboost_json,
    )
    try:
        from transmogrifai_tpu.testkit import random_data
        ns["random_data"] = random_data
    except Exception:  # failure-ok: optional shell-namespace preload
        pass
    return ns


def banner(ns: dict | None = None) -> str:
    import jax

    try:
        devs = jax.devices()
        backend = f"{devs[0].platform} x{len(devs)}"
    except Exception as e:  # no usable backend: the shell still opens (failure-ok: banner reports backend unavailable)
        backend = f"unavailable ({type(e).__name__})"
    names = ", ".join(sorted(ns if ns is not None else make_namespace()))
    return (f"transmogrifai_tpu shell — backend: {backend}\n"
            f"preloaded: {names}\n"
            "quick start: survived, predictors = ... ; "
            "features = transmogrify(predictors); "
            "Workflow().set_reader(...).set_result_features(...).train()")


def run_shell() -> int:
    ns = make_namespace()
    text = banner(ns)
    try:
        from IPython import start_ipython
        print(text)
        start_ipython(argv=[], user_ns=ns,
                      display_banner=False)  # type: ignore[call-arg]
    except ImportError:
        import code
        code.interact(banner=text, local=ns)
    return 0
