"""Evaluator base.

Parity: reference ``core/.../evaluators/OpEvaluatorBase.scala:113-226`` —
evaluators consume (label, prediction) and emit a typed metrics bundle;
each declares its default metric and whether larger is better (drives the
ModelSelector's argbest).
"""

from __future__ import annotations

from dataclasses import asdict
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["EvaluatorBase"]


class EvaluatorBase:
    name: str = "evaluator"
    default_metric: str = ""
    #: metric name -> larger_is_better
    metric_directions: dict[str, bool] = {}
    #: whether ``metric_batch_scores_folds_device`` (where the evaluator
    #: has one) reduces ``[k, G, K, n]`` class scores; False: one scalar
    #: score a row (``[k, G, n]``) only, and the selector keeps a family of
    #: several outputs on the per-fold loop
    scores_class_axis: bool = False

    def evaluate_arrays(self, y, pred_col, w=None) -> Any:
        """Compute metrics from a label array + PredictionColumn."""
        raise NotImplementedError

    def evaluate(self, data, label_name: str, pred_name: str) -> Any:
        """Evaluate against a PipelineData holding label + prediction cols."""
        y = data.device_col(label_name).values
        pred = data.device_col(pred_name)
        return self.evaluate_arrays(y, pred)

    def metric_value(self, metrics: Any, metric: Optional[str] = None) -> float:
        m = metric or self.default_metric
        return float(getattr(metrics, _snake(m)))

    def larger_is_better(self, metric: Optional[str] = None) -> bool:
        m = metric or self.default_metric
        return self.metric_directions.get(m, True)

    def metric_from_arrays(self, y, pred_col, metric: Optional[str] = None,
                           w=None) -> float:
        """One scalar metric — the CV sweep's hot call. Default computes the
        full bundle; evaluators with expensive report families override with
        a summary-only pass."""
        return self.metric_value(self.evaluate_arrays(y, pred_col, w),
                                 metric)

    @staticmethod
    def to_json(metrics: Any) -> dict:
        def conv(v):
            if isinstance(v, dict):
                return {str(k): conv(x) for k, x in v.items()}
            if isinstance(v, (list, tuple)):
                return [conv(x) for x in v]
            if isinstance(v, np.ndarray):
                return conv(v.tolist())
            if isinstance(v, np.integer):
                return int(v)
            if isinstance(v, (float, np.floating)):
                # non-finite floats are not valid strict JSON
                f = float(v)
                return f if np.isfinite(f) else None
            return v
        if hasattr(metrics, "to_json") and callable(metrics.to_json):
            return conv(metrics.to_json())
        return conv(asdict(metrics))


def _snake(name: str) -> str:
    """auPR -> au_pr, AuROC -> au_roc, F1 -> f1, Error -> error."""
    out = []
    for i, ch in enumerate(name):
        if ch.isupper() and i > 0 and (not name[i - 1].isupper()):
            out.append("_")
        out.append(ch.lower())
    return "".join(out).replace("__", "_")
