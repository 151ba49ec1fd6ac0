"""Multiclass classification evaluator.

Parity: reference ``core/.../evaluators/OpMultiClassificationEvaluator.scala``
(641 LoC) — weighted Precision/Recall/F1/Error plus the four deep metric
families:

- **threshold metrics** (``calculateThresholdMetrics:398-486``): per topN,
  correct/incorrect/no-prediction counts at every confidence threshold —
  "correct" means the true class is in the model's topN AND its probability
  clears the threshold; "no prediction" means even the max probability
  doesn't.
- **topK metrics** (``calculateTopKMetrics:352-380``): weighted P/R/F1/error
  restricted to the K most frequent labels (rarer true labels relabeled to
  an out-of-set class, so predictions hitting them count as wrong).
- **confusion-by-threshold** (``calculateConfMatrixMetricsByThreshold``):
  flattened confusion matrices over the top ``conf_matrix_num_classes``
  labels, one per confidence threshold (rows with max-prob below drop out).
- **misclassification report** (``calculateMisClassificationMetrics``): per
  label (and per prediction) category, total/correct counts plus the top
  ``conf_matrix_min_support`` misclassified counterparts.

All counts vectorize as numpy histogram/confusion passes — no per-row
Python in the hot path (the RDD treeAggregate analog is a bincount).
"""

from __future__ import annotations

from dataclasses import dataclass, field
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.evaluators.base import EvaluatorBase

__all__ = ["MultiClassificationMetrics", "MulticlassThresholdMetrics",
           "OpMultiClassificationEvaluator"]


@dataclass(frozen=True)
class MulticlassThresholdMetrics:
    top_ns: tuple
    thresholds: tuple
    correct_counts: dict            # topN -> [n_thresholds]
    incorrect_counts: dict
    no_prediction_counts: dict

    def to_json(self) -> dict:
        return {
            "topNs": list(self.top_ns),
            "thresholds": list(self.thresholds),
            "correctCounts": {str(k): list(map(int, v))
                              for k, v in self.correct_counts.items()},
            "incorrectCounts": {str(k): list(map(int, v))
                                for k, v in self.incorrect_counts.items()},
            "noPredictionCounts": {str(k): list(map(int, v))
                                   for k, v in
                                   self.no_prediction_counts.items()},
        }


@dataclass(frozen=True)
class MultiClassificationMetrics:
    precision: float        # weighted by class support
    recall: float
    f1: float
    error: float
    top_k_accuracy: tuple = ()
    confusion: Optional[list] = field(default=None, repr=False)
    threshold_metrics: Optional[MulticlassThresholdMetrics] = \
        field(default=None, repr=False)
    top_k_metrics: Optional[dict] = field(default=None, repr=False)
    conf_matrix_by_threshold: Optional[dict] = field(default=None, repr=False)
    misclassification: Optional[dict] = field(default=None, repr=False)

    def to_json(self) -> dict:
        """Serialization hook consumed by EvaluatorBase.to_json: nested
        threshold metrics keep the reference's camelCase schema."""
        return {
            "precision": self.precision, "recall": self.recall,
            "f1": self.f1, "error": self.error,
            "top_k_accuracy": list(self.top_k_accuracy),
            "confusion": self.confusion,
            "threshold_metrics": (self.threshold_metrics.to_json()
                                  if self.threshold_metrics else None),
            "top_k_metrics": self.top_k_metrics,
            "conf_matrix_by_threshold": self.conf_matrix_by_threshold,
            "misclassification": self.misclassification,
        }


def _weighted_prf(conf: np.ndarray) -> tuple[float, float, float, float]:
    """(precision, recall, f1, error), support-weighted, from a confusion
    matrix conf[label, pred]. F1 is the harmonic mean of the WEIGHTED
    precision/recall — the reference's own definition
    (OpMultiClassificationEvaluator.scala:155: f1 = 2PR/(P+R) from
    weightedPrecision/weightedRecall), deliberately NOT Spark's
    weightedFMeasure (support-weighted mean of per-class F1s)."""
    n_cls = conf.shape[0]
    support = conf.sum(axis=1)
    pred_count = conf.sum(axis=0)
    diag = np.diag(conf)
    prec_c = np.divide(diag, pred_count, out=np.zeros(n_cls),
                       where=pred_count > 0)
    rec_c = np.divide(diag, support, out=np.zeros(n_cls),
                      where=support > 0)
    wsum = max(support.sum(), 1e-12)
    precision = float((prec_c * support).sum() / wsum)
    recall = float((rec_c * support).sum() / wsum)
    f1 = 0.0 if precision + recall == 0 else \
        2 * precision * recall / (precision + recall)
    error = 1.0 - float(diag.sum() / wsum)
    return precision, recall, f1, error


@functools.partial(jax.jit, static_argnames=("metric",))
def _metric_batch_folds(y, scores, w, metric: str):
    """The fold-stacked multiclass metric batch: ``y [k, n]`` class labels,
    ``scores [k, G, K, n]`` class scores (the class axis BEFORE the rows, so
    the rows stay on the TPU's lanes: a trailing axis of 7 pads to 128),
    ``w [k, n]`` -> ``[k, G]``. A lane's prediction is the argmax over the
    class axis (the first of equal scores, as ``jnp.argmax`` in
    ``device_apply``); its ``[K, K]`` confusion matrix is a product of the
    two one-hot encodings over the rows (0/1 operands, so exact at any
    matmul precision up to 2**24 rows); precision, recall, F1 and error
    follow from it as ``_weighted_prf`` computes them on the host."""
    K = scores.shape[2]
    with jax.named_scope("metric.confusion"):
        yhat = jnp.argmax(scores, axis=2)                        # [k, G, n]
        classes = jnp.arange(K, dtype=jnp.int32)
        oh_y = (y.astype(jnp.int32)[:, None, :] == classes[None, :, None]
                ).astype(jnp.float32) * w[:, None, :]            # [k, K, n]
        oh_hat = (yhat[:, :, None, :] == classes[None, None, :, None]
                  ).astype(jnp.float32)                          # [k,G,K,n]
        conf = jnp.einsum("kan,kgbn->kgab", oh_y, oh_hat,
                          precision=jax.lax.Precision.HIGHEST)
    support = jnp.sum(conf, axis=3)                              # [k, G, K]
    pred_count = jnp.sum(conf, axis=2)
    diag = jnp.diagonal(conf, axis1=2, axis2=3)
    prec_c = jnp.where(pred_count > 0,
                       diag / jnp.maximum(pred_count, 1e-30), 0.0)
    rec_c = jnp.where(support > 0, diag / jnp.maximum(support, 1e-30), 0.0)
    wsum = jnp.maximum(jnp.sum(support, axis=2), 1e-12)
    precision = jnp.sum(prec_c * support, axis=2) / wsum
    recall = jnp.sum(rec_c * support, axis=2) / wsum
    if metric == "Precision":
        return precision
    if metric == "Recall":
        return recall
    if metric == "F1":
        both = precision + recall
        return jnp.where(both > 0, 2 * precision * recall
                         / jnp.maximum(both, 1e-30), 0.0)
    return 1.0 - jnp.sum(diag, axis=2) / wsum                    # Error


class OpMultiClassificationEvaluator(EvaluatorBase):
    name = "multiclass classification"
    default_metric = "F1"
    metric_directions = {"Precision": True, "Recall": True, "F1": True,
                         "Error": False}
    scores_class_axis = True

    def __init__(self, top_ns: tuple = (1, 3),
                 top_ks: tuple = (5, 10, 20, 50, 100),
                 thresholds: Optional[tuple] = None,
                 conf_matrix_num_classes: int = 15,
                 conf_matrix_thresholds: tuple = (0.0, 0.2, 0.4, 0.6, 0.8),
                 conf_matrix_min_support: int = 5,
                 with_confusion: bool = False,
                 with_threshold_metrics: bool = True):
        self.top_ns = tuple(top_ns)
        self.top_ks = tuple(top_ks)
        self.thresholds = tuple(thresholds) if thresholds is not None else \
            tuple(round(i / 100.0, 2) for i in range(101))
        self.conf_matrix_num_classes = conf_matrix_num_classes
        self.conf_matrix_thresholds = tuple(conf_matrix_thresholds)
        self.conf_matrix_min_support = conf_matrix_min_support
        self.with_confusion = with_confusion
        self.with_threshold_metrics = with_threshold_metrics

    # -- threshold metrics ---------------------------------------------------
    def _threshold_metrics(self, prob: np.ndarray, y: np.ndarray
                           ) -> MulticlassThresholdMetrics:
        n, n_cls = prob.shape
        thr = np.asarray(self.thresholds)
        true_score = np.where(y < n_cls, prob[np.arange(n), np.clip(y, 0,
                              n_cls - 1)], 0.0)
        top_score = prob.max(axis=1)
        # first threshold index strictly above the score
        true_cut = np.searchsorted(thr, true_score, side="right")
        max_cut = np.searchsorted(thr, top_score, side="right")
        order = np.argsort(-prob, axis=1, kind="stable")
        nT = thr.size

        def rev_count(cuts, mask):
            """out[j] = #{i in mask : cuts[i] > j} for j in [0, nT)."""
            c = np.bincount(cuts[mask], minlength=nT + 1)
            return (mask.sum() - np.cumsum(c)[:nT]).astype(np.int64)

        correct, incorrect, nopred = {}, {}, {}
        for t in self.top_ns:
            in_topn = (order[:, :t] == y[:, None]).any(axis=1)
            cor = rev_count(true_cut, in_topn)
            # incorrect: topN hits count from true_cut..max_cut; misses from
            # 0..max_cut — i.e. all rows to max_cut minus the correct part
            inc = rev_count(max_cut, np.ones(n, bool)) - cor
            correct[t] = cor
            incorrect[t] = inc
            nopred[t] = np.full(nT, n, np.int64) - cor - inc
        return MulticlassThresholdMetrics(
            top_ns=self.top_ns, thresholds=self.thresholds,
            correct_counts=correct, incorrect_counts=incorrect,
            no_prediction_counts=nopred)

    # -- topK metrics --------------------------------------------------------
    def _topk_metrics(self, y: np.ndarray, yhat: np.ndarray,
                      w: np.ndarray) -> dict:
        labels, counts = np.unique(y, return_counts=True)
        by_freq = labels[np.argsort(-counts, kind="stable")]
        out = {"topKs": list(self.top_ks), "Precision": [], "Recall": [],
               "F1": [], "Error": []}
        n_all = max(int(max(y.max(), yhat.max())) + 1, 1) if y.size else 1
        for k in self.top_ks:
            keep = set(int(v) for v in by_freq[:k])
            # rare true labels -> out-of-set class n_all (never predicted)
            y_k = np.where(np.isin(y, list(keep)), y, n_all)
            conf = np.zeros((n_all + 1, n_all + 1))
            np.add.at(conf, (y_k, yhat), w)
            p, r, f1, e = _weighted_prf(conf)
            out["Precision"].append(p)
            out["Recall"].append(r)
            out["F1"].append(f1)
            out["Error"].append(e)
        return out

    # -- confusion by threshold ---------------------------------------------
    def _conf_matrix_by_threshold(self, y, yhat, prob) -> dict:
        labels, counts = np.unique(y, return_counts=True)
        cm_classes = [int(v) for v in
                      labels[np.argsort(-counts, kind="stable")]
                      [:self.conf_matrix_num_classes]]
        idx = {c: i for i, c in enumerate(cm_classes)}
        sel = np.isin(y, cm_classes) & np.isin(yhat, cm_classes)
        yl = np.asarray([idx[int(v)] for v in y[sel]], np.int64)
        yp = np.asarray([idx[int(v)] for v in yhat[sel]], np.int64)
        conf_score = prob[sel].max(axis=1) if prob.size else \
            np.zeros(sel.sum())
        k = len(cm_classes)
        thr = sorted(self.conf_matrix_thresholds)
        matrices = []
        for t in thr:
            m = np.zeros((k, k), np.int64)
            rows = conf_score >= t
            np.add.at(m, (yl[rows], yp[rows]), 1)
            # reference flattens column-major over (label, prediction)
            matrices.append([int(v) for v in m.T.reshape(-1)])
        return {
            "ConfMatrixNumClasses": self.conf_matrix_num_classes,
            "ConfMatrixClassIndices": cm_classes,
            "ConfMatrixThresholds": list(thr),
            "ConfMatrices": matrices,
        }

    # -- misclassification report -------------------------------------------
    def _misclassification(self, y, yhat) -> dict:
        def per_category(keys, others):
            out = []
            cats, totals = np.unique(keys, return_counts=True)
            for c in cats[np.argsort(-totals, kind="stable")]:
                rows = keys == c
                vals, cnts = np.unique(others[rows], return_counts=True)
                correct = int(cnts[vals == c].sum())
                mis = [(int(v), int(n)) for v, n in zip(vals, cnts) if v != c]
                mis.sort(key=lambda t: -t[1])
                out.append({
                    "Category": float(c),
                    "TotalCount": int(rows.sum()),
                    "CorrectCount": correct,
                    "MisClassifications": [
                        {"ClassIndex": float(v), "Count": n}
                        for v, n in mis[:self.conf_matrix_min_support]],
                })
            return out
        return {
            "ConfMatrixMinSupport": self.conf_matrix_min_support,
            "MisClassificationsByLabel": per_category(y, yhat),
            "MisClassificationsByPrediction": per_category(yhat, y),
        }

    def metric_from_arrays(self, y, pred_col, metric=None, w=None) -> float:
        """Summary-only path for the CV sweep: one confusion matrix, none of
        the threshold/topK/misclassification report families."""
        m = metric or self.default_metric
        y = np.asarray(y).astype(np.int64)
        yhat = np.asarray(pred_col.prediction).astype(np.int64)
        w = np.ones_like(y, dtype=np.float64) if w is None else np.asarray(w)
        n_cls = max(int(y.max()), int(yhat.max())) + 1 if y.size else 1
        conf = np.zeros((n_cls, n_cls))
        np.add.at(conf, (y, yhat), w)
        p, r, f1, e = _weighted_prf(conf)
        return {"Precision": p, "Recall": r, "F1": f1, "Error": e}.get(
            m) if m in ("Precision", "Recall", "F1", "Error") else \
            self.metric_value(self.evaluate_arrays(y, pred_col, w), m)

    def metric_batch_scores_folds_device(self, y, scores, metric=None,
                                         w=None):
        """Fold-stacked metric batch WITHOUT the host pull: ``y [k, n]``,
        class scores ``[k, G, K, n]`` (or the ``[k, G, n]`` margins the
        families hand on for a two-class label) -> the ``[k, G]`` values of
        one of the four summary metrics as a device array future, which
        the one-sync sweep settles behind its single barrier."""
        metric = metric or self.default_metric
        if metric not in self.metric_directions:
            raise ValueError(f"no fold-batched form of metric {metric!r}")
        y = jnp.asarray(y, jnp.float32)
        w = jnp.ones_like(y) if w is None else jnp.asarray(w, jnp.float32)
        scores = jnp.asarray(scores, jnp.float32)
        if scores.ndim == 3:    # a two-class label: margins, decided at 0
            scores = jnp.stack([-scores, scores], axis=2)
        return _metric_batch_folds(y, scores, w, metric)

    def metric_batch_scores_folds(self, y, scores, metric=None,
                                  w=None) -> np.ndarray:
        """``metric_batch_scores_folds_device`` pulled to the host."""
        return np.asarray(self.metric_batch_scores_folds_device(
            y, scores, metric, w))

    def evaluate_arrays(self, y, pred_col, w=None) -> MultiClassificationMetrics:
        from transmogrifai_tpu.utils.tracing import span
        # the pulls wait for the device; the span holds the host's own work
        y = np.asarray(y).astype(np.int64)
        yhat = np.asarray(pred_col.prediction).astype(np.int64)
        w = np.ones_like(y, dtype=np.float64) if w is None else np.asarray(w)
        prob = np.asarray(pred_col.probability)
        with span("evaluate.multiclass", rows=int(y.shape[0]),
                  classes=int(prob.shape[1]) if prob.ndim == 2 else 0):
            return self._evaluate_host(y, yhat, w, prob)

    def _evaluate_host(self, y, yhat, w, prob) -> MultiClassificationMetrics:
        """The report's host part: confusion counts, top-K, threshold and
        misclassification families over the pulled scores."""
        n_cls = max(int(y.max()), int(yhat.max())) + 1 if y.size else 1
        conf = np.zeros((n_cls, n_cls))
        np.add.at(conf, (y, yhat), w)
        precision, recall, f1, error = _weighted_prf(conf)
        wsum = max(w.sum(), 1e-12)
        topks = []
        if prob.size and prob.ndim == 2 and prob.shape[1] > 1:
            order = np.argsort(-prob, axis=1, kind="stable")
            for k in self.top_ns:
                hit = (order[:, :k] == y[:, None]).any(axis=1)
                topks.append(float((hit * w).sum() / wsum))
        thr_m = None
        cm_thr = None
        if self.with_threshold_metrics and prob.size and prob.ndim == 2:
            thr_m = self._threshold_metrics(prob, y)
            cm_thr = self._conf_matrix_by_threshold(y, yhat, prob)
        return MultiClassificationMetrics(
            precision=precision, recall=recall, f1=f1, error=error,
            top_k_accuracy=tuple(topks),
            confusion=conf.tolist() if self.with_confusion else None,
            threshold_metrics=thr_m,
            top_k_metrics=self._topk_metrics(y, yhat, w),
            conf_matrix_by_threshold=cm_thr,
            misclassification=self._misclassification(y, yhat))
