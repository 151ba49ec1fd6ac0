"""Forecast, calibration-bin, and log-loss evaluators.

Parity targets:
- ``core/.../evaluators/OpForecastEvaluator.scala`` — SMAPE, SeasonalError,
  MASE over a seasonal-naive baseline with window ``seasonal_window``.
- ``core/.../evaluators/OpBinScoreEvaluator.scala`` — equi-width score bins
  between observed min/max score: per-bin average score, conversion rate,
  counts, plus overall Brier score.
- ``core/.../stages/impl/evaluator/OPLogLoss.scala`` — mean negative
  log-probability of the true class (binary + multiclass variants).

All three are vectorized JAX/NumPy reductions rather than RDD fold/reduce:
the per-row semigroup accumulations of the reference become segment_sum /
masked-mean kernels that XLA fuses into single passes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.evaluators.base import EvaluatorBase

__all__ = [
    "ForecastMetrics", "OpForecastEvaluator",
    "BinaryClassificationBinMetrics", "OpBinScoreEvaluator",
    "SingleMetric", "OPLogLoss",
]


@dataclass(frozen=True)
class ForecastMetrics:
    smape: float
    seasonal_error: float
    mase: float
    # aliases matching the reference's metric casing
    @property
    def SMAPE(self):  # noqa: N802
        return self.smape

    @property
    def MASE(self):  # noqa: N802
        return self.mase


class OpForecastEvaluator(EvaluatorBase):
    """Forecast metrics on (label, prediction) sequences in row order.

    ``seasonal_error`` is the mean |y_t - y_{t+window}| over the first
    ``n - window`` rows (the seasonal-naive forecaster's error); MASE is the
    mean absolute error scaled by it. SMAPE uses the symmetric 2|y-yhat| /
    (|y|+|yhat|) form with zero-denominator rows contributing 0.
    """

    name = "forecast"
    default_metric = "SMAPE"
    metric_directions = {"SMAPE": False, "MASE": False, "SeasonalError": False}

    def __init__(self, seasonal_window: int = 1, max_items: int = 87660):
        if seasonal_window <= 0:
            raise ValueError("seasonal_window must be positive")
        if max_items <= 0:
            raise ValueError("max_items must be positive")
        self.seasonal_window = int(seasonal_window)
        self.max_items = int(max_items)

    def evaluate_arrays(self, y, pred_col, w=None) -> ForecastMetrics:
        y = jnp.asarray(y, jnp.float32)[: self.max_items]
        yhat = jnp.asarray(pred_col.prediction, jnp.float32)[: self.max_items]
        n = y.shape[0]
        win = self.seasonal_window
        abs_diff = jnp.abs(y - yhat)
        sum_abs = jnp.abs(y) + jnp.abs(yhat)
        smape_terms = jnp.where(sum_abs > 0, abs_diff / sum_abs, 0.0)
        smape = float(2.0 * jnp.sum(smape_terms) / n) if n > 0 else 0.0
        seasonal_limit = n - win
        if seasonal_limit > 0:
            seasonal_abs = jnp.sum(jnp.abs(y[:seasonal_limit] - y[win:]))
            seasonal_error = float(seasonal_abs / seasonal_limit)
        else:
            seasonal_error = float("nan") if n == 0 else 0.0
        mase_den = seasonal_error * n
        abs_sum = float(jnp.sum(abs_diff))
        if mase_den > 0:
            mase = abs_sum / mase_den
        else:
            # Deliberate deviation from the reference (which reports 0.0 here):
            # a nonzero-error forecast against a constant label series must not
            # rank as perfect under a smaller-is-better metric.
            mase = 0.0 if abs_sum == 0.0 else float("inf")
        return ForecastMetrics(smape=smape, seasonal_error=seasonal_error,
                               mase=mase)


@dataclass(frozen=True)
class BinaryClassificationBinMetrics:
    brier_score: float
    bin_size: float
    bin_centers: list = field(default_factory=list)
    number_of_data_points: list = field(default_factory=list)
    number_of_positive_labels: list = field(default_factory=list)
    average_score: list = field(default_factory=list)
    average_conversion_rate: list = field(default_factory=list)

    @staticmethod
    def empty() -> "BinaryClassificationBinMetrics":
        return BinaryClassificationBinMetrics(0.0, 0.0, [], [], [], [], [])


class OpBinScoreEvaluator(EvaluatorBase):
    """Score-calibration diagnostics over equi-width bins of P(class=1).

    Bin range spans [min(min_score, 0), max(max_score, 1)] — the reference
    folds the observed scores into a (1.0, 0.0) seed, so the range always
    covers [0, 1] and widens only if scores escape it.
    """

    name = "bin score"
    default_metric = "BrierScore"
    metric_directions = {"BrierScore": False}

    def __init__(self, num_of_bins: int = 100):
        if num_of_bins <= 0:
            raise ValueError("num_of_bins must be positive")
        self.num_of_bins = int(num_of_bins)

    def evaluate_arrays(self, y, pred_col, w=None) -> BinaryClassificationBinMetrics:
        score = pred_col.pos_score()
        y = jnp.asarray(y, jnp.float32)
        n = int(score.shape[0])
        if n == 0:
            return BinaryClassificationBinMetrics.empty()
        b = self.num_of_bins
        # one fused device program, one host pull (one-sync convention,
        # see evaluators/binary.py:_binary_scalars)
        max_s = jnp.maximum(jnp.max(score), 1.0)
        min_s = jnp.minimum(jnp.min(score), 0.0)
        diff = max_s - min_s
        idx = jnp.clip(((score - min_s) / diff * b).astype(jnp.int32), 0, b - 1)
        pos = (y > 0).astype(jnp.float32)
        counts = jnp.zeros(b, jnp.float32).at[idx].add(jnp.ones_like(score))
        positives = jnp.zeros(b, jnp.float32).at[idx].add(pos)
        score_sums = jnp.zeros(b, jnp.float32).at[idx].add(score)
        brier = jnp.mean((score - y) ** 2)
        packed = np.asarray(jnp.concatenate(
            [counts, positives, score_sums, jnp.stack([brier, min_s, max_s])]))
        counts_np, positives_np, score_sums_np = (
            packed[:b], packed[b:2 * b], packed[2 * b:3 * b])
        brier_f, min_f, max_f = (float(x) for x in packed[3 * b:])
        diff_f = max_f - min_f
        safe = np.maximum(counts_np, 1.0)
        centers = [min_f + diff_f * i / b + diff_f / (2 * b) for i in range(b)]
        return BinaryClassificationBinMetrics(
            brier_score=brier_f,
            bin_size=diff_f / b,
            bin_centers=centers,
            number_of_data_points=counts_np.astype(int).tolist(),
            number_of_positive_labels=positives_np.astype(int).tolist(),
            average_score=(score_sums_np / safe).tolist(),
            average_conversion_rate=(positives_np / safe).tolist(),
        )


@dataclass(frozen=True)
class SingleMetric:
    name: str
    value: float


class OPLogLoss(EvaluatorBase):
    """Mean -log P(true class). Works for binary and multiclass predictions;
    the true-class probability is gathered from the probability matrix.
    """

    name = "logloss"
    default_metric = "logLoss"
    metric_directions = {"logLoss": False}

    def __init__(self, eps: float = 1e-15):
        self.eps = float(eps)

    def evaluate_arrays(self, y, pred_col, w=None) -> SingleMetric:
        y = np.asarray(y)
        if y.size == 0:
            raise ValueError("empty data: log loss cannot be calculated")
        prob = pred_col.probability
        yi = jnp.asarray(y, jnp.int32)
        if prob is not None and getattr(prob, "ndim", 1) == 2 and prob.shape[1] >= 2:
            p = jnp.take_along_axis(jnp.asarray(prob, jnp.float32),
                                    yi[:, None], axis=1)[:, 0]
        else:
            # (n,0)-probability models (margin-only / regression convention)
            p1 = pred_col.pos_score()
            p = jnp.where(yi > 0, p1, 1.0 - p1)
        val = float(jnp.mean(-jnp.log(jnp.clip(p, self.eps, 1.0))))
        return SingleMetric(name="logLoss", value=val)

    def metric_value(self, metrics, metric=None):
        return metrics.value

    @staticmethod
    def binary_log_loss() -> "OPLogLoss":
        return OPLogLoss()

    @staticmethod
    def multi_log_loss() -> "OPLogLoss":
        return OPLogLoss()
