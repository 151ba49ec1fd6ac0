"""Selector factories with reference-default candidate grids.

Parity: reference ``core/.../stages/impl/classification/
BinaryClassificationModelSelector.scala:49-272``,
``MultiClassificationModelSelector``, ``regression/RegressionModelSelector``
and ``selector/DefaultSelectorParams.scala`` — ``.withCrossValidation()`` /
``.withTrainValidationSplit()`` assembling default candidates + grids.

Grid values mirror DefaultSelectorParams where the family exists.
"""

from __future__ import annotations

from typing import Optional, Sequence

from transmogrifai_tpu.evaluators import (
    OpBinaryClassificationEvaluator, OpMultiClassificationEvaluator,
    OpRegressionEvaluator,
)
from transmogrifai_tpu.models.extras import OpNaiveBayes
from transmogrifai_tpu.models.linear import (
    OpLinearRegression, OpLinearSVC, OpLogisticRegression,
)
from transmogrifai_tpu.models.trees import (
    OpGBTClassifier, OpGBTRegressor, OpRandomForestClassifier,
    OpRandomForestRegressor,
)
from transmogrifai_tpu.selector.model_selector import ModelSelector
from transmogrifai_tpu.selector.splitters import (
    DataBalancer, DataCutter, DataSplitter,
)
from transmogrifai_tpu.selector.validator import (
    OpCrossValidation, OpTrainValidationSplit,
)

__all__ = ["BinaryClassificationModelSelector",
           "MultiClassificationModelSelector", "RegressionModelSelector"]

# DefaultSelectorParams analogs
_REG_GRID = [0.001, 0.01, 0.1, 0.2]
_ELASTIC_GRID = [0.0, 0.5]


def _lr_grid():
    return [{"reg_param": r, "elastic_net_param": e}
            for r in _REG_GRID for e in _ELASTIC_GRID]


def _svc_grid():
    return [{"reg_param": r} for r in _REG_GRID]


def _default_binary_candidates():
    return [(OpLogisticRegression(), _lr_grid()),
            (OpLinearSVC(), _svc_grid()),
            (OpRandomForestClassifier(), [
                {"num_trees": 50, "max_depth": d} for d in (6, 12)]),
            (OpGBTClassifier(), [
                {"num_rounds": 50, "max_depth": d} for d in (3, 6)])]


def _default_multi_candidates():
    # the reference's multiclass defaults (modelTypesToUse) are LR + RF;
    # naive Bayes rides along here as a closed-form third family. All
    # three take the fold-stacked sweep with a class axis in their scores
    return [(OpLogisticRegression(), _lr_grid()),
            (OpRandomForestClassifier(), [
                {"num_trees": 50, "max_depth": d} for d in (6, 12)]),
            (OpNaiveBayes(), [{}])]


def _default_regression_candidates():
    return [(OpLinearRegression(), _lr_grid()),
            (OpRandomForestRegressor(), [
                {"num_trees": 50, "max_depth": d} for d in (6, 12)]),
            (OpGBTRegressor(), [
                {"num_rounds": 50, "max_depth": d} for d in (3, 6)])]


class BinaryClassificationModelSelector:
    @staticmethod
    def with_cross_validation(
            n_folds: int = 3,
            validation_metric: str = "auPR",
            seed: int = 42,
            splitter: Optional[DataSplitter] = None,
            models_and_parameters: Optional[Sequence] = None,
            stratify: bool = False,
            max_wait_s: Optional[float] = 3600.0,
            checkpoint_dir: Optional[str] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_grids=(models_and_parameters
                              or _default_binary_candidates()),
            validator=OpCrossValidation(n_folds=n_folds, seed=seed,
                                        stratify=stratify),
            splitter=splitter if splitter is not None
            else DataSplitter(seed=seed),
            evaluators=[OpBinaryClassificationEvaluator()],
            validation_metric=validation_metric,
            max_wait_s=max_wait_s,
            checkpoint_dir=checkpoint_dir,
        )

    @staticmethod
    def with_train_validation_split(
            train_ratio: float = 0.75,
            validation_metric: str = "auPR",
            seed: int = 42,
            splitter: Optional[DataSplitter] = None,
            models_and_parameters: Optional[Sequence] = None,
            max_wait_s: Optional[float] = 3600.0,
            checkpoint_dir: Optional[str] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_grids=(models_and_parameters
                              or _default_binary_candidates()),
            validator=OpTrainValidationSplit(train_ratio=train_ratio, seed=seed),
            splitter=splitter if splitter is not None
            else DataSplitter(seed=seed),
            evaluators=[OpBinaryClassificationEvaluator()],
            validation_metric=validation_metric,
            max_wait_s=max_wait_s,
            checkpoint_dir=checkpoint_dir,
        )


class MultiClassificationModelSelector:
    @staticmethod
    def with_cross_validation(
            n_folds: int = 3,
            validation_metric: str = "F1",
            seed: int = 42,
            splitter: Optional[DataSplitter] = None,
            models_and_parameters: Optional[Sequence] = None,
            stratify: bool = False,
            max_wait_s: Optional[float] = 3600.0,
            checkpoint_dir: Optional[str] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_grids=(models_and_parameters
                              or _default_multi_candidates()),
            validator=OpCrossValidation(n_folds=n_folds, seed=seed,
                                        stratify=stratify),
            splitter=splitter if splitter is not None
            else DataCutter(seed=seed),
            evaluators=[OpMultiClassificationEvaluator()],
            validation_metric=validation_metric,
            max_wait_s=max_wait_s,
            checkpoint_dir=checkpoint_dir,
        )

    @staticmethod
    def with_train_validation_split(
            train_ratio: float = 0.75,
            validation_metric: str = "F1",
            seed: int = 42,
            splitter: Optional[DataSplitter] = None,
            models_and_parameters: Optional[Sequence] = None,
            max_wait_s: Optional[float] = 3600.0,
            checkpoint_dir: Optional[str] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_grids=(models_and_parameters
                              or _default_multi_candidates()),
            validator=OpTrainValidationSplit(train_ratio=train_ratio,
                                             seed=seed),
            splitter=splitter if splitter is not None
            else DataCutter(seed=seed),
            evaluators=[OpMultiClassificationEvaluator()],
            validation_metric=validation_metric,
            max_wait_s=max_wait_s,
            checkpoint_dir=checkpoint_dir,
        )


class RegressionModelSelector:
    @staticmethod
    def with_cross_validation(
            n_folds: int = 3,
            validation_metric: str = "RMSE",
            seed: int = 42,
            splitter: Optional[DataSplitter] = None,
            models_and_parameters: Optional[Sequence] = None,
            max_wait_s: Optional[float] = 3600.0,
            checkpoint_dir: Optional[str] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_grids=(models_and_parameters
                              or _default_regression_candidates()),
            validator=OpCrossValidation(n_folds=n_folds, seed=seed),
            splitter=splitter if splitter is not None
            else DataSplitter(seed=seed),
            evaluators=[OpRegressionEvaluator()],
            validation_metric=validation_metric,
            max_wait_s=max_wait_s,
            checkpoint_dir=checkpoint_dir,
        )

    @staticmethod
    def with_train_validation_split(
            train_ratio: float = 0.75,
            validation_metric: str = "RMSE",
            seed: int = 42,
            splitter: Optional[DataSplitter] = None,
            models_and_parameters: Optional[Sequence] = None,
            max_wait_s: Optional[float] = 3600.0,
            checkpoint_dir: Optional[str] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_grids=(models_and_parameters
                              or _default_regression_candidates()),
            validator=OpTrainValidationSplit(train_ratio=train_ratio,
                                             seed=seed),
            splitter=splitter if splitter is not None
            else DataSplitter(seed=seed),
            evaluators=[OpRegressionEvaluator()],
            validation_metric=validation_metric,
            max_wait_s=max_wait_s,
            checkpoint_dir=checkpoint_dir,
        )
