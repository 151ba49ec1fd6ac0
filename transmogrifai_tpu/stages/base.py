"""Typed pipeline stages over features (not columns).

Parity: reference ``features/src/main/scala/com/salesforce/op/stages/
OpPipelineStages.scala:55-552`` and ``stages/base/*`` — stages declare typed
feature inputs/outputs, validate input types, and produce output features
lazily; ``OpTransformer`` adds the row-level path used for local scoring.

TPU-first divergence: instead of the reference's per-row UDF closures, a
transformer here exposes up to three execution paths:

- **device path** (``DeviceTransformer.device_apply``): a pure jittable
  function of (params pytree, device columns) -> device column. All device
  transformers of one DAG layer are fused into a single jitted program by the
  executor (the analog of ``FitStagesUtil.applyOpTransformations`` fusing all
  row closures of a layer into one RDD pass).
- **host path** (``HostTransformer.host_apply``): eager numpy/python over
  host columns — for string-shaped work that stays off the device.
- **row path** (``transform_row``): plain-python single-record scoring; the
  contract tests assert row path == columnar path (the reference's
  OpTransformerSpec invariant).

Estimators fit on the pipeline data and return a fitted Transformer (model).
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Optional, Sequence

import numpy as np

from transmogrifai_tpu.features.feature import Feature, FeatureLike
from transmogrifai_tpu.frame import HostColumn
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.uid import UID

__all__ = [
    "PipelineStage", "Transformer", "HostTransformer", "DeviceTransformer",
    "Estimator", "LambdaTransformer", "FeatureGeneratorStage",
    "STAGE_REGISTRY", "AllowLabelAsInput",
]

#: class-name -> stage class, for model deserialization (the analog of the
#: reference's reflection-based stage reader)
STAGE_REGISTRY: dict[str, type["PipelineStage"]] = {}


class AllowLabelAsInput:
    """Marker: stage may legitimately consume the response feature."""


class PipelineStage:
    """Base of all stages.

    Subclasses declare:
      - ``in_types``: tuple of FeatureType classes, one per input; for
        variadic (sequence) stages set ``variadic = True`` and give the
        element type as the last entry (preceding entries are fixed inputs).
      - ``out_type``: output FeatureType class.
    """

    in_types: tuple[type[ft.FeatureType], ...] = ()
    out_type: type[ft.FeatureType] = ft.FeatureType
    variadic: bool = False
    is_raw_generator: bool = False

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        STAGE_REGISTRY[cls.__name__] = cls

    def __init__(self, operation_name: Optional[str] = None,
                 uid: Optional[str] = None):
        self.uid = uid or UID.of(type(self))
        self.operation_name = operation_name or type(self).__name__
        self._inputs: tuple[FeatureLike, ...] = ()
        self._output: Optional[Feature] = None

    # -- input/output wiring -------------------------------------------------
    def set_input(self, *features: FeatureLike) -> "PipelineStage":
        self.validate_inputs(features)
        self._inputs = tuple(features)
        self._output = None
        return self

    def validate_inputs(self, features: Sequence[FeatureLike]) -> None:
        if self.variadic:
            n_fixed = len(self.in_types) - 1
            if len(features) < n_fixed + 1:
                raise ValueError(
                    f"{self}: needs at least {n_fixed + 1} inputs, got {len(features)}")
            expected = list(self.in_types[:n_fixed]) + [self.in_types[-1]] * (
                len(features) - n_fixed)
        else:
            if len(features) != len(self.in_types):
                raise ValueError(
                    f"{self}: expects {len(self.in_types)} inputs, got {len(features)}")
            expected = list(self.in_types)
        for f, t in zip(features, expected):
            if not ft.is_subtype(f.ftype, t):
                raise TypeError(
                    f"{self}: input {f.name!r} has type {f.ftype.__name__}, "
                    f"expected {t.__name__}")
        labelish = [f for f in features if f.is_response]
        if labelish and not isinstance(self, (AllowLabelAsInput, Estimator)):
            raise ValueError(
                f"{self}: response feature(s) {[f.name for f in labelish]} "
                "cannot feed a plain transformer (label leakage)")

    @property
    def input_features(self) -> tuple[FeatureLike, ...]:
        return self._inputs

    @property
    def input_names(self) -> tuple[str, ...]:
        return tuple(f.name for f in self._inputs)

    def make_output_name(self) -> str:
        base = "-".join(f.name for f in self._inputs[:3]) or "root"
        _, n = UID.from_string(self.uid)
        return f"{base}_{len(self._inputs)}-stagesApplied_{self.operation_name}_{n:012d}"

    def output_is_response(self) -> bool:
        """Derived features stay responses only when every input is one
        (e.g. an indexed label); any predictor input makes the output a
        predictor. This is what workflow-level CV's label-dependence cut
        keys off, so response-ness must survive label derivations."""
        return bool(self._inputs) and all(f.is_response for f in self._inputs)

    def get_output(self) -> Feature:
        if not self._inputs and not self.is_raw_generator:
            raise ValueError(f"{self}: set_input before get_output")
        if self._output is None:
            self._output = Feature(
                name=self.make_output_name(), uid=UID.of("Feature"),
                ftype=self.out_type, origin_stage=self, parents=self._inputs,
                is_response=self.output_is_response(),
            )
        return self._output

    # -- serialization -------------------------------------------------------
    def config(self) -> dict:
        """JSON-able constructor arguments. Default: reflect the __init__
        signature and read identically-named attributes (the analog of the
        reference's ctor-reflection DefaultOpPipelineStageReaderWriter)."""
        sig = inspect.signature(type(self).__init__)
        out = {}
        for name, p in sig.parameters.items():
            if name in ("self", "uid") or p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
                continue
            missing = object()
            v = getattr(self, name, missing)
            if v is missing:
                v = getattr(self, "_" + name, missing)
            if v is missing:
                raise NotImplementedError(
                    f"{type(self).__name__}.config(): cannot reflect ctor arg "
                    f"{name!r}; override config()")
            out[name] = v
        return out

    @classmethod
    def from_config(cls, config: dict, uid: Optional[str] = None) -> "PipelineStage":
        return cls(uid=uid, **config)

    def fitted_state(self) -> dict[str, Any]:
        """Arrays/values learned at fit time (empty for pure transformers)."""
        return {}

    def set_fitted_state(self, state: dict[str, Any]) -> None:
        if state:
            raise NotImplementedError(
                f"{type(self).__name__} got fitted state but defines none")

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.uid})"


# ---------------------------------------------------------------------------
# Transformers
# ---------------------------------------------------------------------------

class Transformer(PipelineStage):
    """A fitted/stateless stage: maps input columns to an output column."""

    is_device: bool = False

    def runtime_input_names(self) -> tuple[str, ...]:
        """Inputs actually required at transform time. Prediction models
        declare (label, features) but consume only features, so scoring
        works on label-less data (reference SelectedModel.transformFn)."""
        return self.input_names

    def transform_row(self, *values: Any) -> Any:
        """Single-record scoring on plain python values (None = missing)."""
        raise NotImplementedError

    def output_column(self, data: "Any") -> Any:  # -> HostColumn | DeviceColumn
        """Columnar transform against a PipelineData; dispatched by executor."""
        raise NotImplementedError


class HostTransformer(Transformer):
    """Eager numpy/python columnar transformer (string-shaped work)."""

    def host_apply(self, *cols: HostColumn) -> HostColumn:
        """Default: row-loop over transform_row (override to vectorize)."""
        n = len(cols[0]) if cols else 0
        vals = [self.transform_row(*(c.python_value(i) for c in cols))
                for i in range(n)]
        return HostColumn.from_values(self.out_type, vals)

    def output_column(self, data) -> HostColumn:
        cols = [data.host_col(n) for n in self.runtime_input_names()]
        return self.host_apply(*cols)

    def device_output_column(self, data):
        """The output as a DEVICE column, for a stage that can fill it
        there from less than the host column holds (a sparse vector from
        its entries); None, the default, sends the batch executor to
        :meth:`output_column`."""
        return None


class DeviceTransformer(Transformer):
    """Jittable columnar transformer, fused per DAG layer by the executor.

    ``device_apply(params, *cols)`` must be pure in its arguments: all fitted
    state rides in the params pytree; static config (widths, flags) may be
    read from ``self`` (it is closed over at trace time and must be
    trace-stable).
    """

    is_device = True

    def device_params(self) -> Any:
        return ()

    def quantize_device_params(self, precision: str) -> Any:
        """Precision-ladder hook: return a params pytree specialized for a
        non-f32 rung, or ``None`` to use ``device_params()`` with the
        builder's generic float cast. Stages with quantizable weight
        payloads (linear/GLM/MLP/NB matmul weights, tree index/threshold
        arrays) override this; returned trees may carry
        ``QuantizedTensor``/``ExactTensor`` leaves which the fused program
        materializes in-trace, so ``device_apply`` stays unchanged."""
        return None

    def device_apply(self, params: Any, *cols: Any) -> Any:
        raise NotImplementedError

    def output_column(self, data) -> Any:
        cols = [data.device_col(n) for n in self.runtime_input_names()]
        return self.device_apply(self.device_params(), *cols)


class LambdaTransformer(HostTransformer):
    """Arbitrary-arity row-function transformer — the analog of the reference
    ``Unary/Binary/Ternary/Quaternary/SequenceTransformer`` lambda bases.

    The lambda operates on plain python values. Not serializable unless the
    function is importable (module-level), mirroring the reference's
    requirement that lambdas be stable classes for serialization.
    """

    def __init__(self, fn: Callable, in_types: tuple, out_type: type,
                 operation_name: Optional[str] = None, variadic: bool = False,
                 uid: Optional[str] = None):
        self.in_types = tuple(in_types)
        self.out_type = out_type
        self.variadic = variadic
        self.fn = fn
        super().__init__(operation_name=operation_name or getattr(
            fn, "__name__", "lambda"), uid=uid)

    def transform_row(self, *values):
        return self.fn(*values)

    def config(self) -> dict:
        fn = self.fn
        mod, qn = getattr(fn, "__module__", None), getattr(fn, "__qualname__", "")
        if not mod or "<lambda>" in qn or "<locals>" in qn:
            raise NotImplementedError(
                "LambdaTransformer with a non-importable function cannot be "
                "serialized; define the function at module level")
        return {
            "fn": f"{mod}:{qn}",
            "in_types": [t.__name__ for t in self.in_types],
            "out_type": self.out_type.__name__,
            "operation_name": self.operation_name,
            "variadic": self.variadic,
        }

    @classmethod
    def from_config(cls, config: dict, uid: Optional[str] = None):
        import importlib
        mod, _, qn = config["fn"].partition(":")
        obj: Any = importlib.import_module(mod)
        for part in qn.split("."):
            obj = getattr(obj, part)
        return cls(
            fn=obj,
            in_types=tuple(ft.feature_type_of(t) for t in config["in_types"]),
            out_type=ft.feature_type_of(config["out_type"]),
            operation_name=config["operation_name"],
            variadic=config["variadic"], uid=uid,
        )


# ---------------------------------------------------------------------------
# Estimators
# ---------------------------------------------------------------------------

class Estimator(PipelineStage):
    """A stage that learns state from data and yields a fitted Transformer.

    Parity: reference ``UnaryEstimator.fit`` etc. — ``fit`` sees the pipeline
    data (host + device views) and must return a Transformer wired to the
    same inputs/uid-derived output so DAG identity is preserved.
    """

    def fit(self, data: "Any") -> Transformer:
        model = self.fit_model(data)
        model._inputs = self._inputs
        model._output = self._output  # share the output feature node
        if model._output is None:
            # materialize output feature from the estimator so downstream
            # features built pre-fit keep pointing at the right node
            model._output = self.get_output()
        return model

    def fit_model(self, data: "Any") -> Transformer:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Raw feature origin
# ---------------------------------------------------------------------------

class FeatureGeneratorStage(PipelineStage):
    """Stage 0 of every DAG: extracts a raw feature from an input record.

    Parity: reference ``stages/FeatureGeneratorStage.scala:66-120`` —
    ``extract_fn: record -> python value`` plus an optional monoid aggregator
    and time window for event-level -> entity-level rollup (executed by the
    readers, not the DAG executor).
    """

    is_raw_generator = True

    def __init__(self, name: str, ftype_name: str,
                 extract_fn: Optional[Callable[[Any], Any]] = None,
                 aggregator: Optional[Any] = None,
                 is_response: bool = False,
                 uid: Optional[str] = None):
        super().__init__(operation_name=f"raw_{name}", uid=uid)
        self.name = name
        self.ftype_name = ftype_name
        self.extract_fn = extract_fn
        self.aggregator = aggregator
        self.is_response = is_response
        self.out_type = ft.feature_type_of(ftype_name)

    def extract(self, record: Any) -> Any:
        if self.extract_fn is not None:
            return self.extract_fn(record)
        if isinstance(record, dict):
            return record.get(self.name)
        return getattr(record, self.name)

    def output_is_response(self) -> bool:
        return self.is_response

    def make_output_name(self) -> str:
        return self.name

    def get_output(self) -> Feature:
        if self._output is None:
            self._output = Feature(
                name=self.name, uid=UID.of("Feature"), ftype=self.out_type,
                origin_stage=self, parents=(), is_response=self.is_response)
        return self._output

    def config(self) -> dict:
        return {
            "name": self.name, "ftype_name": self.ftype_name,
            "is_response": self.is_response,
        }
