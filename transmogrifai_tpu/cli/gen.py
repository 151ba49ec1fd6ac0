"""Project generator: schema -> a runnable AutoML project.

Parity: reference ``cli/src/main/scala/com/salesforce/op/cli/`` (``op gen``)
— from a CSV (auto-inferred schema) or an Avro schema file plus an id and a
response column, emit a buildable project with a reader, typed feature
definitions, a problem-kind-matched workflow, and a run script
(``CliExec.scala:42-83``, ``gen/Ops.scala``, ``gen/ProblemSchema.scala``).

The generated project is plain Python against transmogrifai_tpu — run it
with ``python run.py train``.
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

import numpy as np

from transmogrifai_tpu.types import feature_types as ft

__all__ = ["ProblemKind", "detect_problem_kind", "generate_project"]


class ProblemKind:
    BINARY = "binary"
    MULTICLASS = "multiclass"
    REGRESSION = "regression"


def detect_problem_kind(values, ftype: type[ft.FeatureType]) -> str:
    """Reference ``gen/ProblemSchema.scala`` kind detection: two distinct
    response values -> binary; text-ish or low-cardinality integers ->
    multiclass; otherwise regression."""
    present = [v for v in values if v is not None]
    distinct = set(present)
    if issubclass(ftype, ft.Binary) or len(distinct) == 2:
        return ProblemKind.BINARY
    if issubclass(ftype, ft.Text):
        return ProblemKind.MULTICLASS
    if issubclass(ftype, ft.Integral) and len(distinct) <= 30:
        return ProblemKind.MULTICLASS
    return ProblemKind.REGRESSION


def _safe_name(s: str) -> str:
    out = re.sub(r"\W+", "_", s)
    return out if not out[:1].isdigit() else "_" + out


def generate_project(name: str, input_path: str, id_col: str,
                     response_col: str, output_dir: str,
                     avro_schema_path: Optional[str] = None,
                     overwrite: bool = False) -> str:
    """Generate ``output_dir/name`` with features.py/workflow.py/run.py.
    Returns the project path."""
    if avro_schema_path:
        from transmogrifai_tpu.readers.avro import feature_schema_of_avro
        with open(avro_schema_path) as fh:
            schema = feature_schema_of_avro(json.load(fh))
        sample_rows = []
    elif input_path.endswith(".csv"):
        from transmogrifai_tpu.readers.csv import CSVReader
        reader = CSVReader(input_path)
        schema = dict(reader.schema)
        sample_rows = list(reader.read())[:2000]
    elif input_path.endswith((".parquet", ".pq")):
        from transmogrifai_tpu.readers.parquet import ParquetReader
        reader = ParquetReader(input_path)
        schema = dict(reader.schema())
        sample_rows = list(reader.read())[:2000]
    else:
        raise ValueError(f"Cannot infer a schema from {input_path!r}; "
                         "pass --schema for non-CSV/parquet inputs")
    if response_col not in schema:
        raise KeyError(f"Response column {response_col!r} not in schema "
                       f"{sorted(schema)}")
    if id_col not in schema:
        raise KeyError(f"Id column {id_col!r} not in schema")

    resp_type = schema[response_col]
    if sample_rows:
        kind = detect_problem_kind(
            [r.get(response_col) for r in sample_rows], resp_type)
    elif issubclass(resp_type, ft.Binary):
        kind = ProblemKind.BINARY
    elif issubclass(resp_type, ft.Real):
        kind = ProblemKind.REGRESSION
    else:
        kind = ProblemKind.MULTICLASS
    # ANY non-numeric response (binary included: e.g. a 'yes'/'no' text
    # column) must go through a string indexer before the selectors, whose
    # label input is RealNN (reference gen/ProblemSchema.scala indexes all
    # categorical responses)
    needs_index = resp_type.__name__ not in (
        "RealNN", "Real", "Integral", "Binary")

    proj = os.path.join(output_dir, name)
    if os.path.exists(proj) and not overwrite:
        raise FileExistsError(proj)
    os.makedirs(proj, exist_ok=True)

    predictors = {c: t for c, t in schema.items()
                  if c not in (response_col, id_col)}
    _write(proj, "features.py", _features_py(
        schema, id_col, response_col, kind, needs_index))
    _write(proj, "workflow.py", _workflow_py(
        name, input_path, id_col, response_col, kind, predictors,
        needs_index))
    _write(proj, "run.py", _run_py(name, kind))
    _write(proj, "params.json", json.dumps({
        "modelLocation": "./model",
        "metricsLocation": "./metrics.json",
        "customParams": {},
    }, indent=2))
    _write(proj, "README.md", _readme_md(name, input_path, kind,
                                         response_col))
    return proj


def _write(proj: str, fname: str, content: str) -> None:
    with open(os.path.join(proj, fname), "w") as fh:
        fh.write(content)


def _features_py(schema, id_col, response_col, kind, needs_index) -> str:
    lines = [
        '"""Typed raw feature definitions (generated by transmogrifai_tpu '
        'gen)."""',
        "",
        "from transmogrifai_tpu.stages.base import FeatureGeneratorStage",
        "",
    ]
    for col, fty in schema.items():
        var = _safe_name(col)
        is_resp = col == response_col
        # responses must be non-nullable numerics for the selectors; text
        # labels go through an indexer in workflow.py
        tname = fty.__name__
        if is_resp and not needs_index and tname in ("Real", "Integral",
                                                     "Binary"):
            tname = "RealNN"
        lines.append(
            f'{var} = FeatureGeneratorStage(name="{col}", '
            f'ftype_name="{tname}", is_response={is_resp}).get_output()')
    lines += [
        "",
        f'ID_FEATURE = {_safe_name(id_col)}',
        f'RESPONSE = {_safe_name(response_col)}',
        "PREDICTORS = [" + ", ".join(
            _safe_name(c) for c in schema
            if c not in (response_col, id_col)) + "]",
        "",
    ]
    return "\n".join(lines)


def _workflow_py(name, input_path, id_col, response_col, kind,
                 predictors, needs_index) -> str:
    selector = {
        ProblemKind.BINARY: "BinaryClassificationModelSelector",
        ProblemKind.MULTICLASS: "MultiClassificationModelSelector",
        ProblemKind.REGRESSION: "RegressionModelSelector",
    }[kind]
    label_expr = "RESPONSE"
    extra_imports = ""
    if needs_index:
        extra_imports = ("from transmogrifai_tpu.ops.indexers import "
                         "OpStringIndexerNoFilter\n")
        label_expr = "RESPONSE.transform_with(OpStringIndexerNoFilter())"
    return f'''"""AutoML workflow for {name} (generated by transmogrifai_tpu gen)."""

from transmogrifai_tpu import dsl  # noqa: F401 — installs feature DSL
from transmogrifai_tpu.ops.transmogrifier import transmogrify
from transmogrifai_tpu.readers import DataReaders
from transmogrifai_tpu.selector import {selector}
from transmogrifai_tpu.workflow import Workflow
{extra_imports}
from features import ID_FEATURE, PREDICTORS, RESPONSE  # noqa: F401


def make_reader(path: str = {input_path!r}):
    return DataReaders.Simple.csv_auto(path, key_col={id_col!r}) \\
        if path.endswith(".csv") else DataReaders.Simple.parquet(
            path, key_col={id_col!r})


def make_workflow(path: str = {input_path!r}) -> Workflow:
    label = {label_expr}
    features = transmogrify(PREDICTORS)
    checked = label.sanity_check(features)
    selector = {selector}.with_cross_validation(n_folds=3)
    prediction = label.transform_with(selector, checked)
    return (Workflow()
            .set_reader(make_reader(path))
            .set_result_features(prediction, checked))
'''


def _run_py(name, kind) -> str:
    evaluator = {
        ProblemKind.BINARY: "OpBinaryClassificationEvaluator",
        ProblemKind.MULTICLASS: "OpMultiClassificationEvaluator",
        ProblemKind.REGRESSION: "OpRegressionEvaluator",
    }[kind]
    return f'''"""Entry point for {name}: python run.py train|score|evaluate."""

import sys

from transmogrifai_tpu.evaluators import {evaluator}
from transmogrifai_tpu.params import OpParams
from transmogrifai_tpu.runner import RunTypes, WorkflowRunner
from transmogrifai_tpu.utils.compile_cache import enable_compile_cache

from workflow import make_reader, make_workflow


def main() -> int:
    enable_compile_cache()
    run_type = sys.argv[1] if len(sys.argv) > 1 else RunTypes.TRAIN
    params = OpParams.from_file("params.json")
    runner = WorkflowRunner(
        make_workflow(),
        evaluator={evaluator}(),
        scoring_reader_factory=lambda p: make_reader())
    result = runner.run(run_type, params)
    print(result.get("status"), "-", result.get("wallSeconds"), "s")
    return 0 if result.get("status") == "success" else 1


if __name__ == "__main__":
    sys.exit(main())
'''


def _readme_md(name, input_path, kind, response_col) -> str:
    return f"""# {name}

Generated by `transmogrifai_tpu gen` from `{input_path}`.

- Problem kind: **{kind}** (response `{response_col}`)
- `python run.py train` — train + save the model (see params.json)
- `python run.py score` — score with the saved model
- `python run.py evaluate` — score + metrics

Edit `features.py` to adjust feature types and `workflow.py` to customize
the pipeline (vectorization, sanity check thresholds, model grids).
"""
