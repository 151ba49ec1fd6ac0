"""``transmogrifai_tpu profile`` — score a dataset under full tracing and
emit the merged Perfetto/chrome://tracing timeline plus a top-K
slowest-stages table.

    python -m transmogrifai_tpu.cli profile --model model_dir \
        --input data.csv --trace-out trace.json --metrics-out metrics.json

The run opens one ``jax.profiler`` trace (device timeline, when the
backend supports it), records the hierarchical host span tree
(``utils/tracing.py``) through ingest, every DAG stage, and the fused
layer dispatches, then fuses both into ``--trace-out`` — open it at
chrome://tracing or https://ui.perfetto.dev. The phase and stage tables,
and the device seconds by ``(module, named scope)`` where the backend has a
device plane, print to stderr; ``--metrics-out`` saves the same
``AppMetrics`` json. See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import argparse
import json
import sys

__all__ = ["add_profile_args", "run_profile"]


def add_profile_args(sp: argparse.ArgumentParser) -> None:
    sp.add_argument("--model", required=True, help="saved model directory")
    sp.add_argument("--input", required=True,
                    help="dataset to score: .csv / .parquet / .avro path")
    sp.add_argument("--trace-out", required=True,
                    help="write the merged chrome-trace JSON here")
    sp.add_argument("--metrics-out", default=None,
                    help="write the AppMetrics json here")
    sp.add_argument("--top-k", type=int, default=10,
                    help="slowest-stages table size (default 10)")
    sp.add_argument("--no-device-trace", action="store_true",
                    help="skip the jax.profiler device trace (host spans "
                         "only; cheaper, works on any backend)")


def _reader_for(path: str):
    from transmogrifai_tpu.readers.factory import DataReaders
    if path.endswith(".csv"):
        return DataReaders.Simple.csv_auto(path)
    if path.endswith((".parquet", ".pq")):
        return DataReaders.Simple.parquet(path)
    if path.endswith(".avro"):
        return DataReaders.Simple.avro(path)
    raise ValueError(f"unsupported input {path!r}: expected "
                     ".csv/.parquet/.avro")


def run_profile(args: argparse.Namespace) -> int:
    from transmogrifai_tpu.utils.profiling import OpStep, profiler
    from transmogrifai_tpu.workflow import load_model

    trace_dir = None
    if not args.no_device_trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="transmogrifai_profile_")
    profiler.reset(app_name="transmogrifai_tpu.profile",
                   trace_dir=trace_dir)
    model = load_model(args.model)
    reader = _reader_for(args.input)
    try:
        with profiler.phase(OpStep.SCORING):
            scores = model.score(reader)
        metrics = profiler.finalize()
    finally:
        if trace_dir is not None:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    summary = metrics.export_chrome_trace(args.trace_out)
    if args.metrics_out:
        metrics.save(args.metrics_out)
    print(metrics.pretty(top_k=args.top_k), file=sys.stderr)
    print(f"# scored {scores.n_rows} rows; trace -> {args.trace_out} "
          f"({json.dumps(summary)}); open at chrome://tracing or "
          "https://ui.perfetto.dev", file=sys.stderr)
    return 0
