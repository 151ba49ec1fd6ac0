"""Criteo-shaped ingest + transmogrify benchmark.

SURVEY §6 / BASELINE.json name Criteo-1TB (13 numeric + 26 categorical
columns of click logs) as the pod-scale config. This bench builds the same
column shape synthetically at ``CRITEO_ROWS`` (default 10M) and times the
ingest-side hot path this repo optimized natively:

- text -> codes dictionary encoding (``native/dict_encode.cpp`` C++ pass;
  the pre-round-3 per-row Python loop is timed alongside for the record)
- bulk host -> device upload of the numeric block
- ``.transmogrify()`` vectorization of the full 39-column frame at a
  100k-row slice (the per-stage fit work; scaling it is the row-parallel
  mesh's job, measured by bench.py)

Prints one JSON line. Run: ``python benchmarks/bench_criteo_ingest.py``
(CRITEO_ROWS=200000 for a quick pass).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

N_ROWS = int(os.environ.get("CRITEO_ROWS", 10_000_000))
N_NUM, N_CAT = 13, 26
#: per-column cardinalities cycle through Criteo-like magnitudes
CARDS = [10, 100, 1000, 10_000, 100_000]


def synth_columns(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    nums = {f"i{j}": rng.normal(size=n).astype(np.float64)
            for j in range(N_NUM)}
    cats = {}
    for j in range(N_CAT):
        card = CARDS[j % len(CARDS)]
        codes = rng.integers(0, card, n)
        vals = np.array([f"c{j}_{v}" for v in range(card)], dtype=object)
        col = vals[codes]
        # Criteo columns carry missing values
        col[rng.uniform(size=n) < 0.05] = None
        cats[f"c{j}"] = col
    label = (rng.uniform(size=n) < 0.25).astype(np.float64)
    return nums, cats, label


def main() -> int:
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.pipeline_data import PipelineData
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.dict_encode import (
        _native, dict_encode, dict_encode_py,
    )

    t0 = time.time()
    nums, cats, label = synth_columns(N_ROWS)
    synth_s = time.time() - t0

    # --- dictionary encoding: native vs the old per-row Python loop ------
    t0 = time.time()
    encoded = {name: dict_encode(col) for name, col in cats.items()}
    encode_s = time.time() - t0
    total_uniques = sum(len(v) for _, v in encoded.values())

    py_rows = min(N_ROWS, 500_000)  # the old loop at full 10M would crawl
    t0 = time.time()
    # one column per cardinality class so the extrapolation isn't skewed
    # toward the cheap low-cardinality columns
    n_sampled = len(CARDS)
    for name in list(cats)[:n_sampled]:
        dict_encode_py(cats[name][:py_rows])
    python_encode_extrapolated_s = ((time.time() - t0)
                                    * (N_ROWS / py_rows)
                                    * (N_CAT / n_sampled))

    # the Criteo pain point is the HIGH-cardinality columns (hash-table
    # misses kill the Python dict loop there); time that class head-to-head
    hc = next(name for j, name in enumerate(cats)
              if CARDS[j % len(CARDS)] == max(CARDS))
    hc_rows = min(N_ROWS, 2_000_000)  # python dict cost grows with scale
    t0 = time.time()
    dict_encode(cats[hc][:hc_rows])
    hc_native_s = time.time() - t0
    t0 = time.time()
    dict_encode_py(cats[hc][:hc_rows])
    hc_python_s = time.time() - t0

    # --- frame build + device ingest ------------------------------------
    cols = {n_: fr.HostColumn(ft.Real, v, np.isfinite(v))
            for n_, v in nums.items()}
    for n_, v in cats.items():
        cols[n_] = fr.HostColumn(ft.PickList, v)
    cols["label"] = fr.HostColumn(ft.RealNN, label, np.ones(N_ROWS, bool))
    frame = fr.HostFrame(cols)

    t0 = time.time()
    data = PipelineData.from_host(frame)
    import jax
    data.device_col("i0")            # triggers the bulk numeric upload
    codes0 = data.device_col("c0")   # dictionary-encode + upload one cat
    jax.block_until_ready(codes0.codes)
    upload_s = time.time() - t0

    # --- transmogrify at a bounded slice ---------------------------------
    slice_rows = min(N_ROWS, 100_000)
    idx = np.arange(slice_rows)
    sl = fr.HostFrame({k: c.take(idx) for k, c in cols.items()})
    t0 = time.time()
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.dag import DagExecutor, compute_dag
    feats = FeatureBuilder.from_frame(sl, response="label")
    feats.pop("label")
    vec = transmogrify(list(feats.values()))
    out, _ = DagExecutor().fit_transform(
        PipelineData.from_host(sl), compute_dag([vec]))
    width = int(out.device_col(vec.name).values.shape[1])
    transmogrify_s = time.time() - t0

    print(json.dumps({
        "metric": "criteo_shape_ingest",
        "rows": N_ROWS,
        "columns": {"numeric": N_NUM, "categorical": N_CAT},
        "native_dict_encode": _native() is not None,
        "dict_encode_s": round(encode_s, 2),
        "dict_encode_rows_per_s": round(N_ROWS * N_CAT / encode_s),
        "python_loop_extrapolated_s": round(
            python_encode_extrapolated_s, 2),
        "speedup_vs_python_loop": round(
            python_encode_extrapolated_s / encode_s, 1),
        "high_cardinality_column": {
            "rows": hc_rows, "cardinality": max(CARDS),
            "native_s": round(hc_native_s, 2),
            "python_s": round(hc_python_s, 2),
            "speedup": round(hc_python_s / max(hc_native_s, 1e-9), 1)},
        "total_vocab": total_uniques,
        "numeric_upload_s": round(upload_s, 2),
        "transmogrify_rows": slice_rows,
        "transmogrify_s": round(transmogrify_s, 2),
        "transmogrify_width": width,
        "synth_s": round(synth_s, 2),
        "platform": jax.devices()[0].platform,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
