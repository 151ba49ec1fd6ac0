"""A click-log table through the normal path at a width where the wide
branches run: string columns dictionary-encoded, pivoted or hashed, more
than 4,096 vector columns (no feature-feature correlation in SanityChecker)
and more than 2,048 checked columns (every linear point on Adam), against
the benchmark's plain reference (``chipbench/reference_criteo.py``, which
imports nothing of the program).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare_criteo, data, pipeline  # noqa: E402
from chipbench import reference_criteo as reference  # noqa: E402
from transmogrifai_tpu.ops import smart_text  # noqa: E402
from transmogrifai_tpu.utils.profiling import profiler, sweep_counters  # noqa: E402

SEED = 2147483777


def _config(hash_features: int) -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "criteo_ctr.json")) as fh:
        config = json.load(fh)
    config["pipeline"]["transmogrify"] = {
        "num_hash_features": hash_features}
    return config


def _table(n: int, config: dict):
    """``criteo_like`` rows with two columns rewritten so that one has
    exactly 100 distinct values (the most that pivots) and one 101 (the
    least that hashes)."""
    table = data.make_table(config["dataset"], n, SEED)
    rng = np.random.default_rng(5)
    for name, distinct in (("c2", 100), ("c5", 101)):
        values = np.array([f"{name}v{i:04x}" for i in range(distinct)],
                          dtype=object)
        col = values[np.concatenate([np.arange(distinct), rng.integers(
            0, distinct // 4, size=n - distinct)])]
        col[rng.uniform(size=n) < 0.05] = None
        # every value survives the nulls: the count is exact
        col[:distinct] = values
        table.cats[name] = col
    return table


def _vectorize(table, config: dict) -> np.ndarray:
    """The program's un-checked vector of ``table``, fitted on it."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.workflow import Workflow
    frame = pipeline.to_frame(table)
    feats = FeatureBuilder.from_frame(frame, response="label")
    feats.pop("label")
    vec = transmogrify(list(feats.values()),
                       **config["pipeline"]["transmogrify"])
    model = Workflow().set_input_frame(frame).set_result_features(vec).train()
    out = model.compute_data_up_to(vec, frame)
    return np.asarray(out[vec.name].values, np.float32)


@pytest.mark.parametrize("odd_rows", [False, True])
def test_vector_equals_the_reference_to_the_bit(odd_rows):
    """With ``odd_rows`` a hashed column of values seen once each holds a
    few rows that are not ASCII: the column takes the native pass, those
    rows alone the Python tokenizer, and no column the per-row loop."""
    config = _config(64)
    table = _table(2000, config)
    if odd_rows:
        free = np.array([f"Item_{i:05d} sold-by {i % 7}" for i in range(2000)],
                        dtype=object)
        free[[3, 500, 1999]] = ["crème brûlée", "naïve  café", "5€ “ok”"]
        free[10] = None
        table.cats["c3"] = free
    profiler.reset(app_name="test")
    got = _vectorize(table, config)
    fit = reference.fit_fe(table, reference.fe_settings(config))
    want = reference.apply_fe(table, fit)
    assert fit.treatments["c2"][0] == "pivot"      # exactly 100 values
    assert len(fit.treatments["c2"][1]) == 20      # top 20, the rest OTHER
    assert fit.treatments["c5"][0] == "hash"       # 101 values
    assert any(v is None for v in table.cats["c1"])
    assert got.shape == want.shape
    assert np.array_equal(got, want)
    counters = sweep_counters.run_to_json()
    assert counters["feHashPerRowFallbacks"] == 0
    assert counters["feDistinctValues"] > 0
    # fit-time transform and the read-back each fill the columns once
    assert odd_rows == (counters["feTextPythonRows"] >= 2 * 3)


@pytest.fixture(scope="module")
def small_train():
    """One whole train of 4,000 rows at 224 hash features a hashed column
    (4,424 vector columns: past SanityChecker's correlation width and, once
    checked, past the Newton width), on the fold-stacked path, read back
    and compared as a benchmark run compares its timed unit."""
    from chipbench.units import train as kind
    config = _config(224)
    table = data.make_table(config["dataset"], 4000, SEED)
    frame = pipeline.to_frame(table)
    profiler.reset(app_name="test")
    model, handles, summary = kind.train_unit(frame, config["pipeline"])
    counters = dict(sweep_counters.run_to_json())
    families = sweep_counters.to_json()
    produced = compare_criteo.collect(
        model, handles, summary, frame, config["pipeline"],
        np.random.default_rng(0))
    numbers = compare_criteo.check(produced, table, config)
    return produced, numbers, counters, families


def test_small_train_keeps_the_reference_columns(small_train):
    produced, numbers, _, _ = small_train
    assert produced["vector"].shape[1] > 4096
    assert produced["keep"].size > 2048
    assert numbers["fe_max_abs"] == 0.0
    assert numbers["kept_mismatch"] == 0.0
    assert numbers["sweep_failures"] == 0.0


def test_small_train_fold_metrics_agree(small_train):
    """The pure-L2 points agree closely at every strength. The L1 points
    and the hinge loss end 200 Adam(0.1) steps on a limit cycle, which at
    2,400 rows a fold and 4,100 columns turns rounding into a percent; at
    the well regularized points the hinge loss is five times closer."""
    produced, numbers, _, _ = small_train
    assert len(produced["cv"]) == 12
    assert numbers["cv_gap_lr_l2"] < 2e-4           # reads 3.4e-5
    assert numbers["cv_gap_lr_l2_stable"] < 2e-4
    assert numbers["cv_gap_lr_l1"] < 4e-2           # reads 1.0e-2
    assert numbers["cv_gap_svc"] < 4e-2             # reads 1.1e-2
    assert numbers["cv_gap_svc_stable"] < 1e-2      # reads 2.2e-3


def test_small_train_winner_and_scores_agree(small_train):
    _, numbers, _, _ = small_train
    assert numbers["winner_regret"] < 1e-3          # reads 0
    assert numbers["holdout_auroc_gap_linear"] < 5e-2   # reads 1.6e-2
    assert numbers["holdout_score_gap_linear"] < 5e-2   # reads 1.3e-2


def test_small_train_copies_the_matrix_once(small_train):
    """The stacked path, one host sync, and no matrix-sized operand beyond
    the selector's one split of the checked matrix."""
    produced, _, counters, families = small_train
    n_train = produced["n_rows"] - round(produced["n_rows"] * 0.1)
    matrix = 4 * n_train * produced["keep"].size
    assert counters["sweepHostSyncs"] == 1
    assert all(f["mode"] == "fold_stacked" for f in families.values())
    assert 0 < counters["sweepOperandBytes"] <= 2.2 * matrix
    assert counters["feHashPerRowFallbacks"] == 0


def test_wide_checked_vector_fills_whole_tiles():
    """From 4,096 kept columns on, SanityChecker fills the checked vector up
    with zero columns to a whole number of 128-column tiles, so that two
    samples of one table, whose kept widths differ by a few columns, run
    the same compiled programs; a narrow vector is left as it is."""
    import jax.numpy as jnp

    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.preparators import sanity_checker as sc
    from transmogrifai_tpu.vector_metadata import (
        VectorColumnMetadata, VectorMetadata,
    )
    assert sc.bucketed_width(4095) == 4095
    assert sc.bucketed_width(4096) == 4096
    assert sc.bucketed_width(8840) == sc.bucketed_width(8844) == 8960
    d, keep = 4300, list(range(0, 4300, 1))[:4200]
    meta = VectorMetadata("v", tuple(
        VectorColumnMetadata(("f",), ("Real",), index=i) for i in range(d)))
    pad = sc.bucketed_width(len(keep)) - len(keep)
    model = sc.DropIndicesModel(
        keep_indices=keep, out_meta=sc._padded(meta.select(keep), pad),
        pad=pad)
    X = np.random.default_rng(0).normal(size=(37, d)).astype(np.float32)
    out = model.device_apply(model.device_params(),
                             fr.VectorColumn(jnp.asarray(X), meta))
    got = np.asarray(out.values)
    assert got.shape == (37, 4224) and out.metadata.size == 4224
    assert np.array_equal(got[:, :4200], X[:, keep])
    assert not got[:, 4200:].any()
    assert np.array_equal(model.transform_row(X[3]), got[3])
    again = sc.DropIndicesModel.from_config(model.config())
    assert again.pad == pad and again.keep_indices == keep
    assert out.metadata.columns[-1].parent_feature == ("sanityCheckerPadding",)


def _text_stage(cols: dict, **kw):
    """A fitted ``SmartTextModel`` over text columns, and their data."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.pipeline_data import PipelineData
    from transmogrifai_tpu.types import feature_types as ft
    frame = fr.HostFrame.from_dict({k: (ft.Text, v) for k, v in cols.items()})
    feats = FeatureBuilder.from_frame(frame)
    data_ = PipelineData(frame, {})
    est = smart_text.SmartTextVectorizer(**kw).set_input(*feats.values())
    return est.fit(data_), data_


def _text_columns(n: int = 3000) -> dict:
    rng = np.random.default_rng(11)
    words = np.array(["Hello", "World", "hello", "a_b", "c-d", "", "___",
                      "x y z w", "one two"], dtype=object)

    def nulls(col, rate):
        col[rng.uniform(size=n) < rate] = None
        return col
    return {
        "ids": nulls(np.array([f"{v:08x}" for v in rng.integers(
            0, 900, size=n)], dtype=object), 0.05),
        "few": nulls(rng.choice(np.array(list("abcdefghijklmnopqrstuvwxy"),
                                         dtype=object), size=n), 0.4),
        "words": nulls(np.array([
            f"{rng.choice(words)} tok{rng.integers(0, 400)} "
            f"{rng.choice(words)}" for _ in range(n)], dtype=object), 0.1),
        "empty": np.array([None] * n, dtype=object),
    }


@pytest.mark.parametrize("tracked", [True, False])
def test_device_fill_equals_host_fill_to_the_bit(tracked):
    """Hashed ids, values of several tokens and of none, a pivot with
    OTHER, nulls everywhere and an all-null column: the vector filled on
    the device from each row's entries is the host fill's, bit for bit."""
    model, data_ = _text_stage(_text_columns(), num_hash_features=64,
                               track_nulls=tracked, track_text_len=tracked)
    assert [t["kind"] for t in model.treatments] == [
        "hash", "pivot", "hash", "ignore"]
    host = model.output_column(data_)
    dev = model.device_output_column(data_)
    assert dev is not None and dev.metadata == host.meta
    got = np.asarray(dev.values)
    assert got.dtype == np.float32 and got.shape == host.values.shape
    assert np.array_equal(got, host.values)
    assert got[:, :64].max() == 1.0 and got[:, -3:].sum() > 0


def test_device_fill_takes_free_text_and_leaves_objects_to_the_host():
    """A value of more slots than the per-row table carries fills on the
    device from row-ordered entries; a column holding objects that are not
    strings fills on the host, row by row, and is counted; the executor
    takes whichever the stage gives."""
    from transmogrifai_tpu.dag import DagExecutor
    cols = _text_columns()
    long_text = cols["words"].copy()
    long_text[0] = " ".join(f"w{i}" for i in range(40))
    model, data_ = _text_stage({"words": long_text}, num_hash_features=64)
    dev = model.device_output_column(data_)
    assert dev is not None
    assert np.array_equal(np.asarray(dev.values),
                          model.output_column(data_).values)
    name = model.get_output().name
    out = DagExecutor().apply_layer(data_, [model])
    assert name in out.device and name not in out.host
    class Wrapped:
        """Not a string, though the row path can tokenize it."""

        def __init__(self, text):
            self.text = text

        def lower(self):
            return self.text.lower()

        def __len__(self):
            return len(self.text)

    mixed = cols["ids"].copy()
    mixed[3] = Wrapped("Seen-Once twice TWICE")
    model2, data2 = _text_stage({"ids": cols["ids"]}, num_hash_features=64)
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.pipeline_data import PipelineData
    from transmogrifai_tpu.types import feature_types as ft
    odd = PipelineData(fr.HostFrame(
        {"ids": fr.HostColumn(ft.Text, mixed)}), {})
    assert model2.device_output_column(odd) is None
    profiler.reset(app_name="test")
    by_row = model2.output_column(odd).values
    assert sweep_counters.run_to_json()["feHashPerRowFallbacks"] == 1
    assert np.array_equal(by_row[3], model2.transform_row(mixed[3]))
    assert by_row[3, :64].sum() == 4.0 and by_row[3, 64] == 21.0
    # and where it can, the vector never exists on the host
    name2 = model2.get_output().name
    out2 = DagExecutor().apply_layer(data2, [model2])
    assert name2 in out2.device and name2 not in out2.host
    assert np.array_equal(np.asarray(out2.device[name2].values),
                          model2.output_column(data2).values)
