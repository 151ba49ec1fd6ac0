"""Columns of free text through ``SmartTextVectorizer`` at its defaults:
the fit's cardinality decision without a vocabulary, one native
tokenize-and-hash pass a column with eligibility a ROW, the vector filled
on the device from row-ordered entries in pieces whose size follows the
rows alone, and a whole small train against the benchmark's plain
reference (``chipbench/reference_amazon.py``, which imports nothing of the
program).
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

from chipbench import compare_amazon, data, pipeline  # noqa: E402
from transmogrifai_tpu import frame as fr  # noqa: E402
from transmogrifai_tpu.ops import smart_text  # noqa: E402
from transmogrifai_tpu.ops.vectorizers import hashing  # noqa: E402
from transmogrifai_tpu.types import feature_types as ft  # noqa: E402
from transmogrifai_tpu.utils.profiling import profiler, sweep_counters  # noqa: E402
from transmogrifai_tpu.utils.tracing import recorder  # noqa: E402

SEED = 2147483869


def _config() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "amazon_polarity_text.json")) as fh:
        return json.load(fh)


def _stage(cols: dict, **kw):
    """A fitted ``SmartTextModel`` over text columns, and their data."""
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.pipeline_data import PipelineData
    frame = fr.HostFrame.from_dict({k: (ft.Text, v) for k, v in cols.items()})
    feats = FeatureBuilder.from_frame(frame)
    data_ = PipelineData(frame, {})
    est = smart_text.SmartTextVectorizer(**kw).set_input(*feats.values())
    return est.fit(data_), data_


def _odd_text(n: int = 600) -> np.ndarray:
    """Distinct strings of every kind the tokenizer meets: punctuation,
    digits, underscores, mixed case, apostrophes, doubled spaces, empty
    strings, nulls, rows that are not ASCII, a row past the native row
    length and a token longer than the native token buffer."""
    rng = np.random.default_rng(3)
    words = ["Hello", "WORLD", "it's", "a_b", "c-d", "x2", "42", "MiXeD",
             "snake_case_word", "end.", "(paren)", "semi;colon", "tab\there"]
    col = np.array([
        f"{' '.join(rng.choice(words, size=rng.integers(1, 30)))}  row{i}!"
        for i in range(n)], dtype=object)
    col[5] = None
    col[6] = ""
    col[7] = "___ --- !!!"
    col[8] = "café naïve Über “quoted” don’t 5€"
    col[9] = "plain then é"
    col[10] = "漢字かな mixed with ascii"
    col[11] = " ".join(f"w{i}" for i in range(1500))      # 8,390 characters
    col[12] = "x" * 5000 + " tail"                        # one 5,000-byte token
    col[13] = "UPPER lower Upper"
    col[n - 1] = None
    return col


@pytest.mark.parametrize("tracked", [True, False])
def test_columnar_free_text_equals_the_row_path_to_the_bit(tracked):
    """The host fill from the native pass's entries, the device fill and
    ``transform_row`` agree bit for bit on every kind of row."""
    col = _odd_text()
    model, data_ = _stage({"body": col}, num_hash_features=64,
                          track_nulls=tracked, track_text_len=tracked)
    assert model.treatments == [{"kind": "hash"}]
    profiler.reset(app_name="test")
    host = model.output_column(data_).values
    counters = sweep_counters.run_to_json()
    # rows 8, 9, 10 are not ASCII, rows 11 and 12 are past the row length
    assert counters["feTextPythonRows"] == 5
    assert counters["feHashPerRowFallbacks"] == 0
    assert counters["feTextEntries"] == np.count_nonzero(host[:, :64])
    assert counters["feTextTokens"] == host[:, :64].sum()
    want = np.stack([model.transform_row(v) for v in col])
    assert host.dtype == np.float32 and np.array_equal(host, want)
    dev = model.device_output_column(data_)
    assert dev is not None and np.array_equal(np.asarray(dev.values), want)
    assert host[12, :64].sum() == 2.0 and host[11, :64].sum() == 1500.0


def test_device_fill_takes_its_entries_in_pieces(monkeypatch):
    """More entries than one piece holds go up in several calls of the one
    program; the last piece is filled up and its filling dropped."""
    assert smart_text.entry_piece(1) == 1 << 12
    assert smart_text.entry_piece(360_000) == smart_text.entry_piece(
        500_000) == 1 << 22
    assert smart_text.entry_piece(10 ** 7) == 1 << 22
    monkeypatch.setattr(smart_text, "entry_piece", lambda n: 256)
    col = _odd_text()
    model, data_ = _stage({"body": col, "head": col[::-1].copy()},
                          num_hash_features=64)
    n0 = len(recorder.spans)
    dev = model.device_output_column(data_)
    host = model.output_column(data_).values
    assert np.array_equal(np.asarray(dev.values), host)
    entries = [s.attrs["entries"] for s in recorder.spans[n0:]
               if s.name == "fe.tokenize"][:2]
    assert min(entries) > 10 * 256       # tens of pieces a column


def test_one_odd_row_takes_the_python_tokenizer_alone():
    """One row that is not ASCII among 10,000 sends that row, and no other,
    through the Python tokenizer; no column falls to the per-row loop."""
    rng = np.random.default_rng(4)
    col = np.array([f"review {i} of item{rng.integers(0, 10 ** 6)} ok"
                    for i in range(10_000)], dtype=object)
    col[4321] = "très bien"
    model, data_ = _stage({"body": col})
    profiler.reset(app_name="test")
    n0 = len(recorder.spans)
    host = model.output_column(data_).values
    counters = sweep_counters.run_to_json()
    assert counters["feTextPythonRows"] == 1
    assert counters["feHashPerRowFallbacks"] == 0
    assert counters["feDistinctValues"] == 0     # no dictionary was built
    spans = [s for s in recorder.spans[n0:] if s.name.startswith("fe.")]
    assert [s.name for s in spans] == ["fe.scan", "fe.tokenize"]
    assert spans[1].attrs["pythonRows"] == 1
    assert spans[1].attrs["rows"] == 10_000
    assert np.array_equal(host[4321], model.transform_row(col[4321]))
    assert np.array_equal(host[17], model.transform_row(col[17]))


@pytest.mark.parametrize("distinct", [99, 100, 101, 10_000])
def test_fit_decides_as_text_stats_does(distinct):
    """The vectorized fit (a pass that stops at the 101st distinct value)
    names the treatment ``TextStats`` names, value by value."""
    n = 12_000
    rng = np.random.default_rng(distinct)
    values = np.array([f"value {i:05d}" for i in range(distinct)],
                      dtype=object)
    col = values[np.concatenate([np.arange(distinct), rng.integers(
        0, distinct, size=n - distinct)])]
    rng.shuffle(col)
    col[rng.uniform(size=n) < 0.01] = None
    stats = smart_text.TextStats(max_cardinality=100)
    for v in col:
        stats.add(v)
    n0 = len(recorder.spans)
    model, _ = _stage({"c": col})
    kind = model.treatments[0]["kind"]
    assert kind == ("hash" if stats.overflowed else "pivot")
    assert stats.overflowed == (distinct > 100)
    encoded = [s for s in recorder.spans[n0:] if s.name == "fe.dict_encode"]
    # an overflowing column is never dictionary-encoded by the fit
    assert bool(encoded) == (not stats.overflowed)
    if kind == "pivot":
        top = sorted(stats.counts, key=lambda v: (-stats.counts[v], v))[:20]
        assert model.treatments[0]["categories"] == top


def test_repeating_values_keep_the_per_value_fill():
    """A hashed column whose values repeat (at most half distinct) is
    dictionary-encoded and filled from per-value slots; one whose values do
    not is tokenized row by row in the native pass. Both equal the row
    path."""
    rng = np.random.default_rng(6)
    ids = np.array([f"{v:08x}" for v in rng.integers(0, 3000, size=9000)],
                   dtype=object)
    free = np.array([f"{v:08x} seen once" for v in range(9000)],
                    dtype=object)
    model, data_ = _stage({"ids": ids, "free": free}, num_hash_features=64)
    n0 = len(recorder.spans)
    dev = model.device_output_column(data_)
    by_column: dict = {}
    for s in recorder.spans[n0:]:
        if s.name.startswith("fe.") and "column" in s.attrs:
            by_column.setdefault(s.attrs["column"], []).append(s.name)
    assert by_column["ids"] == ["fe.scan", "fe.dict_encode", "fe.hash"]
    assert by_column["free"] == ["fe.scan", "fe.tokenize"]
    want = np.stack([model.transform_row(a, b) for a, b in zip(ids, free)])
    assert np.array_equal(np.asarray(dev.values), want)
    assert np.array_equal(model.output_column(data_).values, want)


@pytest.fixture(scope="module", params=["copies", "in_place"])
def small_train(request):
    """One whole train of 6,000 generated reviews through the benchmark's
    pipeline at the library's defaults, read back and compared as a
    benchmark run compares its timed unit; once with the Newton points on
    copies of the folds (they fit at this size), once as at the cell's
    size, where they do not and the points train in place."""
    from chipbench.units import train as kind
    from transmogrifai_tpu.models import linear
    config = _config()
    table = data.make_table(config["dataset"], 6000, SEED)
    frame = pipeline.to_frame(table)
    profiler.reset(app_name="test")
    with pytest.MonkeyPatch.context() as patch:
        if request.param == "in_place":
            patch.setattr(linear, "_newton_copies_fit", lambda k, n, d: False)
        model, handles, summary = kind.train_unit(frame, config["pipeline"])
    counters = dict(sweep_counters.run_to_json())
    produced = compare_amazon.collect(
        model, handles, summary, frame, config["pipeline"],
        np.random.default_rng(0))
    numbers = compare_amazon.check(produced, table, config)
    return config, table, produced, numbers, counters


def test_generated_reviews_have_the_stated_shape(small_train):
    config, table, _, _, _ = small_train
    text, title = table.cats["text"], table.cats["title"]
    assert set(table.cats) == {"title", "text"} and not table.nums
    assert table.label.mean() == 0.5              # cut at the median
    assert sum(not v.isascii() for v in text) == 30        # 0.5%
    assert sum(len(v) > hashing._NATIVE_MAX_LEN for v in text) == 1
    assert sum(v is None for v in title) == 12 and None not in set(text)
    again = data.make_table(config["dataset"], 6000, SEED)
    assert np.array_equal(again.cats["text"], text)
    other = data.make_table(config["dataset"], 6000, SEED, stream=1)
    assert not np.array_equal(other.cats["text"], text)
    assert sum(not v.isascii() for v in other.cats["text"]) == 30


def test_small_train_agrees_with_the_reference(small_train):
    """What does not depend on 200 Adam steps having converged on 3,600
    rows a fold is held to the configuration's own limits."""
    config, _, produced, numbers, counters = small_train
    limits = config["limits"]
    assert produced["vector"].shape[1] == 1028
    assert produced["keep"].size == 1027          # text's null indicator
    for name in ("fe_max_abs", "kept_mismatch", "sweep_failures",
                 "sweep_loop_fallbacks", "fe_hash_fallbacks", "cv_gap_lr_l2"):
        assert numbers[name] <= limits[name], (name, numbers[name])
    assert numbers["fe_max_abs"] == 0.0
    # at 3,600 rows a fold (3.5 a column) regularization decides the race
    # and the leaders lie within what 200 Adam steps leave undecided; at
    # the cell's size the weakest pure-L2 point leads by 2e-2
    assert numbers["winner_regret"] < 5e-2
    assert len(produced["cv"]) == 12
    assert numbers["cv_gap_svc"] < 5e-2
    assert numbers["cv_gap_lr_l1"] < 5e-2
    assert counters["sweepHostSyncs"] == 1
    assert counters["feTextPythonRows"] == 31     # 30 not ASCII, 1 long
    assert counters["feTextTokens"] > 6000 * 70


def test_second_train_on_a_fresh_table_compiles_nothing(tmp_path):
    """The number of entries differs from table to table; they go up in
    pieces whose size follows the rows alone, so a train on ANOTHER table
    of the same rows loads every feature-engineering program."""
    from test_devicewatch import _persistent_cache_in
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    from transmogrifai_tpu.workflow import Workflow
    compile_telemetry.ensure_listener()
    config = _config()

    def vectorize(seed):
        table = data.make_table(config["dataset"], 1500, seed)
        frame = pipeline.to_frame(table)
        feats = FeatureBuilder.from_frame(frame, response="label")
        feats.pop("label")
        vec = transmogrify(list(feats.values()))
        model = Workflow().set_input_frame(frame).set_result_features(
            vec).train()
        out = model.compute_data_up_to(vec, frame)
        return np.asarray(out[vec.name].values).sum()

    def programs():
        return {site: c.get("programs", 0) for site, c in
                compile_telemetry.to_json()["bySite"].items()}
    with _persistent_cache_in(tmp_path):
        before = compile_telemetry.programs
        first = vectorize(SEED)
        if compile_telemetry.programs == before:
            pytest.skip("jax.monitoring backend-compile events unavailable")
        p1 = programs()
        second = vectorize(SEED + 1)
        p2 = programs()
    assert first != second                      # another table, other tokens
    assert p2 == p1, {s: (p1.get(s), n) for s, n in p2.items()
                      if p1.get(s) != n}
