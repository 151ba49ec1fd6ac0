"""Vectorizer tests (parity: reference *VectorizerTest suites with
hand-computed expectations + metadata assertions)."""

import numpy as np
import pytest

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.dag import DagExecutor, compute_dag
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.ops.transmogrifier import transmogrify
from transmogrifai_tpu.ops.vectorizers import (
    BinaryVectorizer, DateToUnitCircleVectorizer, IntegralVectorizer,
    OneHotVectorizer, RealVectorizer, SetVectorizer, TextHashingVectorizer,
    VectorsCombiner,
)
from transmogrifai_tpu.ops.vectorizers.dates import TIME_PERIODS
from transmogrifai_tpu.pipeline_data import PipelineData
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.vector_metadata import NULL_INDICATOR, OTHER


def _fit_one(host, result_feature):
    data = PipelineData.from_host(host)
    dag = compute_dag([result_feature])
    ex = DagExecutor()
    out_data, fitted = ex.fit_transform(data, dag)
    return out_data, fitted, ex


def test_real_vectorizer_mean_fill_and_nulls():
    host = fr.HostFrame.from_dict({
        "a": (ft.Real, [1.0, None, 5.0]),
        "b": (ft.Real, [10.0, 20.0, 30.0]),
    })
    feats = FeatureBuilder.from_frame(host)
    out = feats["a"].transform_with(RealVectorizer(), feats["b"])
    data, fitted, _ = _fit_one(host, out)
    vec = data.device_col(out.name)
    np.testing.assert_allclose(
        np.asarray(vec.values),
        [[1.0, 0.0, 10.0, 0.0],
         [3.0, 1.0, 20.0, 0.0],
         [5.0, 0.0, 30.0, 0.0]], rtol=1e-6)
    meta = vec.metadata
    assert meta.size == 4
    assert meta.columns[1].is_null_indicator
    assert meta.columns[0].parent_feature == ("a",)
    # row path parity
    model = fitted[0][0]
    np.testing.assert_allclose(model.transform_row(None, 20.0),
                               [3.0, 1.0, 20.0, 0.0], rtol=1e-6)


def test_integral_mode_fill():
    host = fr.HostFrame.from_dict({
        "x": (ft.Integral, [3, 3, 7, None]),
    })
    feats = FeatureBuilder.from_frame(host)
    out = feats["x"].transform_with(IntegralVectorizer())
    data, fitted, _ = _fit_one(host, out)
    vec = np.asarray(data.device_col(out.name).values)
    np.testing.assert_allclose(vec[:, 0], [3, 3, 7, 3])
    np.testing.assert_allclose(vec[:, 1], [0, 0, 0, 1])


def test_binary_vectorizer():
    host = fr.HostFrame.from_dict({
        "v": (ft.Binary, [True, None, False]),
    })
    feats = FeatureBuilder.from_frame(host)
    out = feats["v"].transform_with(BinaryVectorizer())
    data, _, _ = _fit_one(host, out)
    vec = np.asarray(data.device_col(out.name).values)
    np.testing.assert_allclose(vec, [[1, 0], [0, 1], [0, 0]])


def test_onehot_topk_other_null():
    vals = ["a"] * 5 + ["b"] * 3 + ["c"] * 1 + [None]
    host = fr.HostFrame.from_dict({"p": (ft.PickList, vals)})
    feats = FeatureBuilder.from_frame(host)
    out = feats["p"].transform_with(
        OneHotVectorizer(top_k=2, min_support=2))
    data, fitted, ex = _fit_one(host, out)
    vec = np.asarray(data.device_col(out.name).values)
    meta = data.device_col(out.name).metadata
    # columns: [a, b, OTHER, NULL]
    assert [c.indicator_value for c in meta.columns] == ["a", "b", OTHER, NULL_INDICATOR]
    np.testing.assert_allclose(vec[0], [1, 0, 0, 0])   # "a"
    np.testing.assert_allclose(vec[5], [0, 1, 0, 0])   # "b"
    np.testing.assert_allclose(vec[8], [0, 0, 1, 0])   # "c" -> OTHER (support 1 < 2)
    np.testing.assert_allclose(vec[9], [0, 0, 0, 1])   # None
    # scoring with an unseen vocabulary maps to OTHER
    host2 = fr.HostFrame.from_dict({"p": (ft.PickList, ["zz", "a", None])})
    scored = ex.transform(PipelineData.from_host(host2), fitted)
    vec2 = np.asarray(scored.device_col(out.name).values)
    np.testing.assert_allclose(vec2, [[0, 0, 1, 0], [1, 0, 0, 0], [0, 0, 0, 1]])
    # row path parity
    model = fitted[0][0]
    np.testing.assert_allclose(model.transform_row("zz"), [0, 0, 1, 0])


def test_set_vectorizer():
    host = fr.HostFrame.from_dict({
        "s": (ft.MultiPickList, [{"x", "y"}, {"x"}, set(), {"rare"}]),
    })
    feats = FeatureBuilder.from_frame(host)
    out = feats["s"].transform_with(SetVectorizer(top_k=3, min_support=1))
    data, _, _ = _fit_one(host, out)
    col = data.host_col(out.name)
    meta = col.meta
    # count desc then lexicographic: x(2), rare(1), y(1)
    assert [c.indicator_value for c in meta.columns] == \
        ["x", "rare", "y", OTHER, NULL_INDICATOR]
    np.testing.assert_allclose(col.values[0], [1, 0, 1, 0, 0])
    np.testing.assert_allclose(col.values[2], [0, 0, 0, 0, 1])
    np.testing.assert_allclose(col.values[3], [0, 1, 0, 0, 0])


def test_hashing_vectorizer_deterministic():
    host = fr.HostFrame.from_dict({
        "t": (ft.Text, ["hello world hello", None]),
    })
    feats = FeatureBuilder.from_frame(host)
    stage = TextHashingVectorizer(num_features=8)
    out = feats["t"].transform_with(stage)
    data, fitted, _ = _fit_one(host, out)
    col = data.host_col(out.name)
    assert col.values.shape == (2, 9)  # 8 bins + 1 null indicator
    assert col.values[0].sum() == 3.0  # three tokens
    assert col.values[1, 8] == 1.0     # null indicator
    # row path identical
    np.testing.assert_allclose(fitted[0][0].transform_row("hello world hello"),
                               col.values[0])


def test_date_unit_circle():
    ms_6am = 6 * 3600_000
    host = fr.HostFrame.from_dict({"d": (ft.Date, [ms_6am, None])})
    feats = FeatureBuilder.from_frame(host)
    out = feats["d"].transform_with(
        DateToUnitCircleVectorizer(time_period="HourOfDay"))
    data, _, _ = _fit_one(host, out)
    vec = np.asarray(data.device_col(out.name).values)
    # 6am = quarter turn: sin=1, cos=0
    np.testing.assert_allclose(vec[0], [1.0, 0.0, 0.0], atol=1e-5)
    np.testing.assert_allclose(vec[1], [0.0, 0.0, 1.0], atol=1e-5)


@pytest.mark.parametrize("period", sorted(TIME_PERIODS))
def test_date_unit_circle_keeps_the_phase_of_2016(period):
    """Epoch milliseconds of 2016 are 131 s apart in float32; the device's
    (sin, cos) agree with the float64 ``transform_row`` (the serving path)
    to 1e-5 for every period, over whole seconds of the half year and the
    odd millisecond."""
    rng = np.random.default_rng(2016)
    ms = rng.integers(1_451_606_400_000, 1_467_331_200_000, size=4096)
    ms[:4] = [1_451_606_400_000, 1_451_692_799_999, 1_459_468_800_001,
              1_467_331_199_999]
    host = fr.HostFrame.from_dict({"d": (ft.DateTime, ms.tolist())})
    feats = FeatureBuilder.from_frame(host)
    stage = DateToUnitCircleVectorizer(time_period=period)
    out = feats["d"].transform_with(stage)
    data, fitted, _ = _fit_one(host, out)
    got = np.asarray(data.device_col(out.name).values, np.float64)
    want = np.stack([stage.transform_row(int(v)) for v in ms])
    np.testing.assert_allclose(got[:, :2], want[:, :2], rtol=0, atol=1e-5)
    assert np.all(got[:, 2] == 0.0)


def test_transmogrify_end_to_end_mixed_types():
    host = fr.HostFrame.from_dict({
        "age": (ft.Real, [30.0, None, 45.0, 22.0]),
        "n_items": (ft.Integral, [1, 2, 2, None]),
        "vip": (ft.Binary, [True, False, None, True]),
        "city": (ft.City, ["sf", "la", "sf", None]),
        "bio": (ft.Text, ["loves jax", None, "tpu fan", "jax jax"]),
        "joined": (ft.Date, [3600_000, None, 7200_000, 10_800_000]),
    })
    feats = FeatureBuilder.from_frame(host)
    combined = transmogrify(list(feats.values()), top_k=5, min_support=1,
                            num_hash_features=16)
    data, fitted, ex = _fit_one(host, combined)
    vec = data.device_col(combined.name)
    meta = vec.metadata
    assert vec.values.shape[0] == 4
    assert vec.values.shape[1] == meta.size
    # provenance covers every raw feature
    parents = {p for c in meta.columns for p in c.parent_feature}
    assert parents == {"age", "n_items", "vip", "city", "bio", "joined"}
    # indices are global and consecutive
    assert [c.index for c in meta.columns] == list(range(meta.size))
    # scoring a fresh frame works and matches shape
    scored = ex.transform(PipelineData.from_host(host), fitted)
    assert np.asarray(scored.device_col(combined.name).values).shape == \
        np.asarray(vec.values).shape


def test_transmogrify_label_aware_bucketization():
    """Parity: Transmogrifier.scala:99-104 + RichNumericFeature.scala:315-345
    — with a label, Real/Integral scalars gain per-feature decision-tree
    bucket blocks alongside the mean-fill block; features where the tree
    finds no informative split add no columns; RealNN is exempt."""
    n = 80
    rng = np.random.default_rng(0)
    x = rng.normal(size=n)
    host = fr.HostFrame.from_dict({
        "x": (ft.Real, list(x)),
        "cnt": (ft.Integral, [int(v * 3) for v in x]),
        "const": (ft.Real, [1.5] * n),
        "xnn": (ft.RealNN, list(np.abs(x) + 1.0)),
        "label": (ft.RealNN, list((x > 0.3).astype(float))),
    })
    feats = FeatureBuilder.from_frame(host, response="label")
    label = feats.pop("label")

    def bucket_cols(meta):
        return [c for c in meta.columns
                if c.indicator_value and "Inf" in str(c.indicator_value)]

    plain = transmogrify(list(feats.values()))
    data, _, _ = _fit_one(host, plain)
    meta_plain = data.device_col(plain.name).metadata
    assert bucket_cols(meta_plain) == []

    smart = transmogrify(list(feats.values()), label=label)
    data, fitted, ex = _fit_one(host, smart)
    vec = data.device_col(smart.name)
    meta = vec.metadata
    bcols = bucket_cols(meta)
    bucketized_parents = {p for c in bcols for p in c.parent_feature}
    # informative features got buckets; constant and RealNN did not
    assert "x" in bucketized_parents
    assert "cnt" in bucketized_parents
    assert "const" not in bucketized_parents
    assert "xnn" not in bucketized_parents
    # the mean-fill block survives alongside (x appears as a plain value col)
    plain_x = [c for c in meta.columns
               if "x" in c.parent_feature and not c.indicator_value]
    assert plain_x
    assert vec.values.shape[1] == meta.size
    # scoring a fresh frame reproduces the fitted width
    scored = ex.transform(PipelineData.from_host(host), fitted)
    assert np.asarray(scored.device_col(smart.name).values).shape == \
        np.asarray(vec.values).shape


def test_transmogrify_label_replaces_numeric_map_vectorizer():
    """Parity: RichMapFeature.scala:607-625 — with a label a numeric map is
    vectorized ONLY through the per-key tree bucketizer (the mean-fill map
    block is replaced, not combined)."""
    n = 80
    rng = np.random.default_rng(1)
    x = rng.normal(size=n)
    host = fr.HostFrame.from_dict({
        "m": (ft.RealMap, [{"k": float(v), "j": 2.0} for v in x]),
        "label": (ft.RealNN, list((x > 0.0).astype(float))),
    })
    feats = FeatureBuilder.from_frame(host, response="label")
    label = feats.pop("label")

    smart = transmogrify(list(feats.values()), label=label)
    data, _, _ = _fit_one(host, smart)
    meta = data.device_col(smart.name).metadata
    k_buckets = [c for c in meta.columns if c.grouping == "k"
                 and c.indicator_value and "Inf" in str(c.indicator_value)]
    assert k_buckets  # informative key bucketized
    # no plain mean-fill value column survives for the map
    plain_vals = [c for c in meta.columns
                  if "m" in c.parent_feature and not c.indicator_value]
    assert plain_vals == []
    # constant key "j" contributes only its null indicator
    j_cols = [c for c in meta.columns if c.grouping == "j"]
    assert all(c.indicator_value == NULL_INDICATOR for c in j_cols)
