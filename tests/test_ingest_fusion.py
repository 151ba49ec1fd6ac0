"""Round 14: device-resident feature engineering — multi-layer fused FE
programs, the ``TRANSMOGRIFAI_FE_FUSED=0`` byte-for-byte restore, the
``ingest.fuse`` OOM rung, double-buffered streaming ingest, the
fingerprint-keyed device-frame cache, the two new Pallas kernels
(quantile binning, hashing segment accumulate) with interpret-vs-XLA
bitwise parity, and the generate_frame schema-resolution hoist."""

import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from transmogrifai_tpu import frame as fr  # noqa: E402
from transmogrifai_tpu.features.builder import FeatureBuilder  # noqa: E402
from transmogrifai_tpu.pipeline_data import PipelineData  # noqa: E402
from transmogrifai_tpu.types import feature_types as ft  # noqa: E402
from transmogrifai_tpu.utils.profiling import ingest_counters  # noqa: E402
from transmogrifai_tpu.workflow import Workflow  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_counters():
    ingest_counters.reset()
    yield
    ingest_counters.reset()


@pytest.fixture()
def fe_fused(monkeypatch):
    monkeypatch.setenv("TRANSMOGRIFAI_FE_FUSED", "1")
    yield


def _rich_frame(n=300, seed=0):
    rng = np.random.default_rng(seed)
    date_base = 1_600_000_000_000
    return fr.HostFrame.from_dict({
        "r1": (ft.Real, [None if i % 11 == 0 else float(v)
                         for i, v in enumerate(rng.normal(size=n))]),
        "r2": (ft.Real, rng.normal(size=n)),
        "ints": (ft.Integral, rng.integers(0, 9, n)),
        "flag": (ft.Binary, (rng.uniform(size=n) < 0.5).tolist()),
        "when": (ft.Date, (date_base + rng.integers(0, 10**9, n)).tolist()),
        "cat": (ft.PickList, rng.choice(["a", "b", "c", "d"], n)),
        "txt": (ft.Text, [None if i % 7 == 0 else f"tok{int(v)}"
                          for i, v in enumerate(rng.integers(0, 50, n))]),
        "label": (ft.RealNN, rng.integers(0, 2, n).astype(float)),
    })


def _rich_model(frame):
    """A workflow covering every fusable device stage family: filled
    numeric vectorizers (Real/Integral/Binary), date unit-circle, one-hot
    pivot, fixed + label-tree + percentile bucketization, device murmur
    hashing, and the vector combiner."""
    from transmogrifai_tpu.ops.combiner import VectorsCombiner
    from transmogrifai_tpu.ops.vectorizers.bucketizers import (
        DecisionTreeNumericBucketizer, NumericBucketizer,
        PercentileCalibrator,
    )
    from transmogrifai_tpu.ops.vectorizers.dates import (
        DateToUnitCircleVectorizer,
    )
    from transmogrifai_tpu.ops.vectorizers.hashing import (
        DeviceTextHashingVectorizer,
    )
    from transmogrifai_tpu.ops.vectorizers.numeric import (
        BinaryVectorizer, IntegralVectorizer, RealVectorizer,
    )
    from transmogrifai_tpu.ops.vectorizers.onehot import OneHotVectorizer
    feats = FeatureBuilder.from_frame(frame, response="label")
    lab = feats.pop("label")
    blocks = [
        feats["r1"].transform_with(RealVectorizer(), feats["r2"]),
        feats["ints"].transform_with(IntegralVectorizer()),
        feats["flag"].transform_with(BinaryVectorizer()),
        feats["when"].transform_with(DateToUnitCircleVectorizer()),
        feats["cat"].transform_with(OneHotVectorizer(top_k=3)),
        feats["r2"].transform_with(NumericBucketizer(
            splits=(float("-inf"), -0.5, 0.5, float("inf")),
            track_invalid=True)),
        lab.transform_with(DecisionTreeNumericBucketizer(), feats["r1"]),
        feats["r2"].transform_with(PercentileCalibrator(
            expected_num_buckets=10)).transform_with(
                NumericBucketizer(splits=(0.0, 50.0, 99.0))),
        feats["txt"].transform_with(
            DeviceTextHashingVectorizer(num_features=16)),
    ]
    vec = blocks[0].transform_with(VectorsCombiner(), *blocks[1:])
    model = (Workflow().set_input_frame(frame)
             .set_result_features(vec).train())
    return model, vec.name


def _all_columns(model, frame):
    out = model.score(frame, keep_intermediate_features=True)
    return {n: out[n] for n in out.names()}


@pytest.fixture(scope="module")
def rich():
    """ONE trained rich-DAG model shared by the read-only tests (training
    it per test would dominate the suite's wall). Tests only transform
    through it — env gates flip per test, state lives in the counters."""
    os.environ.pop("TRANSMOGRIFAI_FE_FUSED", None)
    frame = _rich_frame()
    model, vec_name = _rich_model(frame)
    return frame, model, vec_name


# -- fused-vs-unfused parity --------------------------------------------------

def test_fused_parity_across_every_fusable_stage_type(fe_fused, monkeypatch,
                                                      rich):
    frame, model, vec_name = rich
    ingest_counters.reset()
    cols_on = _all_columns(model, frame)
    assert ingest_counters.fe_fused_programs > 0
    assert ingest_counters.fe_fused_stages >= 10
    monkeypatch.setenv("TRANSMOGRIFAI_FE_FUSED", "0")
    ingest_counters.reset()
    cols_off = _all_columns(model, frame)
    assert ingest_counters.fe_fused_programs == 0
    assert set(cols_on) == set(cols_off)
    for name, col in cols_on.items():
        a, b = col.values, cols_off[name].values
        if a.dtype == object:
            assert all(x == y or (x is None and y is None)
                       for x, y in zip(a, b)), name
        else:
            # BITWISE: fusion must not change a single ulp
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_fused_off_is_the_per_layer_path_bitwise(fe_fused, monkeypatch,
                                                 rich):
    from transmogrifai_tpu.dag import DagExecutor
    frame, model, vec_name = rich
    monkeypatch.setenv("TRANSMOGRIFAI_FE_FUSED", "0")
    ingest_counters.reset()
    got = np.asarray(model.transform(frame).host_col(vec_name).values)
    assert ingest_counters.fe_fused_programs == 0
    # the explicit pre-fusion execution: per-layer apply, fresh executor
    data = model._ingest(frame)
    ex = DagExecutor()
    for layer in model.dag:
        data = ex.apply_layer(data, layer)
    ref = np.asarray(data.host_col(vec_name).values)
    assert np.array_equal(got, ref)


def test_fuse_dag_program_chains_levels(fe_fused):
    """Direct unit: a two-level device chain in ONE program — the later
    level reads the earlier level's output from the traced environment."""
    from transmogrifai_tpu.dag import fuse_dag_program
    from transmogrifai_tpu.ops.vectorizers.bucketizers import (
        NumericBucketizer, PercentileCalibrator,
    )
    frame = fr.HostFrame.from_dict(
        {"x": (ft.Real, np.linspace(-2, 2, 64))})
    feats = FeatureBuilder.from_frame(frame)
    cal = PercentileCalibrator(expected_num_buckets=5)
    scaled = feats["x"].transform_with(cal)
    bucket = scaled.transform_with(NumericBucketizer(
        splits=(0.0, 50.0, 99.0)))
    data = PipelineData.from_host(frame)
    cal_model = cal.fit(data)
    buck = bucket.origin_stage
    prog = fuse_dag_program([[cal_model], [buck]])
    params = {cal_model.uid: cal_model.device_params(),
              buck.uid: buck.device_params()}
    outs = prog(params, {}, {"x": data.device_col("x")})
    assert set(outs) == {scaled.name, bucket.name}
    # equals the sequential per-stage execution
    mid = cal_model.output_column(data)
    data2 = data.with_device_cols({scaled.name: mid})
    ref = buck.output_column(data2)
    assert np.array_equal(np.asarray(outs[bucket.name].values),
                          np.asarray(ref.values))


def test_fused_oom_takes_stagewise_rung_with_parity(fe_fused, rich):
    """An injected OOM inside the fused segment dispatch degrades to the
    stagewise rung (site ``ingest.fuse``) and the run completes with
    results bitwise-equal to the clean path."""
    from transmogrifai_tpu.utils import resources
    from transmogrifai_tpu.utils.faults import fault_plan
    frame, model, vec_name = rich
    clean = np.asarray(model.transform(frame).host_col(vec_name).values)
    ingest_counters.reset()
    resources.resource_counters.reset()
    with fault_plan("oom@ingest.fuse#0"), pytest.warns(RuntimeWarning):
        degraded = np.asarray(
            model.transform(frame).host_col(vec_name).values)
    assert np.array_equal(clean, degraded)
    assert ingest_counters.fe_host_fallbacks >= 1
    assert ingest_counters.fe_host_rows > 0
    by_site = resources.resource_counters.to_json()["degradationsBySite"]
    assert by_site.get("ingest.fuse", 0) >= 1


def test_fused_oom_with_ladder_off_raises(fe_fused, monkeypatch, rich):
    from transmogrifai_tpu.utils.faults import XlaRuntimeError, fault_plan
    frame, model, vec_name = rich
    monkeypatch.setenv("TRANSMOGRIFAI_RESOURCE_LADDER", "0")
    with fault_plan("oom@ingest.fuse#0"), pytest.raises(XlaRuntimeError):
        model.transform(frame).host_col(vec_name)


# -- pallas kernels -----------------------------------------------------------

def test_quantile_bin_kernel_bitwise_parity():
    from transmogrifai_tpu.ops.quantile_bin_pallas import (
        bucketize_block, bucketize_block_xla,
    )
    rng = np.random.default_rng(1)
    for n in (5, 1000, 2049):
        for splits in ([-np.inf, 0.0, 1.5, np.inf],
                       [-1.0, 0.5], [-np.inf, np.inf],
                       [-np.inf, -1.0, -0.25, 0.0, 0.8, np.inf]):
            for ti in (False, True):
                for tn in (False, True):
                    v = jnp.asarray(rng.normal(size=n), jnp.float32)
                    m = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
                    sp = np.asarray(splits, np.float64)
                    a = np.asarray(bucketize_block_xla(v, m, sp, ti, tn))
                    b = np.asarray(bucketize_block(
                        v, m, sp, ti, tn, engine="pallas", interpret=True))
                    assert np.array_equal(a, b), (n, splits, ti, tn)


def test_quantile_bin_engine_dispatch(monkeypatch):
    from transmogrifai_tpu.ops import quantile_bin_pallas as qb
    monkeypatch.setenv("TRANSMOGRIFAI_BUCKET_ENGINE", "xla")
    assert qb.bucket_engine() == "xla"
    monkeypatch.setenv("TRANSMOGRIFAI_BUCKET_ENGINE", "pallas")
    assert qb.bucket_engine() == "pallas"
    monkeypatch.setenv("TRANSMOGRIFAI_BUCKET_ENGINE", "auto")
    expected = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert qb.bucket_engine() == expected
    monkeypatch.setenv("TRANSMOGRIFAI_BUCKET_ENGINE", "nope")
    with pytest.raises(ValueError):
        qb.bucket_engine()


def test_bucketizer_stage_agrees_across_engines(monkeypatch):
    """The fitted bucketizer stage produces identical blocks whichever
    engine ``_bucketize_block`` dispatches to."""
    from transmogrifai_tpu.ops.vectorizers.bucketizers import (
        NumericBucketizer,
    )
    frame = fr.HostFrame.from_dict(
        {"x": (ft.Real, [None, -3.0, -0.2, 0.0, 0.4, 2.5, 9.9])})
    feats = FeatureBuilder.from_frame(frame)
    stage = NumericBucketizer(splits=(float("-inf"), 0.0, 1.0, float("inf")),
                              track_invalid=True)
    stage.set_input(feats["x"])
    data = PipelineData.from_host(frame)
    monkeypatch.setenv("TRANSMOGRIFAI_BUCKET_ENGINE", "xla")
    a = np.asarray(stage.output_column(data).values)
    monkeypatch.setenv("TRANSMOGRIFAI_BUCKET_ENGINE", "pallas")
    b = np.asarray(stage.output_column(data).values)
    assert np.array_equal(a, b)


def test_segment_onehot_kernel_bitwise_parity():
    from transmogrifai_tpu.ops.hashing_pallas import (
        segment_onehot, segment_onehot_xla,
    )
    rng = np.random.default_rng(2)
    for n, T, B in ((3, 1, 8), (777, 4, 64), (1025, 2, 512)):
        ids = jnp.asarray(rng.integers(-1, B, size=(n, T)), jnp.int32)
        a = np.asarray(segment_onehot_xla(ids, B))
        b = np.asarray(segment_onehot(ids, B, engine="pallas",
                                      interpret=True))
        assert np.array_equal(a, b), (n, T, B)
        # every non-negative token lands in exactly one bin
        expect = (np.asarray(ids) >= 0).sum(axis=1)
        assert np.array_equal(a.sum(axis=1), expect.astype(np.float32))


def test_murmur3_reference_vectors():
    """Pin the hash to murmur3 x86_32 (the Spark/reference HashingTF
    family): published test vectors, so the trace-time vocab tables and
    the row path can never drift apart silently."""
    from transmogrifai_tpu.ops.hashing_pallas import (
        murmur3_bytes, murmur3_str,
    )
    assert murmur3_str("") == 0
    assert murmur3_bytes(b"", 1) == 0x514E28B7
    assert murmur3_str("hello") == 0x248BFA47
    assert murmur3_str("hello, world") == 0x149BBB7F
    assert murmur3_bytes(b"\xff\xff\xff\xff") == 0x76293B50


def test_device_hashing_vectorizer_row_vs_columnar_parity():
    from transmogrifai_tpu.ops.vectorizers.hashing import (
        DeviceTextHashingVectorizer,
    )
    rng = np.random.default_rng(4)
    vals = rng.choice(["aa", "bb", "cc", None], 150).tolist()
    vals2 = rng.choice(["x", "yy", None], 150).tolist()
    frame = fr.HostFrame.from_dict({"t": (ft.Text, vals),
                                    "u": (ft.Text, vals2)})
    feats = FeatureBuilder.from_frame(frame)
    st = DeviceTextHashingVectorizer(num_features=16)
    st.set_input(feats["t"], feats["u"])
    data = PipelineData.from_host(frame)
    col = st.output_column(data)
    dev = np.asarray(col.values)
    assert dev.shape[1] == 2 * 16 + 2
    assert col.metadata.size == dev.shape[1]
    for i in range(len(vals)):
        assert np.array_equal(st.transform_row(vals[i], vals2[i]), dev[i]), i


def test_device_hashing_vectorizer_serializes(tmp_path):
    from transmogrifai_tpu.ops.vectorizers.hashing import (
        DeviceTextHashingVectorizer,
    )
    frame = fr.HostFrame.from_dict(
        {"t": (ft.Text, ["a", "b", None, "a"] * 10)})
    feats = FeatureBuilder.from_frame(frame)
    vec = feats["t"].transform_with(DeviceTextHashingVectorizer(
        num_features=8))
    model = (Workflow().set_input_frame(frame)
             .set_result_features(vec).train())
    ref = np.asarray(model.transform(frame).host_col(vec.name).values)
    path = str(tmp_path / "m")
    model.save(path)
    loaded = Workflow.load_model(path)
    got = np.asarray(loaded.transform(frame).host_col(vec.name).values)
    assert np.array_equal(ref, got)


# -- chunk prefetcher ---------------------------------------------------------

def test_prefetcher_preserves_order_and_meters():
    from transmogrifai_tpu.ingest_fusion import ChunkPrefetcher
    items = list(range(8))
    pf = ChunkPrefetcher(items, lambda i: i * 10, depth=2)
    assert list(pf) == [i * 10 for i in items]
    assert pf.chunks == 8
    assert ingest_counters.chunks_prefetched == 8


def test_prefetcher_decodes_ahead_of_consumer():
    """With a slow consumer the producer runs ahead (bounded by depth):
    by the time the consumer finishes item 0, later items are decoded."""
    from transmogrifai_tpu.ingest_fusion import ChunkPrefetcher
    decoded = []
    pf = ChunkPrefetcher(range(5), lambda i: decoded.append(i) or i,
                         depth=2)
    it = iter(pf)
    first = next(it)
    deadline = time.monotonic() + 5.0
    while len(decoded) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)  # producer keeps decoding while we "compute"
    assert first == 0
    assert len(decoded) >= 3
    assert list(it) == [1, 2, 3, 4]


def test_prefetcher_error_raises_at_consumer():
    from transmogrifai_tpu.ingest_fusion import ChunkPrefetcher

    def fn(i):
        if i == 2:
            raise ValueError("poisoned chunk")
        return i

    pf = ChunkPrefetcher(range(5), fn, depth=2)
    got = []
    with pytest.raises(ValueError, match="poisoned"):
        for v in pf:
            got.append(v)
    assert got == [0, 1]


def test_prefetcher_serial_when_depth_zero():
    from transmogrifai_tpu.ingest_fusion import ChunkPrefetcher
    consumer = threading.current_thread().name
    seen = []
    pf = ChunkPrefetcher(range(3),
                         lambda i: seen.append(
                             threading.current_thread().name) or i,
                         depth=0)
    assert list(pf) == [0, 1, 2]
    assert set(seen) == {consumer}
    # serial decode is NOT counted as prefetched (nothing overlapped)
    assert ingest_counters.chunks_prefetched == 0


def test_prefetcher_waits_are_watchdog_armed_while_decoding():
    """The stall guard arms only while the producer is INSIDE the decode
    fn — a wedged decode autopsies, while a healthy idle upstream (a
    file stream between arrivals) waits unguarded (no false stalls)."""
    from transmogrifai_tpu.ingest_fusion import ChunkPrefetcher
    from transmogrifai_tpu.utils import devicewatch as dw
    dw.watchdog.configure(enabled=True)
    before = dw.watchdog.guards
    list(ChunkPrefetcher(range(4), lambda i: time.sleep(0.05) or i,
                         depth=1))
    assert dw.watchdog.guards > before

    def idle_items():
        yield 0
        time.sleep(0.8)  # idle upstream: longer than the unguarded poll
        yield 1

    guards_at = dw.watchdog.guards
    pf = ChunkPrefetcher(idle_items(), lambda i: i, depth=1)
    assert list(pf) == [0, 1]
    # the idle gap waited unguarded: at most the decode-catch guards of
    # two instant decodes, never one guard per 0.5s poll slice
    assert dw.watchdog.guards - guards_at <= 2


def test_prefetcher_fault_site_fires():
    from transmogrifai_tpu.ingest_fusion import ChunkPrefetcher
    from transmogrifai_tpu.utils.faults import fault_plan

    with fault_plan("io@ingest.prefetch#1") as plan:
        pf = ChunkPrefetcher(range(3), lambda i: i, depth=1)
        with pytest.raises(OSError):
            list(pf)
        assert plan.fired


def test_stream_score_prefetch_matches_serial(monkeypatch, rich):
    from transmogrifai_tpu.readers.streaming import (
        StreamingReader, stream_score,
    )
    frame, model, vec_name = rich

    class R(StreamingReader):
        schema = None

        def stream(self):
            rng = np.random.default_rng(9)
            for _ in range(3):
                yield [{"r1": float(rng.normal()),
                        "r2": float(rng.normal()),
                        "ints": int(rng.integers(0, 9)),
                        "flag": bool(rng.integers(0, 2)),
                        "when": 1_600_000_000_000 + int(rng.integers(0, 10**9)),
                        "cat": "a", "txt": "tok1"} for _ in range(10)]

    serial = [np.asarray(f[vec_name].values)
              for f in stream_score(model, R(), prefetch=0)]
    overlapped = [np.asarray(f[vec_name].values)
                  for f in stream_score(model, R(), prefetch=2)]
    assert len(serial) == len(overlapped) == 3
    for a, b in zip(serial, overlapped):
        assert np.array_equal(a, b)


def test_stream_score_checkpointed_stream_stays_serial(tmp_path, rich):
    """A durable (checkpointed) stream must NOT prefetch: the commit
    fires when the source generator advances, so decode-ahead would mark
    a batch done before it was consumed."""
    from transmogrifai_tpu.readers.streaming import (
        FileStreamingReader, stream_score,
    )
    frame, model, vec_name = rich
    d = tmp_path / "stream"
    d.mkdir()
    for i in range(2):
        with open(d / f"b{i}.csv", "w") as fh:
            fh.write("r1,r2,ints,flag,when,cat,txt\n")
            fh.write(f"0.1,0.2,3,true,1600000000000,a,tok{i}\n")
    reader = FileStreamingReader(
        str(d), pattern="*.csv", max_batches=2, timeout_s=1.0,
        checkpoint=str(tmp_path / "ckpt.json"))
    ingest_counters.reset()
    out = list(stream_score(model, reader, prefetch=4))
    assert len(out) == 2
    # serial decode path: nothing counted as prefetched
    assert ingest_counters.chunks_prefetched == 0


# -- device-frame cache -------------------------------------------------------

def test_frame_cache_skips_retransfer_and_keys_on_content(rich):
    frame, model, vec_name = rich
    ingest_counters.reset()
    a = np.asarray(model.transform(frame).host_col(vec_name).values)
    first_reuses = ingest_counters.frame_cache_reuses
    b = np.asarray(model.transform(frame).host_col(vec_name).values)
    assert ingest_counters.frame_cache_reuses > first_reuses
    assert np.array_equal(a, b)
    # content change -> different fingerprint -> no stale reuse
    cols = {n: (frame[n].ftype,
                [frame[n].python_value(i) for i in range(frame.n_rows)])
            for n in frame.names()}
    cols["r2"] = (ft.Real, [v + 1.0 if v is not None else None
                            for v in cols["r2"][1]])
    frame2 = fr.HostFrame.from_dict(cols)
    reuses = ingest_counters.frame_cache_reuses
    c = np.asarray(model.transform(frame2).host_col(vec_name).values)
    assert ingest_counters.frame_cache_reuses == reuses
    assert not np.array_equal(a, c)


def test_frame_cache_disabled_by_env(monkeypatch, rich):
    monkeypatch.setenv("TRANSMOGRIFAI_FRAME_CACHE", "0")
    frame, model, vec_name = rich
    ingest_counters.reset()
    model.transform(frame)
    model.transform(frame)
    assert ingest_counters.frame_cache_reuses == 0
    assert ingest_counters.frame_cache_stores == 0


def test_frame_cache_drops_under_pressure(monkeypatch):
    from transmogrifai_tpu.ingest_fusion import DeviceFrameCache
    from transmogrifai_tpu.utils import resources
    frame = fr.HostFrame.from_dict({"x": (ft.Real, [1.0, 2.0, 3.0])})
    cache = DeviceFrameCache(capacity=2)
    data = PipelineData.from_host(frame)
    data.device_col("x")  # populate a device column
    assert cache.adopt(frame, data) is data
    assert cache.entries() == 1
    monkeypatch.setattr(
        resources, "hbm_pressure_state",
        lambda: {"hbmBytesInUse": 99, "hbmBytesLimit": 100,
                 "hbmPressureFrac": 0.85, "pressured": True})
    ingest_counters.reset()
    fresh = PipelineData.from_host(frame)
    assert cache.adopt(frame, fresh) is fresh  # no reuse under pressure
    assert cache.entries() == 0
    assert ingest_counters.frame_cache_drops == 1


def test_frame_cache_lru_bound():
    from transmogrifai_tpu.ingest_fusion import DeviceFrameCache
    cache = DeviceFrameCache(capacity=1)
    for v in (1.0, 2.0, 3.0):
        frame = fr.HostFrame.from_dict({"x": (ft.Real, [v])})
        cache.adopt(frame, PipelineData.from_host(frame))
    assert cache.entries() == 1


def test_train_then_train_reuses_device_frame():
    frame = _rich_frame(seed=13)
    rng_feats = FeatureBuilder.from_frame(frame, response="label")
    lab = rng_feats.pop("label")
    from transmogrifai_tpu.ops.vectorizers.numeric import RealVectorizer
    vec = rng_feats["r1"].transform_with(RealVectorizer(), rng_feats["r2"])
    wf = Workflow().set_input_frame(frame).set_result_features(vec)
    ingest_counters.reset()
    wf.train()
    assert ingest_counters.frame_cache_stores == 1
    wf.train()
    assert ingest_counters.frame_cache_reuses >= 1


# -- fingerprints + builder hoist ---------------------------------------------

def test_frame_fingerprint_sensitivity():
    f1 = fr.HostFrame.from_dict({"x": (ft.Real, [1.0, 2.0]),
                                 "t": (ft.Text, ["a", None])})
    f2 = fr.HostFrame.from_dict({"x": (ft.Real, [1.0, 2.0]),
                                 "t": (ft.Text, ["a", None])})
    f3 = fr.HostFrame.from_dict({"x": (ft.Real, [1.0, 2.5]),
                                 "t": (ft.Text, ["a", None])})
    f4 = fr.HostFrame.from_dict({"x": (ft.Real, [1.0, 2.0]),
                                 "t": (ft.Text, ["b", None])})
    assert fr.frame_fingerprint(f1) == fr.frame_fingerprint(f2)
    assert fr.frame_fingerprint(f1) != fr.frame_fingerprint(f3)
    assert fr.frame_fingerprint(f1) != fr.frame_fingerprint(f4)


@pytest.mark.parametrize("other", [
    ["ab", "c", None, ""],          # a character moved across a row boundary
    ["a", "bc", "", None],          # the null and the empty string swapped
    ["a", "bc", None, "", None],    # one more null
    ["a", "bC", None, ""],          # another character
    ["a", "b\u00e7", None, ""],     # not ASCII
    ["a", 7.5, None, ""],           # an object that is no string
])
def test_frame_fingerprint_tells_string_columns_apart(other):
    """A column of strings hashes its text end to end with each value's
    length and null flag: what tells the values apart row by row still
    tells the frames apart, and objects that are not strings take the
    per-row path."""
    def frame(values):
        return fr.HostFrame({"t": fr.HostColumn(
            ft.Text, np.array(values, dtype=object))})
    base = ["a", "bc", None, ""]
    assert fr.frame_fingerprint(frame(base)) == fr.frame_fingerprint(
        frame(list(base)))
    assert fr.frame_fingerprint(frame(base)) != fr.frame_fingerprint(
        frame(other))
    assert fr.frame_fingerprint(frame(other)) == fr.frame_fingerprint(
        frame(list(other)))


def test_generate_frame_resolves_schema_once_per_reader(monkeypatch):
    """The satellite fix: HostColumn.builder (the kind dispatch) runs
    once per (reader, feature), however many chunks stream through."""
    from transmogrifai_tpu.readers.base import CustomReader
    from transmogrifai_tpu.stages.base import FeatureGeneratorStage
    calls = []
    orig = fr.HostColumn.builder

    def counting(ftype):
        calls.append(ftype.__name__)
        return orig(ftype)

    monkeypatch.setattr(fr.HostColumn, "builder", staticmethod(counting))
    records = [{"x": float(i), "t": f"v{i}"} for i in range(10)]
    reader = CustomReader(records=records)
    reader.chunk_rows = 3  # 4 chunks
    x = FeatureGeneratorStage("x", "Real").get_output()
    t = FeatureGeneratorStage("t", "Text").get_output()
    frame = reader.generate_frame([x, t])
    assert frame.n_rows == 10
    assert sorted(calls) == ["Real", "Text"]
    assert float(frame["x"].values[7]) == 7.0


# -- mesh: pre-partitioned operands ------------------------------------------

def test_shard_rows_skips_already_placed():
    from transmogrifai_tpu.parallel import mesh as pmesh
    ctx = pmesh.make_mesh(devices=jax.devices()[:1])
    with pmesh.use_mesh(ctx):
        arr = pmesh.shard_rows(jnp.arange(8, dtype=jnp.float32))
        before = ingest_counters.presharded_skips
        again = pmesh.shard_rows(arr)
        assert ingest_counters.presharded_skips == before + 1
        assert again is arr


def test_sweep_operand_handoff_span(fe_fused):
    """The ingest->sweep handoff is observable: the sweep.operands span
    records that the feature matrix arrived device-resident."""
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.utils.tracing import recorder
    frame = _rich_frame(seed=14)
    feats = FeatureBuilder.from_frame(frame, response="label")
    lab = feats.pop("label")
    vec = transmogrify([feats["r1"], feats["r2"]])
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1,
        models_and_parameters=[(OpLogisticRegression(max_iter=5),
                                [{"reg_param": 0.1}])])
    pred = lab.transform_with(sel, vec)
    recorder.reset()
    (Workflow().set_input_frame(frame)
     .set_result_features(pred).train())
    spans = [s for s in recorder.spans if s.name == "sweep.operands"]
    assert spans and spans[0].attrs["presharded"] is True
