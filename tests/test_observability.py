"""Observability spine: hierarchical span tracing, device-time
attribution (phases + stages), chrome-trace export via the runner,
Prometheus exposition served end-to-end from a live ScoringServer, the
metric-name lint, and the frozen-wall / rolling-throughput fixes."""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".py", "").replace("/", "_"), os.path.join(REPO, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- span recorder ------------------------------------------------------------

def test_span_tree_lineage_and_attrs():
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    rec = SpanRecorder()
    with rec.span("outer", kind="a"):
        with rec.span("inner", stage_uid="u1"):
            pass
        with rec.span("inner2"):
            pass
    spans = {s.name: s for s in rec.spans}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner2"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].attrs == {"kind": "a"}
    assert spans["inner"].t0 >= spans["outer"].t0
    assert spans["inner"].t1 <= spans["outer"].t1


def test_span_threads_are_isolated():
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    rec = SpanRecorder()
    started = threading.Event()
    release = threading.Event()

    def worker():
        with rec.span("worker_span"):
            started.set()
            release.wait(timeout=5)

    with rec.span("main_span"):
        t = threading.Thread(target=worker)
        t.start()
        started.wait(timeout=5)
        release.set()
        t.join(timeout=5)
    spans = {s.name: s for s in rec.spans}
    # the worker's span must NOT be parented under the main thread's span
    assert spans["worker_span"].parent_id is None
    assert spans["main_span"].parent_id is None
    assert spans["worker_span"].thread != spans["main_span"].thread


def test_span_disabled_and_bounded():
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    rec = SpanRecorder(max_spans=2)
    rec.enable(False)
    with rec.span("x"):
        pass
    assert rec.spans == []
    rec.enable(True)
    for i in range(4):
        with rec.span(f"s{i}"):
            pass
    assert len(rec.spans) == 2 and rec.dropped == 2
    # ring semantics: a long-lived recorder keeps its NEWEST activity
    assert [s.name for s in rec.spans] == ["s2", "s3"]


def test_span_add_retroactive_and_aggregate():
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    rec = SpanRecorder()
    rec.add("queue_wait", 100.0, 100.5, rows=8)
    rec.add("queue_wait", 101.0, 101.25, rows=4)
    agg = rec.aggregate()
    assert agg["queue_wait"]["count"] == 2
    assert agg["queue_wait"]["wallSeconds"] == pytest.approx(0.75)
    assert agg["queue_wait"]["maxWallSeconds"] == pytest.approx(0.5)


def _recorded_trace_dir(tmp_path):
    """A trace directory holding the small trace recorded on one TPU v5e
    chip (tests/fixtures/README.md): one run of ``jit_prog`` with ops
    staged under ``tree.L0/hist`` and ``tree.leaf`` and ops with no scope,
    and the host annotation ``anchor.mark`` 0.3277 ms before its first
    op."""
    import shutil
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "tests", "fixtures",
                             "tpu_v5e_scoped.xplane.pb"), d)
    return str(tmp_path)


def test_trace_reader_names_module_op_and_scope(tmp_path, monkeypatch):
    """The program's trace reader on a recorded TPU trace: every leaf op
    comes back with its module, op kind and named-scope path (read from
    the event METADATA's ``tf_op``, which ``ProfileData`` does not yield),
    placed on the host's clock by the anchor annotation; the (module,
    scope) table sums them and span windows take what ran inside."""
    from transmogrifai_tpu.utils import profiling
    from transmogrifai_tpu.utils.profiling import (
        AppMetrics, scope_of, trace_device_events,
    )
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    d = _recorded_trace_dir(tmp_path)
    monkeypatch.setattr(profiling, "TRACE_ANCHOR", "anchor.mark")
    events = trace_device_events(d, anchor_epoch_s=1000.0)
    assert len(events) == 10
    assert {e.module for e in events} == {"jit_prog"}
    by_op = {e.op: e for e in events}
    assert by_op["convolution_bitcast_fusion"].scope == \
        "tree.L0/hist/bcs,bcd->bsd"
    assert by_op["multiply_reduce_fusion"].scope == "tree.leaf"
    assert by_op["reduce-window"].scope == ""   # the compiler's own op
    # on the host's clock: the first op starts 0.3277 ms after the anchor
    assert min(e.start_s for e in events) == pytest.approx(
        1000.0 + 0.3277e-3, abs=1e-6)
    # without an anchor the events stay on the trace's own clock
    raw = trace_device_events(d)
    assert min(e.start_s for e in raw) == pytest.approx(0.041372, abs=1e-5)
    # an anchor that was asked for and is not in the trace is an error
    monkeypatch.setattr(profiling, "TRACE_ANCHOR", "no.such.mark")
    with pytest.raises(ValueError, match="no.such.mark"):
        trace_device_events(d, anchor_epoch_s=1000.0)

    m = AppMetrics()
    total = m.attribute_device_scopes(events)
    assert total == pytest.approx(sum(e.duration_s for e in events))
    assert total == pytest.approx(58.95e-6, rel=1e-3)   # the module's run
    hist = m.device_scopes[("jit_prog", "tree.L0/hist/bcs,bcd->bsd")]
    assert hist[0] == pytest.approx(6.790e-6, rel=1e-3) and hist[1] == 1
    assert m.device_scopes[("jit_prog", "")][1] == 8
    doc = m.to_json()["deviceScopes"]
    assert doc[0]["scope"] == "" and doc[0]["module"] == "jit_prog"
    assert {"module", "scope", "deviceSeconds", "opCount"} == set(doc[0])
    assert "tree.leaf" in m.pretty() and "(unscoped)" in m.pretty()

    # spans: only device_window spans take device seconds, by containment
    rec = SpanRecorder()
    t0 = min(e.start_s for e in events)
    rec.add("sweep.device", t0, t0 + 10e-6, family="rf",
            device_window=True)                              # hist + part
    rec.add("sweep.settle", t0 - 1.0, t0 + 1.0, device_window=True)
    rec.add("sweep.family", t0 - 1.0, t0 + 1.0)             # a dispatch
    rec.attribute_device_windows(events)
    spans = {s.name: s for s in rec.spans}
    assert spans["sweep.device"].device_s == pytest.approx(6.80e-6,
                                                           rel=1e-2)
    assert spans["sweep.settle"].device_s == pytest.approx(total)
    assert spans["sweep.family"].device_s == 0.0

    assert scope_of("jit(f)/vmap(vmap(jit(g)))/while/body/closed_call/"
                    "vmap(jit(grow_tree))/tree.L3/split/jit(cumsum)/"
                    "cumsum") == "tree.L3/split"
    assert scope_of("jit(f)/vmap(vmap(tree.predict))/vmap(jit(clip))/max"
                    ) == "tree.predict"
    assert scope_of("") == ""


def test_stage_table_takes_device_seconds_from_stage_scopes():
    """Inside a fused FE program a stage's ops are staged under the scope
    ``<operation>[<uid>]``: the stage table's device seconds come from the
    (module, scope) table by that uid, not from any span's window."""
    from transmogrifai_tpu.utils.profiling import profiler
    from transmogrifai_tpu.utils.tracing import span
    profiler.reset()
    with span("stage.transform", stage_uid="Vec_1", stage_cls="Vec"):
        pass
    scopes = profiler.metrics.device_scopes
    scopes[("jit_fe_fused", "vecReal[Vec_1]")] = [0.25, 3]
    scopes[("jit_fe_fused", "vecReal[Vec_1]/clip")] = [0.05, 1]
    scopes[("jit_train_score_stacked", "tree.L0/hist")] = [9.0, 1]
    m = profiler.finalize()
    assert m.stages["Vec (Vec_1)"]["deviceSeconds"] == pytest.approx(0.30)
    assert profiler.finalize().stages["Vec (Vec_1)"]["deviceSeconds"] == \
        pytest.approx(0.30)     # idempotent


def test_trace_reader_raises_on_missing_or_broken_trace(tmp_path):
    """A trace that should be there and is not, or cannot be decoded, is
    an error — never an empty timeline read as "the device did nothing"."""
    from transmogrifai_tpu.utils.profiling import trace_device_events
    with pytest.raises(FileNotFoundError):
        trace_device_events(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"\x0a\xff\xff\xff\xff\x0fxx")
    with pytest.raises((ValueError, IndexError)):
        trace_device_events(str(tmp_path))


def test_stage_table_does_not_double_count_nested_same_uid_spans():
    """The selector's sweep/refit spans nest inside its stage.fit span
    with the same stage_uid: the rollup must count the OUTERMOST wall
    once, while device seconds (carried by the device-window spans only:
    ``selector.sweep`` and ``selector.refit``, which are disjoint) sum
    across all of them."""
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    rec = SpanRecorder()
    with rec.span("stage.fit", stage_uid="sel", stage_cls="ModelSelector",
                  phase="fit"):
        time.sleep(0.02)
        with rec.span("selector.sweep", stage_uid="sel",
                      stage_cls="ModelSelector", phase="sweep", device_window=True):
            time.sleep(0.01)
        with rec.span("selector.refit", stage_uid="sel",
                      stage_cls="ModelSelector", phase="refit", device_window=True):
            time.sleep(0.01)
    from transmogrifai_tpu.utils.profiling import DeviceEvent
    by_name = {s.name: s for s in rec.spans}
    sweep, refit = by_name["selector.sweep"], by_name["selector.refit"]
    outer = by_name["stage.fit"]
    rec.attribute_device_windows([
        DeviceEvent(sweep.t0 + 0.001, 0.004, "jit_a", "fusion", "", 1),
        DeviceEvent(refit.t0 + 0.001, 0.002, "jit_b", "fusion", "", 2),
        DeviceEvent(outer.t0 + 0.001, 0.001, "jit_c", "fusion", "", 3)])
    assert outer.device_s == 0.0     # not a device window: owns nothing
    table = rec.stage_table()
    entry = table["ModelSelector (sel)"]
    assert entry["count"] == 1
    assert entry["wallSeconds"] == pytest.approx(outer.wall_s)
    assert entry["wallSeconds"] < outer.wall_s + sweep.wall_s
    assert entry["deviceSeconds"] == pytest.approx(0.006)


# -- device-time attribution units (satellite) --------------------------------

def test_attribute_device_time_midpoint_and_nesting():
    from transmogrifai_tpu.utils.profiling import AppMetrics
    m = AppMetrics()
    m.spans = [("FeatureEngineering", 0.0, 10.0),
               ("CrossValidation", 2.0, 6.0)]  # nested, later-started
    total = m.attribute_device_time([
        (2.5, 1.0),    # midpoint 3.0: inside both -> innermost (CV)
        (5.9, 0.4),    # midpoint 6.1: only FE contains it
        (9.0, 0.5),    # midpoint 9.25 -> FE
        (11.0, 1.0),   # midpoint 11.5 -> outside: unattributed
    ])
    assert total == pytest.approx(1.9)
    assert m.phases["CrossValidation"].device_s == pytest.approx(1.0)
    assert m.phases["FeatureEngineering"].device_s == pytest.approx(0.9)


def test_attribute_device_time_innermost_owner_tie():
    """Two spans starting at the same instant: ownership resolves to the
    LATER entry in span order (the ``>=`` innermost comparison) — pinned
    so a refactor can't silently flip attribution."""
    from transmogrifai_tpu.utils.profiling import AppMetrics
    m = AppMetrics()
    m.spans = [("ModelTraining", 1.0, 5.0), ("Scoring", 1.0, 5.0)]
    m.attribute_device_time([(2.0, 1.0)])
    assert m.phases["Scoring"].device_s == pytest.approx(1.0)
    assert "ModelTraining" not in m.phases


def test_profiler_phase_exclusive_wall_child_stack():
    """Nested phases must not double-count wall: the parent records its
    own elapsed MINUS the children's (exclusive wall)."""
    import jax

    from transmogrifai_tpu.utils.profiling import OpStep, profiler
    jax.local_devices()  # backend init must not land inside a phase window
    m = profiler.reset("excl")
    with profiler.phase(OpStep.FEATURE_ENGINEERING):
        time.sleep(0.02)
        with profiler.phase(OpStep.CROSS_VALIDATION):
            time.sleep(0.1)
    fe = m.phases["FeatureEngineering"].wall_s
    cv = m.phases["CrossValidation"].wall_s
    assert cv >= 0.1
    assert fe < cv  # parent's exclusive wall excludes the nested phase
    assert fe >= 0.02 * 0.5  # but keeps its own work
    # spans timeline records BOTH occurrences inclusively
    assert len(m.spans) == 2


def test_total_wall_freezes_at_finalize():
    from transmogrifai_tpu.utils.profiling import profiler
    m = profiler.reset("freeze")
    m2 = profiler.finalize()
    assert m2 is m and m.end_time is not None
    w = m.total_wall_s
    time.sleep(0.03)
    assert m.total_wall_s == w
    assert m.to_json()["totalWallSeconds"] == w


# -- stage table + chrome trace through the runner ----------------------------

N = 160


def _tiny_runner():
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.runner import WorkflowRunner
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(8)
    x1 = rng.normal(size=N)
    x2 = rng.normal(size=N)
    y = (rng.uniform(size=N)
         < 1 / (1 + np.exp(-(1.3 * x1 - x2)))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x1"], feats["x2"]])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=2, models_and_parameters=[
            (OpLogisticRegression(max_iter=10), [{}])])
    pred = feats["y"].transform_with(sel, features)
    wf = (Workflow().set_input_frame(frame)
          .set_result_features(pred, features))
    return WorkflowRunner(wf)


def test_runner_trace_out_emits_valid_chrome_trace(tmp_path):
    from transmogrifai_tpu.params import OpParams
    from transmogrifai_tpu.runner import RunTypes
    runner = _tiny_runner()
    out = str(tmp_path / "trace.json")
    res = runner.run(RunTypes.TRAIN, OpParams(), trace_out=out)
    assert res["status"] == "success"
    assert res["traceOut"] == out
    assert res["trace"]["hostSpans"] > 0
    doc = json.load(open(out))
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    # schema: every event names itself and carries a phase marker; every
    # complete event has microsecond ts + dur ("C" = the devicewatch HBM
    # counter track, present when the run sampled the census)
    for e in events:
        assert isinstance(e.get("name"), str) and e["name"]
        assert e.get("ph") in ("X", "M", "C")
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) or isinstance(e["ts"], int)
            assert e["dur"] >= 0
    names = {e["name"] for e in events}
    # host stage spans AND the coarse phase timeline are both present
    assert "stage.fit" in names
    assert "reader.generate_frame" in names
    assert any(n in names for n in ("FeatureEngineering", "ModelTraining"))
    # device slices appear iff a device plane existed (never on CPU CI);
    # when present they live in pid 2
    dev = [e for e in events
           if e.get("ph") == "X" and e.get("args", {}).get("kind")
           == "device"]
    assert len(dev) == res["trace"]["deviceSlices"]
    # the run summary carries the per-stage rollup with device columns
    stages = res["appMetrics"]["stages"]
    assert any("OpLogisticRegression" in k or "Vectorizer" in k
               or "(" in k for k in stages)
    for v in stages.values():
        assert {"wallSeconds", "deviceSeconds", "count"} <= set(v)


def test_sweep_and_ingest_spans_recorded():
    from transmogrifai_tpu.params import OpParams
    from transmogrifai_tpu.runner import RunTypes
    from transmogrifai_tpu.utils.tracing import recorder
    runner = _tiny_runner()
    runner.run(RunTypes.TRAIN, OpParams())
    names = {s.name for s in recorder.spans}
    assert {"workflow.ingest", "reader.generate_frame", "stage.fit",
            "selector.sweep", "sweep.dispatch", "sweep.family",
            "sweep.settle"} <= names


def test_one_sync_sweep_span_nesting():
    """Round 9 span topology: the dispatch/settle phases nest under
    ``selector.sweep`` with every ``sweep.family`` a child of
    ``sweep.dispatch`` (families overlap; the chrome trace shows one
    dispatch burst then one settle instead of serialized family blocks),
    and the stacked winner refit opens ``selector.refit_stacked`` under
    ``selector.refit``."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import (
        OpLinearSVC, OpLogisticRegression,
    )
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.profiling import profiler
    from transmogrifai_tpu.utils.tracing import recorder
    from transmogrifai_tpu.workflow import Workflow

    profiler.reset()
    rng = np.random.default_rng(3)
    x = rng.normal(size=N)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-1.5 * x))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x": (ft.Real, x.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x"]])
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=2, models_and_parameters=[
            (OpLogisticRegression(max_iter=10),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpLinearSVC(max_iter=10), [{"reg_param": 0.01}]),
        ])
    pred = feats["y"].transform_with(sel, features)
    (Workflow().set_input_frame(frame)
     .set_result_features(pred, features).train())

    spans = recorder.spans
    by_id = {s.span_id: s for s in spans}

    def ancestors(s):
        out, pid = [], s.parent_id
        while pid is not None:
            out.append(by_id[pid].name)
            pid = by_id[pid].parent_id
        return out

    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert {"sweep.dispatch", "sweep.settle", "sweep.family",
            "selector.refit_stacked"} <= set(by_name), sorted(by_name)
    for s in by_name["sweep.dispatch"] + by_name["sweep.settle"]:
        assert "selector.sweep" in ancestors(s), ancestors(s)
    fams = by_name["sweep.family"]
    assert len(fams) == 2
    for s in fams:
        assert by_id[s.parent_id].name == "sweep.dispatch"
    # the settle span accounts every dispatched family
    settle = by_name["sweep.settle"][0]
    assert settle.attrs["families"] == 2
    # both families' dispatch spans CLOSE before the settle opens —
    # the overlap the chrome trace renders
    assert max(s.t1 for s in fams) <= settle.t0
    for s in by_name["selector.refit_stacked"]:
        assert "selector.refit" in ancestors(s), ancestors(s)
        assert "selector.sweep" not in ancestors(s)


# -- serving /metrics end-to-end ----------------------------------------------

@pytest.fixture(scope="module")
def served_with_metrics():
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.serving import ScoringServer
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(4)
    x1 = rng.normal(size=N)
    x2 = rng.normal(size=N)
    y = (rng.uniform(size=N)
         < 1 / (1 + np.exp(-(1.2 * x1 + x2)))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x1"], feats["x2"]])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=3, models_and_parameters=[
            (OpLogisticRegression(max_iter=10), [{}])])
    pred = feats["y"].transform_with(sel, features)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred, features).train())
    rows = [{"x1": float(x1[i]), "x2": float(x2[i])} for i in range(64)]
    server = ScoringServer(model, metrics_port=0).start()
    futs = [server.submit(r) for r in rows]
    for f in futs:
        f.result(timeout=10)
    with pytest.raises(KeyError):
        server.submit({"x1": 1.0})  # strict admission: one invalid reject
    yield server
    server.stop()


def _get(server, path: str):
    port = server.metrics_http.port
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
        return resp.status, resp.headers.get("Content-Type"), \
            resp.read().decode()


def _parse_exposition(body: str) -> dict:
    """{series_with_labels: float} + {name: type} — a minimal but real
    parser: the endpoint's output must be machine-readable, not just
    greppable."""
    values: dict = {}
    types: dict = {}
    for ln in body.splitlines():
        if ln.startswith("# TYPE "):
            _, _, name, mtype = ln.split(" ", 3)
            types[name] = mtype
            continue
        if not ln or ln.startswith("#"):
            continue
        key, val = ln.rsplit(" ", 1)
        values[key] = float(val)
    return {"values": values, "types": types}


def test_metrics_endpoint_exposition(served_with_metrics):
    server = served_with_metrics
    status, ctype, body = _get(server, "/metrics")
    assert status == 200 and ctype.startswith("text/plain")
    parsed = _parse_exposition(body)
    v, t = parsed["values"], parsed["types"]
    # request series
    assert v["transmogrifai_serving_requests_admitted_total"] >= 64
    assert v["transmogrifai_serving_requests_completed_total"] >= 64
    assert v['transmogrifai_serving_rejected_total{reason="invalid"}'] >= 1
    # latency histogram: cumulative, ends at +Inf == count
    buckets = sorted(
        ((k, n) for k, n in v.items()
         if k.startswith("transmogrifai_serving_latency_seconds_bucket")),
        key=lambda kv: float("inf") if "+Inf" in kv[0]
        else float(kv[0].split('le="')[1].rstrip('"}')))
    counts = [n for _, n in buckets]
    assert counts == sorted(counts), "histogram buckets must be cumulative"
    assert counts[-1] == v["transmogrifai_serving_latency_seconds_count"]
    assert v["transmogrifai_serving_latency_seconds_count"] >= 64
    # queue + degradation + compile series
    assert "transmogrifai_serving_queue_depth" in v
    assert v["transmogrifai_serving_queue_capacity"] == 1024
    assert v["transmogrifai_serving_degraded"] == 0
    assert v["transmogrifai_serving_degraded_entries_total"] == 0
    assert any(k.startswith("transmogrifai_serving_compiles_total{bucket=")
               for k in v)
    assert any(k.startswith(
        "transmogrifai_serving_dispatches_total{bucket=") for k in v)
    # process-wide training series ride the same endpoint
    assert any(k.startswith("transmogrifai_phase_wall_seconds_total")
               for k in v)
    # naming contract holds on the wire
    for name, mtype in t.items():
        assert name.startswith("transmogrifai_")
        if mtype == "counter":
            assert name.endswith("_total"), name


def test_healthz_endpoint(served_with_metrics):
    status, ctype, body = _get(served_with_metrics, "/healthz")
    assert status == 200 and ctype == "application/json"
    doc = json.loads(body)
    assert doc["status"] == "ok"
    assert doc["degraded"] is False
    assert "queueDepth" in doc


def test_metrics_endpoint_404_on_unknown_path(served_with_metrics):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(served_with_metrics, "/unknown")
    assert ei.value.code == 404


def test_metrics_http_stops_with_server(served_with_metrics):
    # a second server on port 0 starts and stops cleanly without
    # disturbing the module fixture's endpoint
    from transmogrifai_tpu.serving.http import MetricsServer
    ms = MetricsServer(render_fn=lambda: "x 1\n",
                       health_fn=lambda: {"status": "ok"}, port=0).start()
    port = ms.port
    ms.stop()
    with pytest.raises(Exception):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                               timeout=0.5)


# -- ServingMetrics fixes -----------------------------------------------------

def test_rolling_rps_vs_lifetime_idle_then_busy():
    from transmogrifai_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(rolling_window_s=5.0)
    m._t0 -= 1000.0  # the server has been idle for ~17 minutes
    m.record_requests_done([(0.01, True)] * 50)
    lifetime = m.throughput_rps()
    rolling = m.rolling_rps()
    assert lifetime < 0.1          # idle-diluted average
    assert rolling >= 50 / 5.0     # steady-state window sees the burst
    snap = m.snapshot(mirror_to_profiler=False)
    assert snap["throughputRps"] == pytest.approx(lifetime, rel=0.2)
    assert snap["throughputRpsRolling"] >= 10.0
    assert snap["rollingWindowSeconds"] == 5.0


def test_latency_histogram_cumulative_and_monotonic():
    from transmogrifai_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.record_requests_done([(0.0005, True), (0.003, True), (0.3, True),
                            (99.0, False)])
    h = m.latency_histogram()
    assert h["count"] == 4
    assert h["buckets"]["0.001"] == 1
    assert h["buckets"]["0.005"] == 2
    assert h["buckets"]["0.5"] == 3
    assert h["buckets"]["+Inf"] == 4
    assert h["sum"] == pytest.approx(99.3035)
    vals = list(h["buckets"].values())
    assert vals == sorted(vals)
    # monotonic: recording more never decreases any bucket
    m.record_requests_done([(0.002, True)])
    h2 = m.latency_histogram()
    assert all(h2["buckets"][k] >= h["buckets"][k] for k in h["buckets"])


# -- prometheus registry units ------------------------------------------------

def test_registry_rejects_bad_names():
    from transmogrifai_tpu.utils.prometheus import PromRegistry
    reg = PromRegistry()
    with pytest.raises(ValueError, match="snake_case"):
        reg.register("badName", "gauge", "x", lambda: [])
    with pytest.raises(ValueError, match="prefix|snake_case"):
        reg.register("serving_x", "gauge", "x", lambda: [])
    with pytest.raises(ValueError, match="_total"):
        reg.register("transmogrifai_x", "counter", "x", lambda: [])
    with pytest.raises(ValueError, match="_total"):
        reg.register("transmogrifai_x_total", "gauge", "x", lambda: [])
    reg.register("transmogrifai_x_total", "counter", "x",
                 lambda: [({}, 1)])
    with pytest.raises(ValueError, match="already"):
        reg.register("transmogrifai_x_total", "counter", "x",
                     lambda: [({}, 1)])


def test_registry_render_escapes_and_survives_broken_collector():
    from transmogrifai_tpu.utils.prometheus import PromRegistry
    reg = PromRegistry()
    reg.register("transmogrifai_ok", "gauge", "fine",
                 lambda: [({"label": 'va"l\n'}, 2.5)])

    def boom():
        raise RuntimeError("collector died")
    reg.register("transmogrifai_broken", "gauge", "broken", boom)
    out = reg.render()
    assert 'transmogrifai_ok{label="va\\"l\\n"} 2.5' in out
    assert "# collect failed: RuntimeError" in out  # scrape still served


# -- metric-name lint (tier-1 wiring) -----------------------------------------

def test_metric_names_lint_passes():
    lint = _load_script("scripts/check_metric_names.py")
    assert lint.collect_violations() == []
    assert lint.main([]) == 0


def test_metric_names_lint_flags_violations():
    lint = _load_script("scripts/check_metric_names.py")
    out = lint.check_json_doc({"snake_case_key": 1,
                               "okKey": {"BadInner": 2}}, "doc")
    assert len(out) == 2
    # data-keyed maps are exempt
    assert lint.check_json_doc(
        {"phases": {"ModelTraining": {"wallSeconds": 1}}}, "doc") == []

    class FakeReg:
        def names(self):
            return ["transmogrifai_thing_total", "transmogrifai_BAD"]

        def metric_types(self):
            return {"transmogrifai_thing_total": "gauge",
                    "transmogrifai_BAD": "counter"}

        def render(self):
            return ""
    out = lint.check_registry(FakeReg())
    assert any("_total" in v for v in out)
    assert any("snake_case" in v for v in out)


# -- artifact schema ----------------------------------------------------------

def test_observability_artifact_committed_and_valid():
    checker = _load_script("scripts/check_artifacts.py")
    path = os.path.join(REPO, "benchmarks", "OBSERVABILITY.json")
    assert os.path.exists(path), "benchmarks/OBSERVABILITY.json missing"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["spans_overhead_pct"] <= checker.MAX_SPAN_OVERHEAD_PCT
    assert art["span_count"] > 0


def test_observability_artifact_schema_rejections():
    checker = _load_script("scripts/check_artifacts.py")
    good = {"metric": "observability_overhead", "platform": "cpu",
            "rows": 100, "base_wall_s": 1.0, "spans_wall_s": 1.02,
            "export_wall_s": 1.1, "spans_overhead_pct": 2.0,
            "export_overhead_pct": 10.0, "span_count": 12}
    assert checker.validate_artifact(good) == []
    over = dict(good, spans_overhead_pct=7.5)
    assert any("exceeds" in e for e in checker.validate_artifact(over))
    missing = dict(good)
    del missing["export_wall_s"]
    assert any("export_wall_s" in e
               for e in checker.validate_artifact(missing))
    no_spans = dict(good, span_count=0)
    assert any("span_count" in e
               for e in checker.validate_artifact(no_spans))


# -- multihost aggregation ----------------------------------------------------

def test_aggregate_across_hosts_identity_and_mesh(mesh8):
    from transmogrifai_tpu.utils.profiling import (
        AppMetrics, OpStep, aggregate_across_hosts,
    )
    m = AppMetrics()
    m.record(OpStep.MODEL_TRAINING, 2.0)
    m.record(OpStep.SCORING, 1.0)
    m.phases["ModelTraining"].device_s = 0.5
    m.stages = {"Vec (u1)": {"wallSeconds": 0.25, "deviceSeconds": 0.1,
                             "count": 2, "phase": "fit"}}
    local = aggregate_across_hosts(m, ctx=None)
    assert local["hosts"] == 1
    assert local["phases"]["ModelTraining"]["wallSeconds"] == 2.0
    # through the mesh reduction (single-process: sums must equal local)
    agg = aggregate_across_hosts(m, ctx=mesh8)
    assert agg["phases"]["ModelTraining"]["wallSeconds"] == \
        pytest.approx(2.0, rel=1e-5)
    assert agg["phases"]["ModelTraining"]["deviceSeconds"] == \
        pytest.approx(0.5, rel=1e-5)
    assert agg["phases"]["ModelTraining"]["count"] == 1
    assert agg["phases"]["Scoring"]["wallSeconds"] == \
        pytest.approx(1.0, rel=1e-5)
    assert agg["stages"]["Vec (u1)"]["wallSeconds"] == \
        pytest.approx(0.25, rel=1e-5)
    assert agg["stages"]["Vec (u1)"]["count"] == 2


def test_reduce_host_metrics_sums(mesh8):
    from transmogrifai_tpu.parallel.collectives import reduce_host_metrics
    out = reduce_host_metrics(mesh8, {"a": 3.0, "b": 0.5})
    assert out["a"] == pytest.approx(3.0, rel=1e-5)
    assert out["b"] == pytest.approx(0.5, rel=1e-5)
    assert reduce_host_metrics(mesh8, {}) == {}


# -- cli profile --------------------------------------------------------------

def test_cli_profile_emits_trace_and_table(served_with_metrics, tmp_path,
                                           capsys):
    from transmogrifai_tpu.cli import main as cli_main
    model = served_with_metrics.model
    model_dir = str(tmp_path / "model")
    model.save(model_dir)
    rng = np.random.default_rng(0)
    csv_path = str(tmp_path / "data.csv")
    with open(csv_path, "w") as fh:
        fh.write("x1,x2\n")
        for _ in range(20):
            fh.write(f"{rng.normal():.4f},{rng.normal():.4f}\n")
    trace = str(tmp_path / "trace.json")
    metrics = str(tmp_path / "metrics.json")
    rc = cli_main(["profile", "--model", model_dir, "--input", csv_path,
                   "--trace-out", trace, "--metrics-out", metrics,
                   "--no-device-trace"])
    assert rc == 0
    doc = json.load(open(trace))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "reader.generate_frame" in names
    # scoring dispatches through the fused FE segment program when
    # TRANSMOGRIFAI_FE_FUSED=1 (the default, round 14) and through the
    # per-layer program otherwise — either span proves the device leg
    assert {"layer.apply_device", "fe.fused"} & names
    mdoc = json.load(open(metrics))
    assert "Scoring" in mdoc["phases"]
    err = capsys.readouterr().err
    assert "slowest stages" in err or "metrics" in err


# -- serving span coverage ----------------------------------------------------

def test_serving_batch_spans_recorded(served_with_metrics):
    from transmogrifai_tpu.utils.tracing import recorder
    server = served_with_metrics
    server.score({"x1": 0.5, "x2": -0.5}, timeout_s=10)
    names = {s.name for s in recorder.spans}
    assert {"serving.queue_wait", "serving.dispatch",
            "serving.compiled_dispatch", "serving.settle"} <= names
    qw = [s for s in recorder.spans if s.name == "serving.queue_wait"]
    assert all(s.attrs.get("rows", 0) >= 1 for s in qw)
