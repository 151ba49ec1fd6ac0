"""Resource-exhaustion resilience (the adaptive degradation ladder):
OOM/ENOSPC classification over shared cause chains, the oom/enospc
fault kinds, the sweep's stacked->fold-loop and tree lane-chunk rungs
(bitwise winner parity + checkpointed rung log), the serving
bucket-shedding rung (zero dropped requests), counted best-effort
ENOSPC handling in durable writes and the event spill, the continuous
retrain window shrink, and the transmogrifai_resource_* / healthz
surfaces — with the ladder-disabled fail-fast contract asserted
alongside every rung."""

import errno
import json
import os
import warnings

import numpy as np
import pytest

from transmogrifai_tpu import dsl  # noqa: F401 — installs operators
from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.models.trees import OpGBTClassifier
from transmogrifai_tpu.ops.transmogrifier import transmogrify
from transmogrifai_tpu.selector import (
    BinaryClassificationModelSelector, DataSplitter,
)
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.uid import UID
from transmogrifai_tpu.utils import resources
from transmogrifai_tpu.utils.faults import (
    FaultPlan, FaultSpec, XlaRuntimeError, fault_plan,
)
from transmogrifai_tpu.utils.resources import resource_counters
from transmogrifai_tpu.utils.retry import is_transient_device_error
from transmogrifai_tpu.workflow import Workflow


def _oom_error() -> XlaRuntimeError:
    return XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1073741824 bytes")


@pytest.fixture(autouse=True)
def _clean_counters():
    resource_counters.reset()
    yield
    resource_counters.reset()


def _frame(n=300, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n).astype(float)
    x = rng.normal(size=n) + 0.8 * y
    return fr.HostFrame.from_dict({
        "x": (ft.Real, x.tolist()),
        "x2": (ft.Real, rng.normal(size=n).tolist()),
        "label": (ft.RealNN, y.tolist()),
    })


def _train(selector, frame):
    UID.reset()
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    vec = transmogrify(list(feats.values()), min_support=1)
    pred = label.transform_with(selector, vec)
    return (Workflow().set_input_frame(frame)
            .set_result_features(pred).train())


def _selector(checkpoint_dir=None, single=False):
    fams = [(OpLogisticRegression(max_iter=25),
             [{"reg_param": r} for r in (0.01, 0.1)])]
    if not single:
        fams.append((OpGBTClassifier(num_rounds=4, max_depth=2),
                     [{"learning_rate": lr} for lr in (0.1, 0.3)]))
    return BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1, models_and_parameters=fams,
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1),
        checkpoint_dir=checkpoint_dir)


def _assert_summaries_equal(s1, s2):
    assert s1.best_model_name == s2.best_model_name
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    assert set(v1) == set(v2)
    for k in v1:
        for m in v1[k]:
            assert v1[k][m] == v2[k][m], (k, m)


# ---------------------------------------------------------------------------
# classifiers (the shared cause-chain walk)
# ---------------------------------------------------------------------------

def test_oom_classifier_walks_cause_chain():
    oom = _oom_error()
    assert resources.is_resource_exhausted(oom)
    assert not is_transient_device_error(oom)  # never same-shape retried
    # wrapped cause: still classified
    try:
        try:
            raise oom
        except Exception as e:
            raise ValueError("layer wrap") from e
    except ValueError as wrapped:
        assert resources.is_resource_exhausted(wrapped)
    # implicit context (raise-while-handling): still classified
    try:
        try:
            raise oom
        except Exception:
            raise KeyError("handler blew up")
    except KeyError as ctx:
        assert resources.is_resource_exhausted(ctx)
    # `raise ... from None` severs the chain — honored
    try:
        try:
            raise oom
        except Exception:
            raise ValueError("deliberately severed") from None
    except ValueError as severed:
        assert not resources.is_resource_exhausted(severed)
    # host allocation failure is unambiguous
    assert resources.is_resource_exhausted(MemoryError())
    # exact type names only: RuntimeError subclasses never match
    assert not resources.is_resource_exhausted(
        NotImplementedError("Out of memory"))
    # transient stays transient, OOM stays OOM — disjoint marker sets
    transient = XlaRuntimeError("UNAVAILABLE: flaky device")
    assert is_transient_device_error(transient)
    assert not resources.is_resource_exhausted(transient)


def test_disk_full_classifier():
    e = OSError(errno.ENOSPC, "No space left on device")
    assert resources.is_disk_full(e)
    assert not resources.is_disk_full(OSError("plain IO error"))
    assert not resources.is_disk_full(_oom_error())
    try:
        try:
            raise e
        except OSError as inner:
            raise RuntimeError("checkpoint failed") from inner
    except RuntimeError as wrapped:
        assert resources.is_disk_full(wrapped)


# ---------------------------------------------------------------------------
# fault kinds
# ---------------------------------------------------------------------------

def test_oom_and_enospc_fault_kinds():
    spec = FaultSpec.parse("oom@sweep.fit#1x2")
    assert (spec.kind, spec.at, spec.times) == ("oom", 1, 2)
    plan = FaultPlan(["oom@sweep.fit#1x2", "enospc@checkpoint.write"])
    # invocation 0 clean, 1 and 2 fire, 3 clean
    plan.check("sweep.fit")
    for _ in range(2):
        with pytest.raises(XlaRuntimeError) as ei:
            plan.check("sweep.fit")
        assert resources.is_resource_exhausted(ei.value)
        assert not is_transient_device_error(ei.value)
    plan.check("sweep.fit")
    with pytest.raises(OSError) as oi:
        plan.check("checkpoint.write")
    assert oi.value.errno == errno.ENOSPC
    assert resources.is_disk_full(oi.value)
    assert plan.fired == [("sweep.fit", 1, "oom"), ("sweep.fit", 2, "oom"),
                          ("checkpoint.write", 0, "enospc")]


# ---------------------------------------------------------------------------
# sweep rungs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_frame():
    return _frame()


@pytest.fixture(scope="module")
def loop_summary(sweep_frame, fold_loop):
    """The per-fold-loop reference run every rung's result must match
    bitwise."""
    with fold_loop():
        return _train(_selector(), sweep_frame).selector_summary()


def test_stacked_family_oom_degrades_to_fold_loop(sweep_frame,
                                                 loop_summary):
    """An OOM at the LR family's stacked dispatch re-dispatches that
    family on the per-fold loop: the run completes, the winner and every
    validation metric are bitwise those of the loop path, and the rung
    is counted once at its site."""
    with fault_plan("oom@sweep.fit#0"):
        with pytest.warns(RuntimeWarning, match="degrading to rung"):
            s = _train(_selector(), sweep_frame).selector_summary()
    rc = resource_counters.to_json()
    assert rc["degradationsBySite"] == {"sweep.stacked": 1}
    assert rc["oomEvents"] == 1
    assert not s.failures
    _assert_summaries_equal(s, loop_summary)


def test_tree_group_oom_halves_lane_chunks(sweep_frame, loop_summary,
                                           tmp_path):
    """An OOM at the GBT depth-group's stacked chunk (invocation 1: the
    LR family dispatched clean at 0) halves the lane-chunk width and
    retries the SAME lanes — only that group degrades, the LR family
    stays on its stacked path, metrics stay bitwise, and the sweep
    checkpoint records the rung."""
    ckpt = str(tmp_path / "sweep_ckpt")
    with fault_plan("oom@sweep.fit#1"):
        with pytest.warns(RuntimeWarning, match="degrading to rung"):
            s = _train(_selector(checkpoint_dir=ckpt),
                       sweep_frame).selector_summary()
    rc = resource_counters.to_json()
    assert rc["degradationsBySite"] == {"sweep.tree_group": 1}
    assert not s.failures
    _assert_summaries_equal(s, loop_summary)
    # the checkpoint records WHICH shape ran degraded, at which rung
    with open(os.path.join(ckpt, "sweep.json")) as fh:
        doc = json.load(fh)
    degs = doc.get("degradations")
    assert degs and degs[0]["site"] == "sweep.tree_group"
    assert degs[0]["rung"].startswith("lane_chunk_")
    # the LR family was untouched by the tree group's rung
    from transmogrifai_tpu.utils.profiling import sweep_counters
    lr = sweep_counters.families.get("OpLogisticRegression_0")
    assert lr is not None and lr.mode == "fold_stacked"


def test_settle_oom_collects_family_for_fold_retry(sweep_frame):
    """A settle-time OOM (device pressure that materializes only when
    the overlapped programs run) routes the family into the caller's
    ``oom_retry`` list instead of a failure record, popping its partial
    scores."""
    class _OomOnMaterialize:
        def __array__(self, dtype=None):
            raise _oom_error()

    sel = _selector(single=True)
    per_scores = {(0, 0): [0.5], (0, 1): [0.6]}
    failures: list = []
    oom_retry: list = []
    pending = [{"kind": "stacked", "ci": 0, "fname": "LR_0",
                "key": "0:stacked:3x100x2", "k": 3, "grid_len": 2,
                "chunks": [(0, 2, _OomOnMaterialize())]}]
    with pytest.warns(RuntimeWarning, match="degrading to rung"):
        sel._settle(pending, {}, per_scores, failures,
                    oom_retry=oom_retry)
    assert oom_retry == [0]
    assert failures == []
    assert per_scores == {}
    # without the ladder the same settle failure records a failure
    resource_counters.reset()
    os.environ["TRANSMOGRIFAI_RESOURCE_LADDER"] = "0"
    try:
        pending[0]["chunks"] = [(0, 2, _OomOnMaterialize())]
        oom_retry2: list = []
        sel._settle(pending, {}, {(0, 0): [0.5]}, failures,
                    oom_retry=oom_retry2)
        assert oom_retry2 == [] and len(failures) == 1
        assert resource_counters.to_json()["degradations"] == 0
    finally:
        del os.environ["TRANSMOGRIFAI_RESOURCE_LADDER"]


def test_ladder_disabled_sweep_fault_fails_fast(sweep_frame,
                                                monkeypatch):
    """With the ladder off, the identical injected OOM keeps its
    pre-ladder behavior exactly: candidate-failure isolation (and a
    single-family selector raises), zero rungs counted."""
    monkeypatch.setenv("TRANSMOGRIFAI_RESOURCE_LADDER", "0")
    with fault_plan("oom@sweep.fit#0"):
        s = _train(_selector(), sweep_frame).selector_summary()
    assert any("RESOURCE_EXHAUSTED" in f.get("reason", "")
               for f in s.failures)
    assert resource_counters.to_json()["degradations"] == 0
    with fault_plan("oom@sweep.fit#0x*"):
        with pytest.raises(RuntimeError, match="every candidate failed"):
            _train(_selector(single=True), sweep_frame)


def test_refit_warm_oom_falls_back_cold(sweep_frame):
    """An OOM inside the warm-started winner refit releases the retained
    fold parameters and refits cold (bitwise the refit of a family that
    reports no warm refit) instead of dying after a completed sweep."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(OpLogisticRegression, "supports_warm_refit",
                   lambda self: False)
        s_cold = _train(_selector(single=True),
                        sweep_frame).selector_summary()
    resource_counters.reset()
    # single LR family: sweep.fit#0 is the stacked sweep dispatch,
    # #1 is the refit unit
    with fault_plan("oom@sweep.fit#1"):
        with pytest.warns(RuntimeWarning, match="degrading to rung"):
            s = _train(_selector(single=True),
                       sweep_frame).selector_summary()
    rc = resource_counters.to_json()
    assert rc["degradationsBySite"] == {"selector.refit": 1}
    assert s.best_model_name == s_cold.best_model_name
    for k in s.train_evaluation:
        assert s.train_evaluation[k] == s_cold.train_evaluation[k]


# ---------------------------------------------------------------------------
# serving rungs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    UID.reset()
    n = 160
    rng = np.random.default_rng(3)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = (rng.uniform(size=n)
         < 1 / (1 + np.exp(-(1.5 * x1 - x2)))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x1"], feats["x2"]])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=1, models_and_parameters=[
            (OpLogisticRegression(max_iter=25), [{}])])
    pred = feats["y"].transform_with(sel, features)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred, features).train())
    rows = [{"x1": float(x1[i]), "x2": float(x2[i])} for i in range(n)]
    return model, rows


def test_serving_oom_sheds_buckets_zero_drops(served):
    """A mid-traffic OOM sheds the largest padding bucket and re-serves
    the batch compiled at the smaller shape: zero dropped requests, zero
    failed futures, NO row-path degradation, and the rung observable in
    counters + the flight recorder."""
    from transmogrifai_tpu.serving import ScoringServer
    from transmogrifai_tpu.utils.events import events
    model, rows = served
    events.reset()
    srv = ScoringServer(model, max_batch=32, min_bucket=8,
                        max_wait_ms=1.0)
    srv.start(warmup_row=rows[0])
    assert srv.scorer.buckets == [8, 16, 32]
    with fault_plan("oom@serving.dispatch#1"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            futs = [srv.submit(dict(r)) for r in rows[:60]]
            results = [f.result(timeout=30) for f in futs]
    srv.stop()
    assert all(isinstance(r, dict) for r in results)
    assert srv.scorer.buckets == [8, 16]
    assert srv.scorer.max_batch == 16
    snap = srv.metrics.snapshot(mirror_to_profiler=False)
    assert snap["requests"]["failed"] == 0
    assert snap["requests"]["completed"] == 60
    assert snap["degraded"]["entries"] == 0  # compiled path, narrower
    rc = resource_counters.to_json()
    assert rc["degradationsBySite"].get("serving.dispatch", 0) >= 1
    degr = [e for e in events.tail() if e["kind"] == "resource.degrade"]
    assert degr and degr[0]["site"] == "serving.dispatch"
    assert degr[0]["rung"] == "shed_bucket_32"


def test_serving_shed_floor_falls_to_row_path(served):
    """OOM with only one bucket left exhausts the rungs: the row path
    serves (pre-existing degradation), still zero drops."""
    from transmogrifai_tpu.serving import ScoringServer
    model, rows = served
    srv = ScoringServer(model, max_batch=8, min_bucket=8,
                        max_wait_ms=1.0)
    srv.start(warmup_row=rows[0])
    assert srv.scorer.buckets == [8]
    with fault_plan("oom@serving.dispatch#1x*"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            futs = [srv.submit(dict(r)) for r in rows[:20]]
            results = [f.result(timeout=30) for f in futs]
    srv.stop()
    assert all(isinstance(r, dict) for r in results)
    snap = srv.metrics.snapshot(mirror_to_profiler=False)
    assert snap["requests"]["failed"] == 0
    assert snap["degraded"]["entries"] >= 1  # floor reached: row path
    assert srv.scorer.buckets == [8]  # nothing left to shed


def test_shed_success_exits_degraded_mode(served):
    """An OOM on a degraded-mode PROBE batch that the shed rung recovers
    clears degraded mode immediately (recovery recorded) — the server
    must not pin traffic on the row path for another probe interval
    after the compiled path just proved good at the smaller shape."""
    import time as _time
    from transmogrifai_tpu.serving import ScoringServer
    model, rows = served
    srv = ScoringServer(model, max_batch=32, min_bucket=8,
                        max_wait_ms=1.0)
    srv.start(warmup_row=rows[0])
    srv._degraded_since = _time.monotonic() - 5.0  # degraded, probe due
    srv._last_probe = 0.0
    srv.metrics.record_degraded_entry()
    with fault_plan("oom@serving.dispatch#0"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            r = srv.score(dict(rows[0]), timeout_s=30)
    srv.stop()
    assert isinstance(r, dict)
    assert not srv.degraded
    assert srv.metrics.snapshot(
        mirror_to_profiler=False)["degraded"]["recoveries"] >= 1


def test_serving_ladder_off_keeps_old_behavior(served, monkeypatch):
    """Ladder off + the same OOM = the pre-ladder contract exactly:
    row-path degradation, buckets untouched, zero rungs."""
    from transmogrifai_tpu.serving import ScoringServer
    monkeypatch.setenv("TRANSMOGRIFAI_RESOURCE_LADDER", "0")
    model, rows = served
    srv = ScoringServer(model, max_batch=32, min_bucket=8,
                        max_wait_ms=1.0)
    srv.start(warmup_row=rows[0])
    with fault_plan("oom@serving.dispatch#1"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            futs = [srv.submit(dict(r)) for r in rows[:40]]
            results = [f.result(timeout=30) for f in futs]
    srv.stop()
    assert all(isinstance(r, dict) for r in results)
    assert srv.scorer.buckets == [8, 16, 32]
    snap = srv.metrics.snapshot(mirror_to_profiler=False)
    assert snap["requests"]["failed"] == 0
    assert snap["degraded"]["entries"] >= 1
    assert resource_counters.to_json()["degradations"] == 0


def test_program_cache_pressure_eviction():
    """evict_cold frees LRU-oldest entries (never the last one) and
    evict_bucket drops one (model, bucket) slice, both attributing
    evictions to the owners' counters."""
    from transmogrifai_tpu.serving.fleet import ProgramCache
    from transmogrifai_tpu.utils.profiling import ServingCounters
    cache = ProgramCache()
    c = ServingCounters()
    for i, b in enumerate((8, 16, 32)):
        cache.get(("fp", 0, b), lambda: object(), bytes_est=100,
                  counters=c, bucket=b)
        cache.get(("fp2", 0, b), lambda: object(), bytes_est=100,
                  counters=c, bucket=b)
    assert len(cache) == 6 and cache.current_bytes == 600
    freed = cache.evict_cold(250)
    assert freed == 300 and len(cache) == 3
    assert cache.evictions == 3
    n = cache.evict_bucket("fp2", 32)
    assert n == 1
    assert ("fp2", 0, 32) not in cache.keys()
    # never evicts the last entry under pressure
    cache2 = ProgramCache()
    cache2.get(("fp", 0, 8), lambda: object(), bytes_est=100,
               counters=c, bucket=8)
    assert cache2.evict_cold(10**9) == 0 and len(cache2) == 1
    # evictions attributed per bucket: the LRU pass dropped both 8s and
    # one 16; evict_bucket dropped one 32
    assert c.bucket(8).evictions == 2
    assert c.bucket(16).evictions == 1
    assert c.bucket(32).evictions == 1


# ---------------------------------------------------------------------------
# ENOSPC: counted best-effort writes + spill accounting
# ---------------------------------------------------------------------------

def test_enospc_checkpoint_write_counts_and_backs_off():
    from transmogrifai_tpu.utils.durable import best_effort_checkpoint_write
    calls = []

    def full_disk():
        calls.append(1)
        raise OSError(errno.ENOSPC, "No space left on device")

    with pytest.warns(RuntimeWarning, match="No space left"):
        assert best_effort_checkpoint_write(full_disk, "ckpt write") \
            is False
    rc = resource_counters.to_json()
    assert rc["enospcEvents"] == 1
    assert resource_counters.enospc_backoff_active()
    # inside the cooldown: the write is SKIPPED (counted), not attempted
    assert best_effort_checkpoint_write(full_disk, "ckpt write") is False
    assert len(calls) == 1
    assert resource_counters.to_json()["writesSkipped"] == 1
    # a non-ENOSPC failure neither counts nor arms the backoff
    resource_counters.reset()

    def plain_fail():
        raise OSError("unrelated")

    with pytest.warns(RuntimeWarning):
        best_effort_checkpoint_write(plain_fail, "ckpt write")
    rc = resource_counters.to_json()
    assert rc["enospcEvents"] == 0
    assert not resource_counters.enospc_backoff_active()


def test_enospc_event_spill_counted_never_raises(tmp_path):
    """ENOSPC inside the spill writer loses the batch ACCOUNTED
    (spill_lost + resource enospc counters), never raises into the
    serving path — and does NOT arm the durable-write cooldown (the
    spill's volume may not be the checkpoint volume; checkpoint writes
    re-detect their own ENOSPC)."""
    from transmogrifai_tpu.utils.events import EventRing
    ring = EventRing(maxlen=64)
    ring.configure(spill_path=str(tmp_path / "events.jsonl"))
    try:
        with fault_plan("enospc@events.spill#0"):
            ring.emit("test.event", n=1)
            ring.flush()  # hits the injected ENOSPC; must not raise
        assert ring.spill_lost >= 1
        assert resource_counters.to_json()["enospcEvents"] >= 1
        assert not resource_counters.enospc_backoff_active()
        # the spill recovers on the next drain (new batch, reopened file)
        ring.emit("test.event", n=2)
        ring.flush()
        assert ring.spilled >= 1
    finally:
        ring.configure(spill_path=None)


# ---------------------------------------------------------------------------
# continuous loop: retrain window shrink
# ---------------------------------------------------------------------------

def test_continuous_retrain_oom_shrinks_window(tmp_path):
    """An OOM-failed retrain halves the row window for the backed-off
    retry and keeps the pending record (old model keeps serving, no
    abandonment); the capped retry trains on the newest half."""
    from transmogrifai_tpu.continuous import ContinuousLoop
    UID.reset()
    rng = np.random.default_rng(0)
    n = 120
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = (rng.uniform(size=n)
         < 1 / (1 + np.exp(-(1.5 * x1 - x2)))).astype(float)
    host = fr.HostFrame.from_dict({
        "label": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
    })
    feats = FeatureBuilder.from_frame(host, response="label")
    vec = transmogrify([feats["x1"], feats["x2"]])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=1, models_and_parameters=[
            (OpLogisticRegression(max_iter=20), [{}])])
    pred = feats["label"].transform_with(sel, vec)
    wf = Workflow().set_input_frame(host).set_result_features(pred, vec)

    loop = ContinuousLoop(
        wf, stream_dir=str(tmp_path / "stream"),
        state_dir=str(tmp_path / "state"), window_batches=1,
        poll_interval_s=0.05, timeout_s=0.1)
    rows = [{"label": float(y[i]), "x1": float(x1[i]),
             "x2": float(x2[i])} for i in range(n)]
    loop._rows_by_source["b0.csv"] = rows
    loop.state.record_batch("b0.csv", len(rows), 8)
    loop.state.begin_retrain(["test"], str(tmp_path / "ckpt"))
    with fault_plan("oom@continuous.retrain#0"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            assert loop._execute_retrain() is False
    assert loop._retrain_row_cap == len(rows) // 2
    assert loop.state.pending_retrain is not None  # NOT abandoned
    rc = resource_counters.to_json()
    assert rc["degradationsBySite"].get("continuous.retrain") == 1
    assert loop.metrics.retrain_failures == 1
    assert loop._window_rows(loop.state.pending_retrain) == \
        rows[-(len(rows) // 2):]
    # the capped retry trains and promotes (bootstrap registration),
    # which resets the cap for the next full window
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert loop._execute_retrain() is True
    try:
        assert loop.fleet.registry.active_version("live") is not None
        assert loop._retrain_row_cap is None
    finally:
        loop.fleet.stop()


# ---------------------------------------------------------------------------
# observability surfaces
# ---------------------------------------------------------------------------

def test_resource_prometheus_series_and_health(served):
    from transmogrifai_tpu.serving import ScoringServer
    from transmogrifai_tpu.utils.prometheus import build_registry
    resource_counters.note_degradation("serving.dispatch")
    resource_counters.note_oom()
    text = build_registry(include_app=False).render()
    assert ('transmogrifai_resource_degradations_total'
            '{site="serving.dispatch"} 1') in text
    assert "transmogrifai_resource_oom_events_total 1" in text
    assert "transmogrifai_resource_rss_bytes" in text
    assert "transmogrifai_resource_ladder_enabled 1" in text
    assert "# collect failed" not in text
    model, rows = served
    srv = ScoringServer(model, max_batch=8)
    doc = srv.health()
    res = doc["resources"]
    assert res["ladderEnabled"] is True
    assert res["counters"]["degradations"] == 1
    assert isinstance(res["rssBytes"], int)


def test_pressure_state_budgets_and_watchdog(monkeypatch):
    state = resources.pressure_state()
    assert state["rssPressure"] is False  # no budget configured
    assert state["rssBytes"] > 0
    monkeypatch.setenv("TRANSMOGRIFAI_RSS_BUDGET", "1")
    monkeypatch.setenv("TRANSMOGRIFAI_DISK_MIN_FREE", "1")
    state = resources.pressure_state()
    assert state["rssPressure"] is True
    assert state["diskPressure"] is False  # plenty of disk vs 1 byte
    wd = resources.ResourceWatchdog(".", interval_s=0.01)
    from transmogrifai_tpu.utils.events import events
    events.reset()
    with pytest.warns(RuntimeWarning, match="host resource pressure"):
        sample = wd.tick()
    assert sample["rssPressure"] is True
    assert any(e["kind"] == "resource.pressure" for e in events.tail())
    # second tick in the same pressured state: no duplicate event
    n_events = len(events.tail())
    wd.tick()
    assert len(events.tail()) == n_events


def test_watch_path_points_probes_at_write_root(tmp_path):
    """Daemons point the default pressure probes at their write root —
    the /healthz and gauge disk numbers must describe the filesystem
    the process writes, not the cwd's."""
    saved = resources.watch_path()
    try:
        resources.set_watch_path(str(tmp_path))
        assert resources.watch_path() == str(tmp_path)
        assert resources.disk_free_bytes() > 0
        assert resources.pressure_state()["diskFreeBytes"] > 0
        # a bogus watch path degrades to the -1 probe-failed sentinel,
        # never a raise in a health endpoint
        resources.set_watch_path(str(tmp_path / "nope"))
        assert resources.pressure_state()["diskFreeBytes"] == -1
    finally:
        resources.set_watch_path(saved)


def test_run_summary_carries_resource_counters():
    from transmogrifai_tpu.utils.profiling import AppMetrics
    resource_counters.note_degradation("sweep.stacked")
    doc = AppMetrics().to_json()
    assert doc["resourceCounters"]["degradations"] == 1
    assert doc["resourceCounters"]["degradationsBySite"] == {
        "sweep.stacked": 1}


def test_failure_lint_rejects_adhoc_classifier(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import check_failure_paths as lint
    bad = tmp_path / "handler.py"
    bad.write_text(
        "def f(e):\n"
        "    if 'RESOURCE_EXHAUSTED' in str(e):\n"
        "        return True\n")
    out = lint.check_file(str(bad))
    assert out and "ad-hoc resource-exhaustion" in out[0]
    ok = tmp_path / "resources.py"
    ok.write_text(
        "def f(e):\n"
        "    return 'RESOURCE_EXHAUSTED' in str(e)\n")
    assert lint.check_file(str(ok)) == []
    # the live tree stays clean
    pkg_root = os.path.join(os.path.dirname(__file__), "..",
                            "transmogrifai_tpu")
    assert [v for v in lint.check_tree(pkg_root)
            if "ad-hoc" in v] == []
