"""Device-execution observatory (round 12): the shared all-device HBM
census, the dispatch ledger, the stall watchdog (arm/disarm/fire/
no-false-fire), fault-injected hang autopsies end-to-end (a slow
collective and a stalled one-sync settle), compile telemetry, the
``cli autopsy`` reader, and the new artifact schemas."""

import contextlib
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 160


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".py", "").replace("/", "_"), os.path.join(REPO, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@contextlib.contextmanager
def _persistent_cache_in(path):
    """JAX's persistent compilation cache in ``path``, every program
    cached, for the block; the settings it found come back after it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        yield
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()


@pytest.fixture
def dw():
    """The devicewatch module with the global watchdog's config + stall
    counters snapshotted and restored (tests stall it on purpose)."""
    from transmogrifai_tpu.utils import devicewatch
    wd = devicewatch.watchdog
    saved = (wd.enabled, wd.incident_dir, wd._default_timeout_s,
             wd.poll_interval_s, wd.stalls, dict(wd.stalls_by_site),
             wd.autopsies, wd.guards)
    led_enabled = devicewatch.dispatch_ledger.enabled
    yield devicewatch
    (wd.enabled, wd.incident_dir, wd._default_timeout_s,
     wd.poll_interval_s, wd.stalls, wd.stalls_by_site,
     wd.autopsies, wd.guards) = (saved[0], saved[1], saved[2], saved[3],
                                 saved[4], dict(saved[5]), saved[6],
                                 saved[7])
    devicewatch.dispatch_ledger.enabled = led_enabled


class _FakeDev:
    def __init__(self, in_use, peak, limit):
        self._s = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                   "bytes_limit": limit}

    def memory_stats(self):
        return self._s

    def __str__(self):
        return f"FakeDev({self._s['bytes_in_use']})"


# -- the shared census --------------------------------------------------------

def test_census_sums_across_all_devices(monkeypatch):
    import jax

    from transmogrifai_tpu.utils import devicewatch
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDev(1, 2, 3), _FakeDev(10, 20, 30),
                                 _FakeDev(100, 200, 300)])
    c = devicewatch.device_memory_census()
    assert c["bytesInUse"] == 111
    assert c["peakBytesInUse"] == 222
    assert c["bytesLimit"] == 333
    assert len(c["devices"]) == 3
    assert devicewatch.device_memory() == (111, 222)
    assert devicewatch.device_bytes_limit() == 333


def test_single_device_probes_deleted_for_shared_census(monkeypatch):
    """The satellite fix: per-phase (profiling), per-span (tracing), and
    the sweep HBM budget all read the SAME all-device census — none of
    them probes jax.local_devices()[0] anymore. The budget sums the
    mesh only when one is ACTIVE (un-meshed, the stacked batch lands on
    a single device and an N-device sum would over-admit by N)."""
    import jax

    from transmogrifai_tpu.parallel import mesh as pmesh
    from transmogrifai_tpu.selector.model_selector import ModelSelector
    from transmogrifai_tpu.utils.profiling import _device_memory
    from transmogrifai_tpu.utils.tracing import SpanRecorder
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDev(5, 7, 1 << 30),
                                 _FakeDev(6, 9, 1 << 30)])
    assert _device_memory() == (11, 16)
    assert SpanRecorder._device_peak() == 16
    monkeypatch.delenv("TRANSMOGRIFAI_SWEEP_HBM_BUDGET", raising=False)
    monkeypatch.setattr(pmesh, "current_mesh", lambda: None)
    assert ModelSelector._stacked_hbm_budget() == pytest.approx(
        0.5 * (1 << 30))
    monkeypatch.setattr(pmesh, "current_mesh", lambda: object())
    assert ModelSelector._stacked_hbm_budget() == pytest.approx(
        0.5 * 2 * (1 << 30))


def test_live_buffer_census_buckets():
    import jax.numpy as jnp

    from transmogrifai_tpu.utils import devicewatch
    keep = [jnp.ones((64, 3)), jnp.ones((64, 3)), jnp.zeros(7)]
    c = devicewatch.live_buffer_census(top_k=5)
    assert c["arrays"] >= 3
    assert c["totalBytes"] > 0
    sizes = [b["bytes"] for b in c["buckets"]]
    assert sizes == sorted(sizes, reverse=True)
    shapes = {b["shape"] for b in c["buckets"]}
    assert "(64, 3)" in shapes
    del keep


def test_thread_stacks_capture_blocked_thread():
    from transmogrifai_tpu.utils import devicewatch
    release = threading.Event()
    started = threading.Event()

    def blocked():
        started.set()
        release.wait(timeout=5)

    t = threading.Thread(target=blocked, name="blocked-worker")
    t.start()
    started.wait(timeout=5)
    try:
        stacks = devicewatch.thread_stacks()
    finally:
        release.set()
        t.join(timeout=5)
    by_name = {s["threadName"]: s for s in stacks}
    assert "blocked-worker" in by_name
    frames = by_name["blocked-worker"]["frames"]
    assert frames and any("wait" in f for f in frames)


# -- the dispatch ledger ------------------------------------------------------

def test_ledger_register_complete_inventory():
    from transmogrifai_tpu.utils.devicewatch import DispatchLedger
    led = DispatchLedger()
    a = led.register("sweep.pending", family="OpGBT", unitKind="tree")
    b = led.register("serving.dispatch", rows=64)
    inv = led.inventory()
    assert len(led) == 2 and len(inv) == 2
    assert inv[0]["site"] == "sweep.pending"
    assert inv[0]["family"] == "OpGBT"
    assert inv[1]["rows"] == 64
    assert all(e["ageSeconds"] >= 0 for e in inv)
    led.complete(a)
    led.complete(a)  # idempotent
    led.complete(None)
    assert len(led) == 1 and led.completed == 1
    led.complete(b)
    assert len(led) == 0 and led.registered == 2


# -- watchdog units -----------------------------------------------------------

def test_guard_no_false_fire(dw):
    wd = dw.DispatchWatchdog()
    wd.configure(enabled=True, stall_timeout_s=5.0, poll_interval_s=0.05)
    with wd.guard("quick"):
        time.sleep(0.02)
    assert wd.stalls == 0 and wd.guards == 1
    assert wd.active_waits() == []


def test_guard_disabled_is_noop(dw):
    wd = dw.DispatchWatchdog()
    wd.configure(enabled=False, stall_timeout_s=0.01)
    with wd.guard("x") as wid:
        assert wid is None
        time.sleep(0.05)
    assert wd.stalls == 0 and wd.guards == 0


def test_configure_disabled_switches_off_ledger_too(dw):
    """TRANSMOGRIFAI_DEVICEWATCH=0 / configure(enabled=False) must
    restore the pre-observatory hot path: register() returns None and
    records nothing — the guard AND the ledger switch off together."""
    registered0 = dw.dispatch_ledger.registered
    in_flight0 = len(dw.dispatch_ledger)
    dw.configure(enabled=False)
    try:
        assert dw.dispatch_ledger.register("serving.dispatch",
                                           rows=8) is None
        assert dw.dispatch_ledger.registered == registered0
        assert len(dw.dispatch_ledger) == in_flight0
        dw.dispatch_ledger.complete(None)  # the paired call: a no-op
    finally:
        dw.configure(enabled=True)
    eid = dw.dispatch_ledger.register("serving.dispatch", rows=8)
    assert eid is not None
    dw.dispatch_ledger.complete(eid)


def test_guard_stall_fires_once_with_incident(dw, tmp_path):
    from transmogrifai_tpu.utils.events import events
    wd = dw.DispatchWatchdog()
    wd.configure(enabled=True, incident_dir=str(tmp_path),
                 stall_timeout_s=0.15, poll_interval_s=0.03)
    eid = dw.dispatch_ledger.register("sweep.pending",
                                      family="OpGBTClassifier_1",
                                      unitKind="tree", units=2)
    try:
        with wd.guard("sweep.settle", site="sweep.settle", families=2):
            time.sleep(0.6)  # several polls past the deadline
    finally:
        dw.dispatch_ledger.complete(eid)
    assert wd.stalls == 1, "expired wait must fire EXACTLY one autopsy"
    assert wd.stalls_by_site == {"sweep.settle": 1}
    inc_dir = tmp_path / "incidents"
    files = sorted(os.listdir(inc_dir))
    assert len(files) == 1
    doc = json.load(open(inc_dir / files[0]))
    autopsy = doc["extra"]["autopsy"]
    assert autopsy["threadStacks"], "autopsy must carry thread stacks"
    assert any(s["threadName"] == "MainThread"
               for s in autopsy["threadStacks"])
    pend = autopsy["pendingDispatches"]
    assert any(p.get("family") == "OpGBTClassifier_1" for p in pend)
    assert "bytesInUse" in autopsy["hbmCensus"]
    assert autopsy["wait"]["site"] == "sweep.settle"
    assert autopsy["wait"]["elapsedSeconds"] >= 0.15
    stall_events = [e for e in events.tail()
                    if e["kind"] == "device.stall"
                    and e.get("site") == "sweep.settle"]
    assert stall_events and stall_events[-1]["pendingDispatches"] >= 1


def test_guard_no_false_fire_on_slow_but_progressing(dw):
    """Two sequential waits, each under the deadline, totaling over it:
    the deadline is per-wait (progress re-arms), not cumulative."""
    wd = dw.DispatchWatchdog()
    wd.configure(enabled=True, stall_timeout_s=0.3, poll_interval_s=0.03)
    for _ in range(3):
        with wd.guard("sweep.settle"):
            time.sleep(0.15)
    assert wd.stalls == 0 and wd.guards == 3


def test_guard_disarms_on_exception_oom_ladder_interplay(dw):
    """An OOM-rung retry exits the guarded block via the exception — the
    old deadline MUST disarm with it (the fold-loop retry arms its own),
    never fire for a wait that no longer exists."""
    from transmogrifai_tpu.utils.faults import XlaRuntimeError
    wd = dw.DispatchWatchdog()
    wd.configure(enabled=True, stall_timeout_s=0.2, poll_interval_s=0.03)
    with pytest.raises(XlaRuntimeError):
        with wd.guard("sweep.settle", site="sweep.settle"):
            raise XlaRuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 1073741824 bytes")
    assert wd.active_waits() == []
    time.sleep(0.4)  # well past the (disarmed) deadline
    assert wd.stalls == 0


# -- fault-injected hangs end-to-end ------------------------------------------

def test_slow_collective_timeout_no_autopsy_when_disabled(dw, tmp_path):
    """TRANSMOGRIFAI_DEVICEWATCH=0 must restore the pre-observatory
    collective timeout byte for byte: the error still raises, but no
    autopsy fires, no counters move, nothing is written."""
    from transmogrifai_tpu.parallel.collectives import (
        CollectiveTimeoutError,
    )
    from transmogrifai_tpu.parallel.distributed import barrier
    from transmogrifai_tpu.utils.faults import fault_plan
    dw.configure(enabled=False, incident_dir=str(tmp_path))
    stalls0 = dw.watchdog.stalls
    with fault_plan("slow@collective:2"):
        with pytest.raises(CollectiveTimeoutError, match="barrier"):
            barrier("dw-off-test", timeout_s=0.3)
    assert dw.watchdog.stalls == stalls0
    assert not os.path.exists(tmp_path / "incidents")


def test_slow_collective_timeout_fires_autopsy(dw, tmp_path):
    from transmogrifai_tpu.parallel.collectives import (
        CollectiveTimeoutError,
    )
    from transmogrifai_tpu.parallel.distributed import barrier
    from transmogrifai_tpu.utils.faults import fault_plan
    dw.configure(incident_dir=str(tmp_path))
    stalls0 = dw.watchdog.stalls
    with fault_plan("slow@collective:2"):
        with pytest.raises(CollectiveTimeoutError, match="barrier"):
            barrier("dw-test", timeout_s=0.3)
    assert dw.watchdog.stalls == stalls0 + 1
    files = sorted(os.listdir(tmp_path / "incidents"))
    assert files, "the collective timeout must freeze an incident"
    doc = json.load(open(tmp_path / "incidents" / files[-1]))
    assert "collective.timeout" in doc["reason"]
    autopsy = doc["extra"]["autopsy"]
    # the abandoned worker thread is frozen mid-collective in the stacks
    names = [s["threadName"] for s in autopsy["threadStacks"]]
    assert any(n.startswith("collective[") for n in names), names
    # the ledger still held the in-flight collective when it expired
    assert any(p["site"] == "collective"
               for p in autopsy["pendingDispatches"])
    assert "bytesInUse" in autopsy["hbmCensus"]


def _tiny_stacked_workflow(seed=3, families=2):
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import (
        OpLinearSVC, OpLogisticRegression,
    )
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(seed)
    x = rng.normal(size=N)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-1.5 * x))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x": (ft.Real, x.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x"]])
    cands = [(OpLogisticRegression(max_iter=10),
              [{"reg_param": r} for r in (0.01, 0.1)])]
    if families > 1:
        cands.append((OpLinearSVC(max_iter=10), [{"reg_param": 0.01}]))
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=2, models_and_parameters=cands)
    pred = feats["y"].transform_with(sel, features)
    return (Workflow().set_input_frame(frame)
            .set_result_features(pred, features))


def test_stalled_settle_autopsies_and_keeps_one_sync(dw, tmp_path,
                                                     monkeypatch):
    """The acceptance e2e: a stalled one-sync settle produces a
    committed-format incident (thread stacks + family-labeled pending
    dispatches + HBM census) while the sweep, once the stall clears,
    still completes with sweepHostSyncs == 1 under the armed watchdog
    and leaves the dispatch ledger empty."""
    import jax

    from transmogrifai_tpu.utils.profiling import profiler, sweep_counters
    dw.configure(incident_dir=str(tmp_path), stall_timeout_s=0.15,
                 poll_interval_s=0.03)
    stalls0 = dw.watchdog.stalls
    registered0 = dw.dispatch_ledger.registered
    profiler.reset()

    real = jax.block_until_ready
    state = {"stalled": False}

    def stall_settle_once(x):
        import sys as _sys
        if not state["stalled"] \
                and _sys._getframe(1).f_code.co_name == "_stamp_device":
            # the settle's one barrier: walked chunk by chunk
            state["stalled"] = True
            time.sleep(0.5)  # past the 0.15s stall deadline
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", stall_settle_once)
    _tiny_stacked_workflow().train()
    monkeypatch.setattr(jax, "block_until_ready", real)

    assert state["stalled"], "the settle barrier was never reached"
    assert dw.watchdog.stalls_by_site.get("sweep.settle", 0) >= 1
    assert dw.watchdog.stalls > stalls0
    # the armed watchdog added observation, not syncs
    assert sweep_counters.run_to_json()["sweepHostSyncs"] == 1
    # ledger: every pending family registered and completed
    assert dw.dispatch_ledger.registered > registered0
    assert len(dw.dispatch_ledger) == 0
    files = sorted(os.listdir(tmp_path / "incidents"))
    assert files
    doc = json.load(open(tmp_path / "incidents" / files[-1]))
    autopsy = doc["extra"]["autopsy"]
    assert autopsy["threadStacks"]
    fams = {p.get("family") for p in autopsy["pendingDispatches"]
            if p["site"] == "sweep.pending"}
    assert any(f and "OpL" in f for f in fams), fams
    assert "bytesInUse" in autopsy["hbmCensus"]
    # the spilled incident carries the recent event tail too
    assert any(e["kind"] == "device.stall" for e in doc["events"])


# -- compile telemetry --------------------------------------------------------

def test_compile_telemetry_attribution_and_slow_event(monkeypatch):
    from transmogrifai_tpu.utils.devicewatch import CompileTelemetry
    from transmogrifai_tpu.utils.events import events
    from transmogrifai_tpu.utils.tracing import recorder
    monkeypatch.setenv("TRANSMOGRIFAI_SLOW_COMPILE_S", "0.5")
    tele = CompileTelemetry()
    tele._listening = True   # a throwaway: never hears the real events
    ev = CompileTelemetry.COMPILE_EVENT
    with tele.building("sweep.family:OpLR_0", family="OpLR_0"):
        assert tele.in_progress == 1
        tele._on_event(ev, 0.2)
        tele._on_event(ev, 0.9, fun_name="train")
        tele._on_event("/jax/other/event", 99.0)  # ignored
        with tele.building("bin_data"):   # innermost site, family kept
            tele._on_event(ev, 0.3)
    tele._on_event(ev, 0.1)
    assert tele.in_progress == 0
    doc = tele.to_json()
    assert doc["programs"] == 4 and doc["cacheLoads"] == 0
    assert doc["bySite"]["sweep.family:OpLR_0"]["programs"] == 2
    assert doc["bySite"]["bin_data"]["programs"] == 1
    assert doc["bySite"]["unattributed"]["programs"] == 1
    assert tele.family_compiles() == {"OpLR_0": 3}
    assert doc["maxWallSeconds"] == pytest.approx(0.9)
    assert doc["slowCompiles"] == 1
    slow = [e for e in events.tail() if e["kind"] == "compile.slow"]
    assert slow and slow[-1]["site"] == "sweep.family:OpLR_0"
    # the site rides in the span's NAME (consumers that keep names only)
    spans = [s for s in recorder.spans
             if s.name.startswith("compile.program:")]
    assert len(spans) >= 4
    assert spans[-1].name == "compile.program:unattributed"
    assert spans[-1].wall_s == pytest.approx(0.1, abs=0.01)
    assert any(s.name == "compile.program:sweep.family:OpLR_0"
               and s.attrs.get("program") == "train" for s in spans)


def test_compile_telemetry_tells_cache_loads_from_compiles():
    """On this JAX a persistent-cache hit fires the backend-compile
    duration event too, preceded on the same thread by
    ``/jax/compilation_cache/cache_hits``: a duration event that follows
    a hit is a LOAD, kept apart from compiles in every count and named
    ``compile.cache_load:<site>``."""
    from transmogrifai_tpu.utils.devicewatch import CompileTelemetry
    from transmogrifai_tpu.utils.tracing import recorder
    tele = CompileTelemetry()
    tele._listening = True   # a throwaway: never hears the real events
    ev, hit = CompileTelemetry.COMPILE_EVENT, CompileTelemetry.CACHE_HIT_EVENT
    n0 = len([s for s in recorder.spans
              if s.name == "compile.cache_load:predict:M"])
    with tele.building("predict:M", family="fam"):
        tele._on_cache_event(hit)
        tele._on_event(ev, 0.05)      # the load the hit announced
        tele._on_event(ev, 0.40)      # a real compile
        tele._on_cache_event("/jax/compilation_cache/cache_misses")
        tele._on_event(ev, 0.20)      # a miss is a compile
    doc = tele.to_json()
    assert doc["programs"] == 2 and doc["cacheLoads"] == 1
    assert doc["wallSeconds"] == pytest.approx(0.6)
    site = doc["bySite"]["predict:M"]
    assert site == {"programs": 2, "wallSeconds": pytest.approx(0.6),
                    "cacheLoads": 1, "loadSeconds": pytest.approx(0.05)}
    assert tele.family_compiles() == {"fam": 2}   # loads are no compiles
    assert [r["cacheLoad"] for r in tele.records] == [True, False, False]
    loads = [s for s in recorder.spans
             if s.name == "compile.cache_load:predict:M"]
    assert len(loads) == n0 + 1


def test_real_cache_hit_event_order_and_site(tmp_path):
    """The order of the two events on the installed JAX, established with
    a real persistent cache in ``tmp_path``: first run compiles (and
    writes), second run (in-memory caches cleared) loads — classified
    apart, each under its ``building`` site."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    compile_telemetry.ensure_listener()
    c = float(_time.time())   # run-unique HLO

    def unique_program(a):
        return a * c + 1.0

    def site(name):
        return dict(compile_telemetry.to_json()["bySite"].get(
            name, {"programs": 0, "cacheLoads": 0}))
    with _persistent_cache_in(tmp_path):
        x = jnp.ones(3)   # made outside: its own small programs compile here
        before_c, before_l = site("test.compile"), site("test.load")
        with compile_telemetry.building("test.compile"):
            jax.jit(unique_program)(x).block_until_ready()
        if site("test.compile")["programs"] == before_c["programs"]:
            pytest.skip("jax.monitoring backend-compile events unavailable")
        if not any(tmp_path.iterdir()):
            pytest.skip("this backend wrote no persistent cache entry")
        jax.clear_caches()
        with compile_telemetry.building("test.load"):
            jax.jit(unique_program)(x).block_until_ready()
    after_c, after_l = site("test.compile"), site("test.load")
    assert after_c["programs"] == before_c["programs"] + 1
    assert after_c["cacheLoads"] == before_c["cacheLoads"]
    assert after_l["cacheLoads"] == before_l["cacheLoads"] + 1
    assert after_l["programs"] == before_l["programs"]
    from transmogrifai_tpu.utils.tracing import recorder
    names = {s.name for s in recorder.spans}
    assert "compile.program:test.compile" in names
    assert "compile.cache_load:test.load" in names


def test_second_workflow_loads_its_fe_programs(tmp_path):
    """Every ``Workflow`` draws new stage uids. The training executor's
    fused feature-engineering programs take and return positional leaves,
    so the uids stay out of the module and of the persistent cache's key:
    a second train of the same shapes loads the programs the first
    compiled."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    from transmogrifai_tpu.workflow import Workflow
    compile_telemetry.ensure_listener()
    rng = np.random.default_rng(3)
    x = rng.normal(size=(400, 3))
    frame = fr.HostFrame.from_dict({
        "a": (ft.Real, x[:, 0]), "b": (ft.Real, x[:, 1]),
        "c": (ft.Real, x[:, 2]),
        "label": (ft.RealNN, (x[:, 0] + x[:, 1] > 0).astype(np.float64))})

    def train():
        feats = FeatureBuilder.from_frame(frame, response="label")
        label = feats.pop("label")
        vec = transmogrify(list(feats.values()))
        checked = label.transform_with(SanityChecker(), vec)
        Workflow().set_input_frame(frame).set_result_features(
            checked).train()

    def fe_sites():
        by = compile_telemetry.to_json()["bySite"]
        return tuple(sum(by.get(s, {}).get(k, 0)
                         for s in ("fe.fused", "fe.layer"))
                     for k in ("programs", "cacheLoads"))
    with _persistent_cache_in(tmp_path):
        p0, l0 = fe_sites()
        train()
        p1, l1 = fe_sites()
        if p1 == p0:
            pytest.skip("jax.monitoring backend-compile events unavailable")
        if not any(tmp_path.iterdir()):
            pytest.skip("this backend wrote no persistent cache entry")
        train()
        p2, l2 = fe_sites()
    # the second train compiled no FE program: it loaded what it ran
    assert p2 == p1 and l2 > l1 == l0


@pytest.mark.parametrize("winner", ["gbt_classifier", "rf_classifier",
                                    "gbt_regressor"])
def test_train_on_fresh_table_loads_tree_winner_programs(tmp_path, winner):
    """A tree winner's base score (a GBT classifier's is the log-odds of
    its training split's label mean, a regressor's the mean) is a value of
    the table. It reaches the winner's programs as an argument, so a train
    on ANOTHER table of the same shapes compiles none of them anew: the
    fused feature-engineering programs, the one in which the winner scores
    the frame among them, and the selector's two ``predict_arrays``
    programs all load from the persistent cache."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter,
        RegressionModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    from transmogrifai_tpu.workflow import Workflow
    compile_telemetry.ensure_listener()
    sites = ("fe.fused", "fe.layer", "predict:TreeEnsembleModel")
    # grid values no other test uses: the first train compiles its programs
    grid = [{"max_depth": 2, "num_rounds": 4}]
    selector, estimator = {
        "gbt_classifier": (BinaryClassificationModelSelector,
                           trees.OpGBTClassifier),
        "rf_classifier": (BinaryClassificationModelSelector,
                          trees.OpRandomForestClassifier),
        "gbt_regressor": (RegressionModelSelector, trees.OpGBTRegressor),
    }[winner]

    def train(seed):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(500, 4))
        y = X[:, 0] * X[:, 1] + 0.3 * X[:, 2] + 0.2 * seed
        if winner.endswith("classifier"):
            y = (y > 0).astype(np.float64)
        cols = {f"x{i}": (ft.Real, X[:, i]) for i in range(4)}
        cols["label"] = (ft.RealNN, y)
        frame = fr.HostFrame.from_dict(cols)
        feats = FeatureBuilder.from_frame(frame, response="label")
        label = feats.pop("label")
        checked = label.transform_with(
            SanityChecker(), transmogrify(list(feats.values())))
        sel = selector.with_cross_validation(
            n_folds=2, seed=1, models_and_parameters=[(estimator(), grid)],
            splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
        model = Workflow().set_input_frame(frame).set_result_features(
            label.transform_with(sel, checked)).train()
        return model.selector_summary()

    def counts():
        by = compile_telemetry.to_json()["bySite"]
        return {s: (by.get(s, {}).get("programs", 0),
                    by.get(s, {}).get("cacheLoads", 0)) for s in sites}
    with _persistent_cache_in(tmp_path):
        c0 = counts()
        first = train(1)
        c1 = counts()
        if c1["fe.fused"][0] == c0["fe.fused"][0]:
            pytest.skip("jax.monitoring backend-compile events unavailable")
        if not any(tmp_path.iterdir()):
            pytest.skip("this backend wrote no persistent cache entry")
        second = train(2)
        c2 = counts()
    # another table: another split, another label mean, another model
    assert first.train_evaluation != second.train_evaluation
    # ... and the same programs: none compiled, the winner's were loaded
    assert {s: c2[s][0] - c1[s][0] for s in sites} == dict.fromkeys(sites, 0)
    for s in ("fe.fused", "predict:TreeEnsembleModel"):
        assert c2[s][1] > c1[s][1], s


def test_compile_telemetry_real_sweep_series():
    """Real-compile integration: backend compiles observed during a
    stacked sweep land in the telemetry, attributed to sweep sites, and
    render as transmogrifai_compile_* series."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    from transmogrifai_tpu.utils.prometheus import build_registry
    compile_telemetry.ensure_listener()
    before = compile_telemetry.programs
    c = float(_time.time())  # run-unique HLO: never persistent-cache-hit
    jax.jit(lambda a: a * c)(jnp.ones(3)).block_until_ready()
    if compile_telemetry.programs == before:
        pytest.skip("jax.monitoring backend-compile events unavailable")
    before = compile_telemetry.programs
    _tiny_stacked_workflow(seed=11, families=1).train()
    assert compile_telemetry.programs > before
    assert any(s.startswith(("sweep.", "selector."))
               for s in compile_telemetry.by_site)
    out = build_registry(include_app=False).render()
    assert "transmogrifai_compile_programs_total{site=" in out
    assert "transmogrifai_compile_wall_seconds_total{site=" in out


def test_train_with_tree_winner_compiles_nothing_unattributed():
    """Every program a ``Workflow.train()`` builds — feature engineering,
    SanityChecker, the sweep's operands and families, binning, the winner's
    refit, its predict program, the evaluators — is built inside a
    ``building(site)`` block: no compile or cache-load span of the train
    is named ``...:unattributed``, the winner's predict program is named
    (``jit(predict_arrays)``, not a lambda) and attributed to its model
    class, and the per-family compile counts come from the same
    listener."""
    import numpy as np

    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.models.trees import OpGBTClassifier
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    from transmogrifai_tpu.utils.profiling import profiler, sweep_counters
    from transmogrifai_tpu.utils.tracing import recorder
    from transmogrifai_tpu.workflow import Workflow
    rng = np.random.default_rng(0)
    n = 600
    X = rng.normal(size=(n, 4))
    # an interaction no linear model finds: the tree family wins
    y = ((X[:, 0] * X[:, 1] + 0.3 * X[:, 2]) > 0).astype(np.float64)
    cols = {f"x{i}": (ft.Real, X[:, i]) for i in range(4)}
    cols["label"] = (ft.RealNN, y)
    frame = fr.HostFrame.from_dict(cols)
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    checked = label.transform_with(
        SanityChecker(), transmogrify(list(feats.values())))
    # grid values no other test uses: these programs compile here
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=1, models_and_parameters=[
            (OpLogisticRegression(max_iter=9), [{"reg_param": 0.0123}]),
            (OpGBTClassifier(), [{"max_depth": 2, "num_rounds": 3},
                                 {"max_depth": 3, "num_rounds": 3}]),
        ], splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    pred = label.transform_with(sel, checked)
    compile_telemetry.ensure_listener()
    profiler.reset()
    model = Workflow().set_input_frame(frame).set_result_features(
        pred).train()
    assert model.selector_summary().best_model_type == "OpGBTClassifier"
    built = [s for s in recorder.spans if s.name.startswith("compile.")]
    if not built:
        pytest.skip("jax.monitoring backend-compile events unavailable")
    assert [s.name for s in built if s.name.endswith(":unattributed")] == []
    assert all(s.name.partition(":")[0] in ("compile.program",
                                            "compile.cache_load")
               for s in built)
    predict = [s for s in built
               if s.attrs.get("program") == "jit(predict_arrays)"]
    assert predict and all(
        s.attrs["site"] == "predict:TreeEnsembleModel" for s in predict)
    assert not any("lambda" in str(s.attrs.get("program")) for s in built)
    sites = {s.attrs["site"] for s in built}
    assert "fe.fused" in sites
    assert any(site.startswith("sweep.tree:OpGBTClassifier")
               for site in sites)
    # SweepCounters reads the same listener: what compiled for the tree
    # family (sweep + refit) is what its sites' compile spans count
    fam = "OpGBTClassifier_1"
    by_site = compile_telemetry.to_json()["bySite"]
    assert sweep_counters.to_json()[fam]["compiles"] <= sum(
        v["programs"] for v in by_site.values())
    n_family_spans = sum(
        1 for s in built if s.name.startswith("compile.program:")
        and s.attrs["site"].endswith(fam))
    assert sweep_counters.to_json()[fam]["compiles"] >= n_family_spans


def test_analyze_program_cost_report():
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.utils.devicewatch import analyze_program
    f = jax.jit(lambda a: a @ a.T)
    cost = analyze_program(f, jnp.ones((8, 8)))
    assert cost.get("hloTextBytes", 0) > 0
    if "flops" in cost:
        assert cost["flops"] > 0
    # a non-jitted callable reports nothing, never raises
    assert analyze_program(lambda a: a, 1) == {}


def test_serving_warmup_records_program_costs():
    from transmogrifai_tpu.serving.compiled import CompiledScorer
    from transmogrifai_tpu.utils.devicewatch import compile_telemetry
    model = _tiny_stacked_workflow(seed=7, families=1).train()
    scorer = CompiledScorer(model, max_batch=16, min_bucket=8)
    scorer.warmup({"x": 0.5})
    costs = {k: v for k, v in compile_telemetry.program_costs.items()
             if k.startswith("serving.layer")}
    assert costs, "warmup must cost-analyze the fused layer programs"
    assert any(v.get("hloTextBytes", 0) > 0 for v in costs.values())
    assert scorer._analyze_cold is False  # hot path never re-analyzes


# -- HBM timeline -------------------------------------------------------------

def test_hbm_timeline_counter_track_and_reset(tmp_path):
    from transmogrifai_tpu.utils import devicewatch
    from transmogrifai_tpu.utils.profiling import profiler
    m = profiler.reset("hbm_timeline_test")
    devicewatch.sample_hbm(t=100.0)
    devicewatch.sample_hbm(t=101.0)
    assert len(devicewatch.hbm_timeline()) == 2
    profiler.finalize()
    out = str(tmp_path / "trace.json")
    summary = m.export_chrome_trace(out)
    assert summary["hbmSamples"] == 2
    doc = json.load(open(out))
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(counters) == 2
    assert counters[0]["name"] == "hbm_bytes_in_use"
    assert "bytesInUse" in counters[0]["args"]
    # a new run's trace starts with a clean timeline
    profiler.reset("hbm_timeline_test2")
    assert devicewatch.hbm_timeline() == []


def test_resource_watchdog_tick_samples_hbm():
    from transmogrifai_tpu.utils import devicewatch
    from transmogrifai_tpu.utils.profiling import profiler
    from transmogrifai_tpu.utils.resources import ResourceWatchdog
    profiler.reset("tick_sample")
    state = ResourceWatchdog().tick()
    assert "deviceHbmBytes" in state
    assert len(devicewatch.hbm_timeline()) >= 1


# -- cli autopsy --------------------------------------------------------------

def _write_incident(dw, tmp_path) -> str:
    wd = dw.DispatchWatchdog()
    wd.configure(enabled=True, incident_dir=str(tmp_path))
    eid = dw.dispatch_ledger.register("sweep.pending", family="OpGBT",
                                      unitKind="tree", units=2)
    try:
        doc = wd.stall_autopsy(
            "device.stall:sweep.settle", site="sweep.settle",
            wait={"name": "sweep.settle", "site": "sweep.settle",
                  "timeoutS": 120.0, "t0": time.time() - 130.0,
                  "thread": "MainThread"})
    finally:
        dw.dispatch_ledger.complete(eid)
    return doc["incidentPath"]


def test_cli_autopsy_renders_incident(dw, tmp_path, capsys):
    from transmogrifai_tpu.cli import main as cli_main
    path = _write_incident(dw, tmp_path)
    assert cli_main(["autopsy", path]) == 0
    out = capsys.readouterr().out
    assert "sweep.settle" in out
    assert "thread stacks" in out
    assert "pending dispatches" in out
    assert "MainThread" in out
    assert "OpGBT" in out
    # directory form resolves to the newest incident
    assert cli_main(["autopsy", str(tmp_path)]) == 0
    assert "sweep.settle" in capsys.readouterr().out


def test_cli_autopsy_reads_events_jsonl(tmp_path, capsys):
    from transmogrifai_tpu.cli import main as cli_main
    spill = tmp_path / "events.jsonl"
    with open(spill, "w") as fh:
        fh.write(json.dumps({"ts": 100.0, "kind": "serve.batch",
                             "rows": 8}) + "\n")
        fh.write(json.dumps({"ts": 101.0, "kind": "device.stall",
                             "site": "serving.dispatch",
                             "elapsedSeconds": 61.2,
                             "pendingDispatches": 1,
                             "hbmBytesInUse": 1024}) + "\n")
    assert cli_main(["autopsy", str(spill)]) == 0
    out = capsys.readouterr().out
    assert "device.stall" in out
    assert "serving.dispatch" in out
    assert "serve.batch" in out


def test_cli_autopsy_unreadable_exits_2(tmp_path, capsys):
    from transmogrifai_tpu.cli import main as cli_main
    assert cli_main(["autopsy", str(tmp_path / "missing.json")]) == 2
    assert cli_main(["autopsy", str(tmp_path)]) == 2  # no incidents


# -- prometheus + lint wiring -------------------------------------------------

def test_registry_carries_device_and_compile_series():
    from transmogrifai_tpu.utils.prometheus import build_registry
    reg = build_registry(include_app=False)
    names = reg.names()
    for expect in ("transmogrifai_device_stalls_total",
                   "transmogrifai_device_guarded_waits_total",
                   "transmogrifai_device_pending_dispatches",
                   "transmogrifai_device_hbm_bytes_in_use",
                   "transmogrifai_device_watch_enabled",
                   "transmogrifai_compile_programs_total",
                   "transmogrifai_compile_slow_total",
                   "transmogrifai_compile_in_progress"):
        assert expect in names, expect
    out = reg.render()
    assert "# collect failed" not in out


# -- artifact schemas ---------------------------------------------------------

def _good_overhead_doc():
    return {
        "metric": "devicewatch_overhead", "platform": "cpu",
        "requests": 24576, "base_rps": 30000.0, "watched_rps": 29800.0,
        "overhead_pct": 0.7, "guards_armed": 120, "false_stalls": 0,
        "sweep_one_sync": {"host_syncs": 1, "watchdog_armed": True,
                           "families": 2, "stalls": 0},
    }


def test_devicewatch_overhead_schema_accepts_and_rejects():
    checker = _load_script("scripts/check_artifacts.py")
    assert checker.validate_artifact(_good_overhead_doc()) == []
    over = dict(_good_overhead_doc(), overhead_pct=3.1)
    assert any("exceeds" in e for e in checker.validate_artifact(over))
    false = dict(_good_overhead_doc(), false_stalls=2)
    assert any("false stall" in e for e in checker.validate_artifact(false))
    synced = dict(_good_overhead_doc(),
                  sweep_one_sync={"host_syncs": 3, "watchdog_armed": True})
    assert any("one-sync" in e for e in checker.validate_artifact(synced))
    unarmed = dict(_good_overhead_doc(), guards_armed=0)
    assert any("guards_armed" in e
               for e in checker.validate_artifact(unarmed))


def test_devicewatch_overhead_artifact_committed_and_valid():
    checker = _load_script("scripts/check_artifacts.py")
    path = os.path.join(REPO, "benchmarks", "DEVICEWATCH_OVERHEAD.json")
    assert os.path.exists(path), "benchmarks/DEVICEWATCH_OVERHEAD.json " \
                                 "missing"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["overhead_pct"] <= checker.MAX_DEVICEWATCH_OVERHEAD_PCT
    assert art["false_stalls"] == 0
    assert art["sweep_one_sync"]["host_syncs"] == 1
