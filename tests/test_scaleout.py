"""Sharded fleet-of-fleets scale-out: the consistent-hash router
(hash/spillover/markdown), the replica supervisor (heartbeats, crash
respawn, zero-drop kill, scale up/down), the coordinated rolling
hot-swap (halt + roll back on a gate rejection), the autoscaler's
signal transitions, the shared program-artifact layer, the durable
ACTIVE alias (incl. concurrent multi-process access), the HTTP
keep-alive/body-bound/admin satellites, and the chaos fault sites
``scaleout.route|heartbeat|roll``.

Multi-process tests run against the jax-free ``stub_worker`` (the wire
protocol's conformance stub) so spawn/kill/respawn semantics stay
cheap; one end-to-end test drives REAL replica workers over a trained
model (router scoring parity, artifact mapping, rolling swap, and a
killed replica respawning onto the durably promoted version)."""

import http.client
import json
import multiprocessing
import os
import signal
import threading
import time
import types

import numpy as np
import pytest

from transmogrifai_tpu.scaleout import wire
from transmogrifai_tpu.scaleout.autoscaler import Autoscaler
from transmogrifai_tpu.scaleout.router import (
    ConsistentHashRing, Router, RouterMetrics,
)
from transmogrifai_tpu.scaleout.supervisor import (
    ReplicaSupervisor, RollingSwapError,
)

STUB = "transmogrifai_tpu.scaleout.stub_worker"


# -- consistent-hash ring -----------------------------------------------------

def test_ring_order_deterministic_and_complete():
    ring = ConsistentHashRing([f"r{i}" for i in range(5)])
    order = ring.order("some_model")
    assert sorted(order) == [f"r{i}" for i in range(5)]
    assert order == ring.order("some_model")
    assert ring.order("another_model") != []


def test_ring_membership_change_moves_only_the_affected_arc():
    """The consistent-hash property: removing one member must not
    reshuffle every other key's primary."""
    members = [f"r{i}" for i in range(6)]
    ring = ConsistentHashRing(members)
    keys = [f"model_{i}" for i in range(200)]
    before = {k: ring.order(k)[0] for k in keys}
    ring.remove("r3")
    moved = 0
    for k in keys:
        primary = ring.order(k)[0]
        if before[k] == "r3":
            assert primary != "r3"
        elif primary != before[k]:
            moved += 1
    # keys not owned by the removed member overwhelmingly keep their
    # primary (a modulo hash would move ~5/6 of them)
    assert moved <= len(keys) * 0.1


def test_ring_empty_and_single():
    ring = ConsistentHashRing()
    assert ring.order("x") == []
    ring.add("only")
    assert ring.order("x") == ["only"]


# -- in-process stub replicas (MetricsServer-backed) --------------------------

def _stub_replica(score_fn):
    from transmogrifai_tpu.serving.http import MetricsServer
    return MetricsServer(render_fn=lambda: "", health_fn=lambda: {},
                         score_fn=score_fn, port=0).start()


def _router_with(replicas, **kwargs):
    router = Router(port=0, **kwargs).start()
    for rid, srv in replicas.items():
        router.set_replica(rid, srv.port)
    return router


def test_router_proxies_and_stamps_served_by():
    srv = _stub_replica(lambda mid, row, tid: {"model": mid,
                                               "echo": row})
    router = _router_with({"rA": srv})
    try:
        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=10)
        conn.request("POST", "/score/m1", json.dumps({"x": 1}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert resp.getheader("X-Served-By") == "rA"
        assert body["model"] == "m1" and body["echo"] == {"x": 1}
        assert router.metrics.completed == 1
        conn.close()
    finally:
        router.stop()
        srv.stop()


def test_router_spillover_on_backpressure():
    """A 503-answering primary spills the request to the next ring
    replica; the spillover is counted."""
    from transmogrifai_tpu.serving.batcher import BackpressureError

    def full(mid, row, tid):
        raise BackpressureError("full", retry_after_s=0.05)

    busy = _stub_replica(full)
    calm = _stub_replica(lambda mid, row, tid: {"ok": True})
    router = Router(port=0).start()
    try:
        router.set_replica("busy", busy.port)
        router.set_replica("calm", calm.port)
        # find a model id whose PRIMARY is the busy replica, so the
        # request must spill to reach the calm one
        mid = next(f"m{i}" for i in range(64)
                   if router.ring.order(f"m{i}")[0] == "busy")
        status, headers, payload, rid = router.dispatch(
            mid, json.dumps({"x": 1}).encode())
        assert status == 200 and rid == "calm"
        assert router.metrics.spillovers >= 1
    finally:
        router.stop()
        busy.stop()
        calm.stop()


def test_router_all_replicas_backpressured_returns_503():
    from transmogrifai_tpu.serving.batcher import BackpressureError

    def full(mid, row, tid):
        raise BackpressureError("full", retry_after_s=0.02)

    a, b = _stub_replica(full), _stub_replica(full)
    router = _router_with({"a": a, "b": b})
    try:
        status, headers, payload, rid = router.dispatch(
            "m", json.dumps({}).encode())
        assert status == 503
        assert "Retry-After" in headers
    finally:
        router.stop()
        a.stop()
        b.stop()


def test_router_markdown_on_dead_replica_and_recovery():
    """A connection-refused replica is marked down and the request is
    served by the successor (retried, not dropped); mark_up restores
    routing."""
    dead = _stub_replica(lambda mid, row, tid: {"who": "dead"})
    live = _stub_replica(lambda mid, row, tid: {"who": "live"})
    router = Router(port=0).start()
    try:
        router.set_replica("dead", dead.port)
        router.set_replica("live", live.port)
        mid = next(f"m{i}" for i in range(64)
                   if router.ring.order(f"m{i}")[0] == "dead")
        dead.stop()     # connection refused from now on
        status, _, payload, rid = router.dispatch(
            mid, json.dumps({}).encode())
        assert status == 200 and rid == "live"
        assert router.metrics.retries >= 1
        assert router.metrics.markdowns == 1
        assert router.replicas()["dead"]["state"] == "down"
        # marked-down replicas are skipped without further probing
        status, _, _, rid = router.dispatch(mid,
                                            json.dumps({}).encode())
        assert status == 200 and rid == "live"
        assert router.metrics.markdowns == 1
        router.mark_up("dead")
        assert router.replicas()["dead"]["state"] == "up"
    finally:
        router.stop()
        live.stop()


def test_router_no_replica_503():
    router = Router(port=0).start()
    try:
        status, headers, payload, rid = router.dispatch("m", b"{}")
        assert status == 503 and rid is None
        assert router.metrics.no_replica == 1
    finally:
        router.stop()


def test_router_metrics_bind_to_slo_engine():
    """RouterMetrics speaks the slice of ServingMetrics the SLO engine
    reads, so availability/latency objectives evaluate over
    router-observed traffic (the autoscaler's burn signal)."""
    from transmogrifai_tpu.utils.slo import SLOEngine
    rm = RouterMetrics()
    router = types.SimpleNamespace(metrics=rm)
    engine = SLOEngine.for_serving(
        [{"name": "avail", "kind": "availability", "target": 0.99},
         {"name": "lat", "kind": "latency", "target": 0.9,
          "thresholdMs": 25}],
        lambda: [router.metrics])
    for _ in range(100):
        rm.record("r0", 200, 0.004)
    engine.observe(t=1000.0)
    for _ in range(50):
        rm.record("r0", 500, 0.004)
    engine.observe(t=1060.0)
    status = engine.status(t=1061.0)
    assert status["objectives"]["avail"]["firing"]
    assert engine.page_firing(t=1061.0)


# -- wire protocol ------------------------------------------------------------

def test_heartbeat_roundtrip_and_freshness(tmp_path):
    state = str(tmp_path)
    path = wire.write_heartbeat(state, {"replicaId": "r9", "port": 123,
                                        "state": "ready"})
    assert os.path.exists(path)
    hb = wire.read_heartbeats(state)["r9"]
    assert hb["port"] == 123
    assert wire.is_fresh(hb, ttl_s=5.0)
    assert not wire.is_fresh(hb, ttl_s=5.0, now=time.time() + 10)
    wire.clear_heartbeat(state, "r9")
    assert wire.read_heartbeats(state) == {}


def test_heartbeat_reader_skips_corrupt_files(tmp_path):
    state = str(tmp_path)
    wire.write_heartbeat(state, {"replicaId": "ok", "port": 1})
    bad = os.path.join(state, wire.HEARTBEAT_DIRNAME, "bad.json")
    with open(bad, "w") as fh:
        fh.write("{torn")
    assert list(wire.read_heartbeats(state)) == ["ok"]


# -- MetricsServer satellites: keep-alive, body bound, admin ------------------

def test_http_keep_alive_persists_connection():
    srv = _stub_replica(lambda mid, row, tid: {"n": 1})
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        for _ in range(3):   # same socket, three requests
            conn.request("POST", "/score/m", "{}",
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
            assert resp.version == 11
            assert (resp.getheader("Connection") or "").lower() \
                != "close"
        conn.close()
    finally:
        srv.stop()


def test_http_body_size_bound_413():
    from transmogrifai_tpu.serving.http import MetricsServer
    srv = MetricsServer(render_fn=lambda: "", health_fn=lambda: {},
                        score_fn=lambda m, r, t: {},
                        max_body_bytes=64, port=0).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=10)
        conn.request("POST", "/score", "x" * 128,
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 413
        conn.close()
    finally:
        srv.stop()


def test_http_admin_routes():
    from transmogrifai_tpu.serving.fleet import ShadowParityError
    from transmogrifai_tpu.serving.http import MetricsServer

    def control(action, payload):
        if action == "boom":
            raise ShadowParityError("gate", max_abs_diff=1.0)
        if action == "bad":
            raise ValueError("nope")
        return {"ok": True, "action": action, "got": payload}

    srv = MetricsServer(render_fn=lambda: "", health_fn=lambda: {},
                        control_fn=control, port=0).start()
    try:
        doc = wire.admin_call(srv.port, "status", {"a": 1})
        assert doc == {"ok": True, "action": "status", "got": {"a": 1}}
        with pytest.raises(wire.AdminError) as ei:
            wire.admin_call(srv.port, "boom")
        assert ei.value.status == 409      # gate rejection is 409
        with pytest.raises(wire.AdminError) as ei:
            wire.admin_call(srv.port, "bad")
        assert ei.value.status == 400
    finally:
        srv.stop()


def test_http_admin_404_without_control_fn():
    srv = _stub_replica(lambda m, r, t: {})
    try:
        with pytest.raises(wire.AdminError) as ei:
            wire.admin_call(srv.port, "status")
        assert ei.value.status == 404
    finally:
        srv.stop()


def test_ephemeral_metrics_ports_do_not_collide():
    """Two servers with metrics_port=0 bind distinct kernel-assigned
    ports reported via bound_metrics_port — multi-process tests and
    benches must not race on fixed ports."""
    from transmogrifai_tpu.serving.http import MetricsServer
    a = MetricsServer(render_fn=lambda: "", health_fn=lambda: {},
                      port=0).start()
    b = MetricsServer(render_fn=lambda: "", health_fn=lambda: {},
                      port=0).start()
    try:
        assert a.port and b.port and a.port != b.port
    finally:
        a.stop()
        b.stop()


# -- supervisor over stub workers ---------------------------------------------

@pytest.fixture
def stub_stack(tmp_path):
    """Router + supervisor over N jax-free stub replicas."""
    created = []

    def make(replicas=2, sup_cls=ReplicaSupervisor, worker_args=None,
             **kw):
        state = str(tmp_path / f"state{len(created)}")
        router = Router(port=0).start()
        sup = sup_cls(None, state, router, replicas=replicas,
                      worker_module=STUB,
                      worker_args=list(worker_args or []),
                      heartbeat_ttl_s=2.0, poll_interval_s=0.15,
                      spawn_timeout_s=30.0, **kw)
        sup.start()
        created.append((router, sup))
        return router, sup

    yield make
    for router, sup in created:
        sup.stop()
        router.stop()


def _score_via(router, model="m", timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                      timeout=timeout)
    try:
        conn.request("POST", f"/score/{model}", "{}",
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, body
    finally:
        conn.close()


def test_supervisor_spawns_and_registers_replicas(stub_stack):
    router, sup = stub_stack(replicas=3)
    reps = router.replicas()
    assert sorted(reps) == ["r0", "r1", "r2"]
    assert all(r["state"] == "up" for r in reps.values())
    status, _ = _score_via(router)
    assert status == 200


def test_replica_kill9_zero_drops_and_respawn(stub_stack):
    """kill -9 one replica while scoring continuously: every request
    settles 200 (router retries absorb the death) and the supervisor
    respawns the victim onto a fresh port."""
    router, sup = stub_stack(replicas=3)
    failures = []
    stop = threading.Event()

    def score_loop():
        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=10)
        i = 0
        while not stop.is_set():
            try:
                conn.request("POST", f"/score/m{i % 4}", "{}",
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    failures.append(resp.status)
            except Exception as e:  # noqa: BLE001 — a client-visible drop
                failures.append(repr(e))
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", router.port, timeout=10)
            i += 1
            time.sleep(0.005)
        conn.close()

    t = threading.Thread(target=score_loop)
    t.start()
    time.sleep(0.3)
    victim = "r1"
    old_pid = sup._procs[victim].proc.pid
    os.kill(old_pid, signal.SIGKILL)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        entry = sup._procs.get(victim)
        if entry is not None and entry.proc.pid != old_pid \
                and entry.proc.poll() is None \
                and router.replicas().get(victim, {}).get("state") \
                == "up":
            break
        time.sleep(0.1)
    time.sleep(0.3)
    stop.set()
    t.join(timeout=10)
    assert failures == []
    assert sup.metrics.respawns == 1
    assert router.metrics.markdowns >= 1
    assert sup._procs[victim].proc.pid != old_pid


def test_scale_to_up_and_down(stub_stack):
    router, sup = stub_stack(replicas=2)
    assert sup.scale_to(4) == 4
    assert sorted(router.replicas()) == ["r0", "r1", "r2", "r3"]
    assert sup.metrics.scale_ups == 1
    assert sup.scale_to(2) == 2
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(router.replicas()) > 2:
        time.sleep(0.1)
    assert sorted(router.replicas()) == ["r0", "r1"]
    assert sup.metrics.scale_downs == 1


def test_rolling_swap_happy_path_converges(stub_stack):
    router, sup = stub_stack(replicas=3)
    report = sup.rolling_swap("m", version="v2")
    assert sorted(report["replicas"]) == ["r0", "r1", "r2"]
    for rid, hb in sup.heartbeats().items():
        st = wire.admin_call(hb["port"], "status")
        assert st["version"] == "v2"
    assert sup.metrics.rolls == 1


def test_rolling_swap_gate_rejection_halts_and_rolls_back(stub_stack,
                                                          tmp_path):
    """THE tested failure semantics: replica r1's shadow gate rejects
    the candidate -> the roll HALTS, already-swapped r0 is forced back
    to the old version (gate skipped), and the fleet converges on the
    OLD version — never split-brain."""

    class PerReplicaArgs(ReplicaSupervisor):
        def _worker_cmd(self, replica_id):
            cmd = super()._worker_cmd(replica_id)
            if replica_id == "r1":
                cmd.append("--reject-swap")
            return cmd

    router, sup = stub_stack(replicas=3, sup_cls=PerReplicaArgs)
    with pytest.raises(RollingSwapError) as ei:
        sup.rolling_swap("m", version="v2")
    err = ei.value
    assert err.gate_rejected
    assert err.failed_replica == "r1"
    assert err.swapped == ["r0"]
    assert err.rolled_back == ["r0"]
    for rid, hb in sup.heartbeats().items():
        st = wire.admin_call(hb["port"], "status")
        assert st["version"] == "v1", f"{rid} diverged"
    # r0's history shows the forced (gate-skipped) restore
    hb0 = sup.heartbeats()["r0"]
    swaps = wire.admin_call(hb0["port"], "status")["swaps"]
    assert [s["to"] for s in swaps] == ["v2", "v1"]
    assert swaps[1]["gated"] is False
    assert sup.metrics.roll_failures == 1
    assert sup.metrics.rollbacks == 1
    # routing recovered: every replica is back up
    assert all(r["state"] == "up"
               for r in router.replicas().values())


def test_stale_heartbeat_marks_down_without_respawn(stub_stack):
    """An alive-but-silent replica leaves routing (markdown) but is not
    respawned; a fresh ready heartbeat brings it back."""
    router, sup = stub_stack(replicas=2)
    hb = sup.heartbeats()["r0"]
    # suspend the process: heartbeats stop, process stays alive
    os.kill(sup._procs["r0"].proc.pid, signal.SIGSTOP)
    try:
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if router.replicas()["r0"]["state"] == "down":
                break
            time.sleep(0.1)
        assert router.replicas()["r0"]["state"] == "down"
        assert sup.metrics.respawns == 0
    finally:
        os.kill(sup._procs["r0"].proc.pid, signal.SIGCONT)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if router.replicas()["r0"]["state"] == "up":
            break
        time.sleep(0.1)
    assert router.replicas()["r0"]["state"] == "up"


# -- autoscaler ---------------------------------------------------------------

class _FakeSupervisor:
    def __init__(self, n=2):
        self.n = n
        self.calls = []
        self.router = types.SimpleNamespace(slo_engine=None)

    def replica_count(self):
        return self.n

    def scale_to(self, n):
        self.calls.append(n)
        self.n = n
        return n

    def queue_ratio(self, queue_capacity=None):
        return 0.0


def _scaler(sup, burn=False, queue=0.0, pressure=None, **kw):
    state = {"burn": burn, "queue": queue,
             "pressure": pressure or {"rssPressure": False,
                                      "diskPressure": False}}
    kw.setdefault("cooldown_s", 10.0)
    scaler = Autoscaler(sup, min_replicas=1, max_replicas=4,
                        low_steps=2,
                        burn_fn=lambda: state["burn"],
                        queue_ratio_fn=lambda: state["queue"],
                        pressure_fn=lambda: state["pressure"], **kw)
    return scaler, state


def test_autoscaler_scales_up_on_burn_and_on_queue():
    sup = _FakeSupervisor(2)
    scaler, state = _scaler(sup, burn=True)
    assert scaler.step(now=0.0) == {"direction": "up",
                                    "fromReplicas": 2,
                                    "toReplicas": 3,
                                    "reason": "slo_burn"}
    state["burn"] = False
    state["queue"] = 0.9
    assert scaler.step(now=100.0)["reason"] == "queue_depth"
    assert sup.calls == [3, 4]


def test_autoscaler_cooldown_and_bounds():
    sup = _FakeSupervisor(2)
    scaler, state = _scaler(sup, burn=True, cooldown_s=30.0)
    assert scaler.step(now=0.0) is not None
    assert scaler.step(now=5.0) is None          # cooldown
    assert scaler.step(now=40.0) is not None     # cooldown over
    assert sup.n == 4
    assert scaler.step(now=100.0) is None        # max_replicas bound


def test_autoscaler_scale_down_needs_sustained_idle():
    sup = _FakeSupervisor(3)
    scaler, state = _scaler(sup, queue=0.0)
    assert scaler.step(now=0.0) is None          # streak 1 of 2
    decision = scaler.step(now=1.0)
    assert decision == {"direction": "down", "fromReplicas": 3,
                        "toReplicas": 2, "reason": "idle"}
    # min bound: drain streak again at n=1
    sup.n = 1
    scaler._low_streak = 0
    assert scaler.step(now=100.0) is None
    assert scaler.step(now=101.0) is None


def test_autoscaler_pressure_blocks_up_and_forces_down():
    sup = _FakeSupervisor(2)
    scaler, state = _scaler(
        sup, burn=True, pressure={"rssPressure": True})
    decision = scaler.step(now=0.0)
    # a pressured host never scales up — it sheds a replica instead
    assert decision == {"direction": "down", "fromReplicas": 2,
                        "toReplicas": 1, "reason": "host_pressure"}
    # at min_replicas, pressure stops shedding (and up stays blocked)
    assert scaler.step(now=100.0) is None
    assert sup.n == 1


# -- chaos fault sites --------------------------------------------------------

def test_fault_scaleout_route_is_retried():
    from transmogrifai_tpu.utils.faults import fault_plan
    a = _stub_replica(lambda m, r, t: {"ok": True})
    b = _stub_replica(lambda m, r, t: {"ok": True})
    router = _router_with({"a": a, "b": b})
    try:
        with fault_plan("transient@scaleout.route#0") as plan:
            status, _, _, rid = router.dispatch("m", b"{}")
        assert status == 200
        assert router.metrics.retries >= 1
        assert ("scaleout.route", 0, "transient") in plan.fired
    finally:
        router.stop()
        a.stop()
        b.stop()


def test_fault_scaleout_heartbeat_monitor_survives(stub_stack,
                                                   recwarn):
    from transmogrifai_tpu.utils.faults import fault_plan
    router, sup = stub_stack(replicas=1)
    with fault_plan("io@scaleout.heartbeat#0x3") as plan:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not plan.fired:
            time.sleep(0.05)
        assert plan.fired
        time.sleep(0.5)
    # the monitor thread survived the injected tick failures and the
    # replica is still routable
    assert sup._monitor.is_alive()
    status, _ = _score_via(router)
    assert status == 200


def test_fault_scaleout_roll_halts_and_rolls_back(stub_stack):
    """An io fault at the SECOND roll step halts the roll; the first
    (already-swapped) replica rolls back — same convergence contract
    as a gate rejection."""
    from transmogrifai_tpu.utils.faults import fault_plan
    router, sup = stub_stack(replicas=2)
    with fault_plan("io@scaleout.roll#1") as plan:
        with pytest.raises(RollingSwapError) as ei:
            sup.rolling_swap("m", version="v2")
    assert ("scaleout.roll", 1, "io") in plan.fired
    assert not ei.value.gate_rejected
    assert ei.value.rolled_back == ei.value.swapped == ["r0"]
    for rid, hb in sup.heartbeats().items():
        assert wire.admin_call(hb["port"], "status")["version"] == "v1"


# -- durable ACTIVE alias (registry satellite) --------------------------------

def test_write_and_read_active_alias(tmp_path):
    from transmogrifai_tpu.serving.registry import (
        read_active_alias, write_active_alias,
    )
    root = str(tmp_path)
    path = write_active_alias(root, "churn", "v2")
    assert os.path.basename(path) == "ACTIVE.json"
    assert read_active_alias(os.path.join(root, "churn")) == "v2"
    # corrupt alias: warn-and-None (replica still serves something)
    with open(path, "w") as fh:
        fh.write("{torn")
    with pytest.warns(RuntimeWarning):
        assert read_active_alias(os.path.join(root, "churn")) is None


def _alias_writer(root, n_iters):
    from transmogrifai_tpu.serving.registry import write_active_alias
    for i in range(n_iters):
        write_active_alias(root, "m", f"v{1 + i % 2}")


def _alias_reader(root, n_iters, out_q):
    from transmogrifai_tpu.serving.registry import read_active_alias
    bad = 0
    seen = set()
    id_dir = os.path.join(root, "m")
    for _ in range(n_iters):
        v = read_active_alias(id_dir)
        if v is None:
            bad += 1        # a torn/partial write would parse-fail
        else:
            seen.add(v)
    out_q.put((bad, sorted(seen)))


def test_active_alias_concurrent_processes_never_torn(tmp_path):
    """Two processes hammering promote (write_active_alias) while two
    more read: every read observes a COMPLETE alias document (old or
    new, never torn/truncated) — the atomic-rename contract the
    multi-process rolling swap stands on."""
    root = str(tmp_path)
    from transmogrifai_tpu.serving.registry import write_active_alias
    write_active_alias(root, "m", "v1")
    ctx = multiprocessing.get_context("fork")
    q = ctx.Queue()
    writers = [ctx.Process(target=_alias_writer, args=(root, 300))
               for _ in range(2)]
    readers = [ctx.Process(target=_alias_reader, args=(root, 600, q))
               for _ in range(2)]
    for p in writers + readers:
        p.start()
    results = [q.get(timeout=60) for _ in readers]
    for p in writers + readers:
        p.join(timeout=30)
    for bad, seen in results:
        assert bad == 0, "a reader observed a torn/unreadable alias"
        assert set(seen) <= {"v1", "v2"}


def test_register_dir_honors_active_alias(tmp_path, zoo_model):
    """A respawned replica must come up serving the durably promoted
    version, not v1."""
    from transmogrifai_tpu.serving.registry import (
        ModelRegistry, write_active_alias,
    )
    model, _ = zoo_model
    root = tmp_path / "models"
    model.save(str(root / "m" / "v1"))
    model.save(str(root / "m" / "v2"))
    reg = ModelRegistry()
    reg.register_dir(str(root))
    assert reg.active_version("m") == "v1"      # no alias: lowest
    write_active_alias(str(root), "m", "v2")
    reg2 = ModelRegistry()
    reg2.register_dir(str(root))
    assert reg2.active_version("m") == "v2"     # alias wins
    # an alias naming a missing version warns and falls back
    write_active_alias(str(root), "m", "v9")
    with pytest.warns(RuntimeWarning, match="unregistered version"):
        reg3 = ModelRegistry()
        reg3.register_dir(str(root))
    assert reg3.active_version("m") == "v1"


# -- artifact store -----------------------------------------------------------

def test_artifact_store_publish_get_idempotent(tmp_path):
    from transmogrifai_tpu.scaleout.artifacts import ArtifactStore
    store = ArtifactStore(str(tmp_path))
    p1 = store.publish("fp1", {"modelId": "m", "warmRow": {"x": 1.0}})
    assert p1 and store.get("fp1")["warmRow"] == {"x": 1.0}
    # first writer wins: a second publish does not clobber
    store.publish("fp1", {"modelId": "m", "warmRow": {"x": 999.0}})
    assert store.get("fp1")["warmRow"] == {"x": 1.0}
    assert store.get("missing") is None
    assert store.list() == ["fp1"]
    doc = store.to_json()
    assert doc["manifests"] == 1


def test_registry_artifact_publication(tmp_path):
    from transmogrifai_tpu.scaleout.artifacts import ArtifactStore
    from transmogrifai_tpu.serving.registry import ModelRegistry
    reg = ModelRegistry()
    assert reg.publish_program_artifact("fp", {}) is None  # unattached
    assert reg.program_artifact("fp") is None
    reg.attach_artifacts(ArtifactStore(str(tmp_path)))
    reg.publish_program_artifact("fp", {"modelId": "m",
                                        "warmRow": {"a": 1}})
    assert reg.program_artifact("fp")["modelId"] == "m"


# -- real-worker end-to-end ---------------------------------------------------

N = 160


@pytest.fixture(scope="module")
def zoo_model():
    """One tiny fitted binary workflow + request rows."""
    from transmogrifai_tpu import dsl  # noqa: F401
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.uid import UID
    from transmogrifai_tpu.workflow import Workflow
    UID.reset()
    rng = np.random.default_rng(7)
    x1 = rng.normal(size=N)
    x2 = rng.normal(size=N)
    color = rng.choice(["red", "green", "blue"], size=N)
    logit = 1.5 * x1 - x2 + (color == "red") * 1.2
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-logit))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
        "color": (ft.PickList, color.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x1"], feats["x2"], feats["color"]])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=1, models_and_parameters=[
            (OpLogisticRegression(max_iter=20), [{}])])
    pred = feats["y"].transform_with(sel, features)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred, features).train())
    rows = [{"x1": float(x1[i]), "x2": float(x2[i]),
             "color": str(color[i])} for i in range(N)]
    return model, rows


def test_real_workers_end_to_end(tmp_path, zoo_model):
    """The full stack over REAL replica workers: router scoring parity
    with direct scoring, shared-artifact mapping with 0 post-warmup
    compiles, a rolling swap converging the fleet on v2 with the
    durable alias written — and a killed replica RESPAWNING onto the
    promoted version (the ACTIVE.json satellite proven end-to-end)."""
    from transmogrifai_tpu.local.scoring import make_score_function
    from transmogrifai_tpu.scaleout.stack import ScaleoutStack
    model, rows = zoo_model
    root = tmp_path / "models"
    model.save(str(root / "ma" / "v1"))
    model.save(str(root / "ma" / "v2"))   # same bytes: loose-gate roll
    stack = ScaleoutStack(
        str(root), str(tmp_path / "state"), replicas=2,
        warm_rows={"ma": rows[0]},
        worker_args=["--max-batch", "16", "--heartbeat-interval",
                     "0.3"],
        heartbeat_ttl_s=4.0, spawn_timeout_s=180.0)
    stack.start()
    try:
        assert len(stack.router.replicas()) == 2
        # scoring parity vs the in-process row scorer
        score_row = make_score_function(model, strict=False)
        conn = http.client.HTTPConnection("127.0.0.1", stack.port,
                                          timeout=60)
        for row in rows[:3]:
            while True:
                conn.request("POST", "/score/ma", json.dumps(row),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = json.loads(resp.read())
                if resp.status == 503:
                    time.sleep(0.05)
                    continue
                break
            assert resp.status == 200
            direct = score_row(dict(row))
            pred_key = next(k for k in direct if "prediction" in
                            str(direct[k]) or isinstance(direct[k],
                                                         dict))
            assert body["lineage"]["modelId"] == "ma"
            got = body[pred_key]["prediction"]
            want = direct[pred_key]["prediction"]
            assert got == pytest.approx(want, abs=1e-6)
        conn.close()
        # every replica mapped the shared artifacts, zero post-warmup
        # compiles
        for rid, hb in stack.supervisor.heartbeats().items():
            st = wire.admin_call(hb["port"], "status", timeout_s=30)
            assert st["artifactMapped"] == ["ma"]
            for per in st["postWarmupCompiles"].values():
                assert not per
        # rolling swap to v2 (identical bytes -> parity gate trivially
        # passes), durable alias written
        report = stack.rolling_swap("ma", version="v2")
        assert sorted(report["replicas"]) == sorted(
            stack.supervisor.replica_ids())
        from transmogrifai_tpu.serving.registry import (
            read_active_alias,
        )
        assert read_active_alias(str(root / "ma")) == "v2"
        # kill -9 one replica: its respawn must come up on v2
        victim = stack.supervisor.replica_ids()[0]
        old_pid = stack.supervisor._procs[victim].proc.pid
        os.kill(old_pid, signal.SIGKILL)
        deadline = time.monotonic() + 120
        respawned_hb = None
        while time.monotonic() < deadline:
            entry = stack.supervisor._procs.get(victim)
            hb = stack.supervisor.heartbeats().get(victim)
            if entry is not None and entry.proc.pid != old_pid \
                    and hb and hb.get("state") == "ready" \
                    and hb.get("pid") == entry.proc.pid:
                respawned_hb = hb
                break
            time.sleep(0.2)
        assert respawned_hb is not None, "victim did not respawn"
        st = wire.admin_call(respawned_hb["port"], "status",
                             timeout_s=30)
        active = {m["modelId"]: m["version"] for m in st["models"]
                  if m["active"]}
        assert active == {"ma": "v2"}, \
            "respawned replica regressed past the durable alias"
    finally:
        stack.stop()


# -- cli surface --------------------------------------------------------------

def test_cli_scaleout_argument_validation(capsys):
    from transmogrifai_tpu.cli import main
    assert main(["scaleout", "status"]) == 2       # needs --url
    assert main(["scaleout", "serve"]) == 2        # needs dirs
    err = capsys.readouterr().err
    assert "--url" in err and "--model-dir" in err


# -- SIGTERM drain (cli satellite) --------------------------------------------

def test_graceful_shutdown_is_systemexit():
    """The SIGTERM handler's exception must be a SystemExit subclass so
    the continuous loop classifies it as a routine shutdown (teardown,
    no incident dump)."""
    from transmogrifai_tpu.cli.serve import (
        GracefulShutdown, install_sigterm_handler,
    )
    assert issubclass(GracefulShutdown, SystemExit)
    assert install_sigterm_handler() is True    # main test thread
    signal.signal(signal.SIGTERM, signal.SIG_DFL)


def test_cli_serve_sigterm_drains_and_exits_zero(tmp_path, zoo_model):
    """`cli serve` under SIGTERM: already-admitted requests settle and
    land in the output, the snapshot is written, exit code 0 — not a
    mid-batch death."""
    import subprocess
    import sys
    model, rows = zoo_model
    mdir = tmp_path / "model"
    model.save(str(mdir))
    metrics = tmp_path / "metrics.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "transmogrifai_tpu.cli", "serve",
         "--model", str(mdir), "--input", "-", "--output", "-",
         "--metrics", str(metrics), "--no-warmup",
         "--metrics-port", "0"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, text=True)
    # readiness: the "# metrics: ..." stderr line prints right after
    # server.start() (scores only flush at window drain, so stdout is
    # silent until then — the exact mid-stream state SIGTERM must
    # handle)
    for line in proc.stderr:
        if line.startswith("#"):    # XLA may log cache loads before it
            break
    assert "# metrics" in line, line
    for row in rows[:5]:
        proc.stdin.write(json.dumps(row) + "\n")
    proc.stdin.flush()
    time.sleep(2.0)     # let the replay loop admit the rows
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err
    assert "SIGTERM: drained and stopped cleanly" in err
    scored = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
    assert len(scored) == 5, "admitted requests must drain to output"
    assert all("error" not in s for s in scored)
    assert metrics.exists()   # the snapshot was still written


# -- runner SCALEOUT mode -----------------------------------------------------

def test_runner_scaleout_replays_through_the_stack(tmp_path,
                                                   zoo_model):
    """`--run-type scaleout`: reader rows replay through a LIVE
    router + replica-worker stack (full multi-process path), metrics
    and replica table reported in the result json."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.params import OpParams
    from transmogrifai_tpu.runner import RunTypes, WorkflowRunner
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow
    model, rows = zoo_model
    root = tmp_path / "models"
    model.save(str(root / "ma" / "v1"))
    sub = rows[:12]
    score_frame = fr.HostFrame.from_dict({
        "x1": (ft.Real, [r["x1"] for r in sub]),
        "x2": (ft.Real, [r["x2"] for r in sub]),
        "color": (ft.PickList, [r["color"] for r in sub]),
    })
    wf = Workflow().set_input_frame(score_frame)
    wf.set_result_features(*model.result_features)
    runner = WorkflowRunner(wf)
    params = OpParams(custom_params={
        "modelDir": str(root), "replicas": 2, "maxBatch": 8,
        "stateDir": str(tmp_path / "state")})
    result = runner.run(RunTypes.SCALEOUT, params)
    assert result["status"] == "success"
    assert result["nRows"] == 12 and result["nErrors"] == 0
    assert result["rowsByModel"] == {"ma": 12}
    sc = result["scaleout"]
    assert len(sc["router"]["replicas"]) == 2
    assert sc["router"]["metrics"]["completed"] == 12
    # a state root is required (heartbeats/logs live there)
    with pytest.raises(ValueError, match="state root"):
        runner.run(RunTypes.SCALEOUT,
                   OpParams(custom_params={"modelDir": str(root)}))


def test_cli_scaleout_status_against_live_router(capsys):
    from transmogrifai_tpu.cli import main
    srv = _stub_replica(lambda m, r, t: {"ok": True})
    router = _router_with({"r0": srv})
    try:
        rc = main(["scaleout", "status",
                   "--url", f"http://127.0.0.1:{router.port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ready: True" in out and "r0" in out
        router.mark_down("r0")
        assert main(["scaleout", "status",
                     "--url", f"http://127.0.0.1:{router.port}"]) == 1
    finally:
        router.stop()
        srv.stop()
