"""Replica supervision: spawn, heartbeat liveness, crash respawn,
graceful scale, and the coordinated rolling hot-swap.

The supervisor owns the replica PROCESSES; the router owns the routing
table; this module wires the two together:

- **spawn**: each replica is a subprocess (``scaleout/worker.py`` by
  default; any module speaking ``scaleout/wire.py`` works — tests use
  the jax-free ``stub_worker``) with stdout/stderr captured under
  ``<state_dir>/replicas/<id>.log``. A replica joins the router only
  after its first heartbeat publishes a bound port.
- **liveness**: the monitor thread polls heartbeat files every
  ``poll_interval_s`` (chaos seam ``scaleout.heartbeat``). A stale
  heartbeat marks the replica down in the router (its in-flight
  requests retry onto ring successors — zero client drops); a dead
  process additionally **respawns** (same replica id, fresh port, the
  router re-points). A fresh ``ready`` heartbeat marks it back up.
- **scale**: ``scale_to(n)`` spawns new replicas or drains victims
  (admin drain -> SIGTERM -> join, ``kill`` only on timeout), keeping
  the ring membership in lockstep.
- **rolling hot-swap**: ``rolling_swap(model_id, ...)`` promotes a new
  version across replicas ONE at a time: the router drains the replica
  (no new traffic), the replica quiesces, its own ``FleetServer.
  hot_swap`` runs behind its shadow gate, the router marks it back up
  — so fleet-wide promotion has zero global downtime by construction.
  **Failure semantics (the tested contract): the roll HALTS and rolls
  BACK.** If any replica's gate rejects the candidate (or the swap
  fails), already-swapped replicas are forced back to the old version
  with the gate skipped (the old version is the known-good one), so
  the fleet converges on the OLD version — never a split-brain fleet
  serving two versions. A completed roll persists the durable
  ``ACTIVE.json`` alias, so respawned replicas come up on the promoted
  version.
"""

from __future__ import annotations

import glob
import os
import signal
import subprocess
import sys
import threading
import time
import warnings
from typing import Optional

from transmogrifai_tpu.scaleout import wire
from transmogrifai_tpu.scaleout.wire import AdminError, ReplicaStates
from transmogrifai_tpu.utils.events import events
from transmogrifai_tpu.utils.faults import fault_point

__all__ = ["ReplicaSupervisor", "RollingSwapError", "ScaleoutMetrics",
           "ChipCapacityError", "host_tpu_chips"]


class ChipCapacityError(RuntimeError):
    """The host cannot give every requested replica process a chip of
    its own (or this process already holds the chips)."""


def host_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device files.
    JAX-free on purpose: the supervisor must never open the chips its
    workers need."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


class RollingSwapError(RuntimeError):
    """A rolling promotion halted. ``gate_rejected`` tells a parity
    rejection from infrastructure failure; ``swapped`` lists replicas
    that had promoted before the halt and ``rolled_back`` which of
    those were forced back to the old version."""

    def __init__(self, msg: str, *, gate_rejected: bool,
                 failed_replica: str, swapped: list,
                 rolled_back: list):
        super().__init__(msg)
        self.gate_rejected = gate_rejected
        self.failed_replica = failed_replica
        self.swapped = list(swapped)
        self.rolled_back = list(rolled_back)


class ScaleoutMetrics:
    """Supervisor lifecycle counters (exported as
    ``transmogrifai_scaleout_*``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spawns = 0
        self.respawns = 0
        self.scale_ups = 0
        self.scale_downs = 0
        self.rolls = 0
        self.roll_failures = 0
        self.rollbacks = 0
        self.rebalances = 0

    def count(self, attr: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, attr, getattr(self, attr) + n)

    def to_json(self) -> dict:
        with self._lock:
            return {"spawns": self.spawns, "respawns": self.respawns,
                    "scaleUps": self.scale_ups,
                    "scaleDowns": self.scale_downs,
                    "rolls": self.rolls,
                    "rollFailures": self.roll_failures,
                    "rollbacks": self.rollbacks,
                    "rebalances": self.rebalances}


class _Proc:
    __slots__ = ("replica_id", "proc", "spawned_at", "respawns",
                 "down_reported")

    def __init__(self, replica_id, proc):
        self.replica_id = replica_id
        self.proc = proc
        self.spawned_at = time.time()
        self.respawns = 0
        #: the crash branch fires once per DEATH, not once per monitor
        #: tick — a permanently-dead replica (respawn budget exhausted)
        #: must not flood the flight recorder forever
        self.down_reported = False


class ReplicaSupervisor:
    """Own N replica worker processes behind one router."""

    def __init__(self, model_dir: Optional[str], state_dir: str,
                 router, *, replicas: int = 2,
                 worker_module: str = "transmogrifai_tpu.scaleout.worker",
                 worker_args: Optional[list] = None,
                 worker_env: Optional[dict] = None,
                 heartbeat_ttl_s: float = 3.0,
                 poll_interval_s: float = 0.5,
                 spawn_timeout_s: float = 120.0,
                 respawn: bool = True,
                 max_respawns_per_replica: int = 5,
                 drain_timeout_s: float = 30.0,
                 rebalance_skew: float = 2.0,
                 rebalance_cooldown_s: float = 10.0):
        self.model_dir = model_dir
        self.state_dir = state_dir
        self.router = router
        self.desired_replicas = int(replicas)
        self.worker_module = worker_module
        self.worker_args = list(worker_args or [])
        self.worker_env = dict(worker_env or {})
        self.heartbeat_ttl_s = float(heartbeat_ttl_s)
        self.poll_interval_s = float(poll_interval_s)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.respawn = bool(respawn)
        self.max_respawns_per_replica = int(max_respawns_per_replica)
        self.drain_timeout_s = float(drain_timeout_s)
        #: trigger a load-weighted ring rebalance when the router's
        #: primary-load skew (max/mean) exceeds this; <= 1.0 disables.
        #: Cooldown keeps successive ticks from thrashing the ring
        #: while the damped re-weighting converges
        self.rebalance_skew = float(rebalance_skew)
        self.rebalance_cooldown_s = float(rebalance_cooldown_s)
        self._last_rebalance = 0.0
        self.metrics = ScaleoutMetrics()
        self._procs: dict[str, _Proc] = {}
        self._lock = threading.RLock()
        self._seq = 0
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None

    # -- spawning -------------------------------------------------------------
    def _next_id(self) -> str:
        with self._lock:
            rid = f"r{self._seq}"
            self._seq += 1
            return rid

    def _worker_cmd(self, replica_id: str) -> list:
        cmd = [sys.executable, "-m", self.worker_module,
               "--state-dir", self.state_dir,
               "--replica-id", replica_id]
        if self.model_dir is not None:
            cmd += ["--model-dir", self.model_dir]
        return cmd + self.worker_args

    def _check_chip_capacity(self, replicas: int) -> None:
        """One process per chip: a TPU belongs to the process that opened
        it, and a worker is given no chip assignment of its own — each
        opens every chip on the host. So on a TPU host, workers that run
        on the chip are refused (a) from a parent that has itself
        initialized the TPU backend (it holds the chips; every worker
        would fail or hang) and (b) in any number above one. CPU-pinned
        workers (``JAX_PLATFORMS=cpu``) and hosts without a TPU are not
        constrained. Raises before anything is spawned."""
        platforms = self.worker_env.get(
            "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
        chips = host_tpu_chips()
        if platforms == "cpu" or chips == 0:
            return
        jax = sys.modules.get("jax")
        if jax is not None:
            from jax._src import xla_bridge
            if xla_bridge.backends_are_initialized() \
                    and jax.default_backend() == "tpu":
                raise ChipCapacityError(
                    "scaleout: this process has initialized the TPU "
                    "backend and holds the host's chips; replica workers "
                    "started from it would fail or hang at start-up. "
                    "Start the supervisor from a process that stays off "
                    "jax (e.g. `python -m transmogrifai_tpu.cli scaleout "
                    "serve`), or pin the workers to the CPU "
                    "(worker_env={'JAX_PLATFORMS': 'cpu'}).")
        if replicas > 1:
            raise ChipCapacityError(
                f"scaleout: {replicas} chip replicas requested on a host "
                f"with {chips} TPU chip(s), but workers get no per-process "
                "chip assignment — each opens every chip, so the host can "
                "give a chip to ONE worker process. Use replicas=1 (one "
                "process can drive all chips), or pin the workers to the "
                "CPU (worker_env={'JAX_PLATFORMS': 'cpu'}).")

    def _spawn(self, replica_id: str, respawn_of: bool = False) -> _Proc:
        log_dir = os.path.join(self.state_dir, wire.HEARTBEAT_DIRNAME)
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(log_dir, f"{replica_id}.log")
        env = dict(os.environ)
        # the worker inherits the SUPERVISOR's import environment: the
        # parent's full sys.path rides in PYTHONPATH so (a) the
        # framework itself is importable from any cwd (source-tree runs
        # outside the repo would respawn-loop on ModuleNotFoundError)
        # and (b) `load_model` can resolve CUSTOM stage classes from
        # wherever the operator's deployment put their modules — if the
        # control process can load the model, its replicas can too
        import transmogrifai_tpu
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(transmogrifai_tpu.__file__)))
        paths = [pkg_root] + [p for p in sys.path if p]
        if env.get("PYTHONPATH"):
            paths.append(env["PYTHONPATH"])
        env["PYTHONPATH"] = os.pathsep.join(
            dict.fromkeys(paths))    # de-duped, order-preserving
        env.update(self.worker_env)
        with open(log_path, "ab") as log_fh:
            proc = subprocess.Popen(
                self._worker_cmd(replica_id), stdout=log_fh,
                stderr=subprocess.STDOUT, env=env,
                start_new_session=True)
        entry = _Proc(replica_id, proc)
        with self._lock:
            prev = self._procs.get(replica_id)
            if prev is not None:
                entry.respawns = prev.respawns + (1 if respawn_of else 0)
            self._procs[replica_id] = entry
        self.metrics.count("respawns" if respawn_of else "spawns")
        events.emit("scaleout.replica_spawned", replica=replica_id,
                    pid=proc.pid, respawn=respawn_of)
        return entry

    def _wait_ready(self, replica_id: str,
                    timeout_s: Optional[float] = None) -> Optional[dict]:
        """Poll for the replica's first fresh heartbeat carrying a bound
        port; registers it with the router. None on timeout/exit."""
        deadline = time.monotonic() + (timeout_s if timeout_s is not None
                                       else self.spawn_timeout_s)
        while time.monotonic() < deadline:
            with self._lock:
                entry = self._procs.get(replica_id)
            if entry is not None and entry.proc.poll() is not None:
                return None     # died during startup; monitor respawns
            hb = wire.read_heartbeats(self.state_dir).get(replica_id)
            if hb and hb.get("port") \
                    and wire.is_fresh(hb, self.heartbeat_ttl_s) \
                    and self._hb_pid_matches(hb, entry) \
                    and hb.get("state") in (ReplicaStates.READY,
                                            ReplicaStates.SWAPPING):
                self.router.set_replica(replica_id, hb["port"])
                return hb
            time.sleep(0.05)
        return None

    @staticmethod
    def _hb_pid_matches(hb: dict, entry: Optional["_Proc"]) -> bool:
        """A killed replica's heartbeat FILE outlives it and stays
        fresh for up to a TTL — a respawn must not read the dead
        process's port as its own readiness. The heartbeat's pid is
        the disambiguator."""
        if entry is None:
            return True
        pid = hb.get("pid")
        return pid is None or pid == entry.proc.pid

    # -- lifecycle ------------------------------------------------------------
    def start(self, wait_ready: bool = True) -> "ReplicaSupervisor":
        self._check_chip_capacity(self.desired_replicas)
        for _ in range(self.desired_replicas):
            self._spawn(self._next_id())
        if wait_ready:
            for rid in self.replica_ids():
                if self._wait_ready(rid) is None:
                    warnings.warn(
                        f"scaleout: replica {rid} did not become ready "
                        f"within {self.spawn_timeout_s:.0f}s (see "
                        f"{self.state_dir}/replicas/{rid}.log)",
                        RuntimeWarning)
        self._stop.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop,
            name="transmogrifai-scaleout-monitor", daemon=True)
        self._monitor.start()
        return self

    def stop(self, drain: bool = True) -> None:
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
            self._monitor = None
        with self._lock:
            entries = list(self._procs.values())
        for entry in entries:
            self._stop_replica(entry, drain=drain)
        with self._lock:
            self._procs.clear()

    def _stop_replica(self, entry: _Proc, drain: bool = True) -> None:
        """Graceful replica stop: router out first, then SIGTERM (the
        worker drains in-flight), kill only on timeout."""
        self.router.set_draining(entry.replica_id)
        if entry.proc.poll() is None:
            try:
                entry.proc.terminate()      # SIGTERM: worker drains
                entry.proc.wait(timeout=self.drain_timeout_s)
            except subprocess.TimeoutExpired:
                warnings.warn(
                    f"scaleout: replica {entry.replica_id} ignored "
                    "SIGTERM; killing", RuntimeWarning)
                entry.proc.kill()
                entry.proc.wait(timeout=5.0)
            except Exception:  # noqa: BLE001 — already-dead races (failure-ok)
                pass
        self.router.remove_replica(entry.replica_id)
        wire.clear_heartbeat(self.state_dir, entry.replica_id)
        events.emit("scaleout.replica_stopped",
                    replica=entry.replica_id)

    def replica_ids(self) -> list[str]:
        with self._lock:
            return sorted(self._procs,
                          key=lambda r: int(r[1:]) if r[1:].isdigit()
                          else 0)

    def replica_count(self) -> int:
        with self._lock:
            return len(self._procs)

    # -- liveness monitor -----------------------------------------------------
    def _monitor_loop(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            try:
                fault_point("scaleout.heartbeat")
                self._tick()
            except Exception as e:  # noqa: BLE001 — the monitor must survive
                from transmogrifai_tpu.utils.faults import (
                    SimulatedPreemption,
                )
                if isinstance(e, SimulatedPreemption):
                    raise   # a preempted supervisor dies, not degrades
                warnings.warn(
                    f"scaleout: monitor tick failed ({type(e).__name__}"
                    f": {e})", RuntimeWarning)

    def _tick(self) -> None:
        heartbeats = wire.read_heartbeats(self.state_dir)
        with self._lock:
            entries = list(self._procs.values())
        for entry in entries:
            rid = entry.replica_id
            hb = heartbeats.get(rid)
            alive = entry.proc.poll() is None
            fresh = hb is not None and wire.is_fresh(
                hb, self.heartbeat_ttl_s)
            state = (hb or {}).get("state")
            if not alive:
                # crash (kill -9, OOM-kill, bug): out of routing NOW,
                # respawn if budgeted — the router already retried the
                # requests that discovered the death. Transition-edged:
                # a permanently-dead replica is reported once, not once
                # per tick.
                if entry.down_reported:
                    continue
                entry.down_reported = True
                self.router.mark_down(rid, reason="process exited "
                                      f"rc={entry.proc.poll()}")
                events.emit("scaleout.replica_down", replica=rid,
                            returncode=entry.proc.poll())
                if self.respawn and not self._stop.is_set():
                    if entry.respawns >= self.max_respawns_per_replica:
                        warnings.warn(
                            f"scaleout: replica {rid} exceeded "
                            f"{self.max_respawns_per_replica} respawns; "
                            "leaving it down", RuntimeWarning)
                        continue
                    with self._lock:
                        # a scale-down/stop may have REMOVED this
                        # replica while the tick was blocked (e.g. in
                        # another replica's _wait_ready): respawning a
                        # deliberately-retired replica would overshoot
                        # desired_replicas and fight the autoscaler
                        if self._procs.get(rid) is not entry:
                            continue
                    self._spawn(rid, respawn_of=True)
                    self._wait_ready(rid)
                continue
            entry.down_reported = False
            if not fresh:
                # alive but silent: hung or thrashing — stop routing to
                # it; it rejoins on its next fresh ready heartbeat
                self.router.mark_down(rid, reason="stale heartbeat")
                continue
            if not self._hb_pid_matches(hb, entry):
                # a fresh-looking heartbeat from the PREVIOUS process
                # of this replica id (killed within the TTL): the new
                # process hasn't published yet — not routable
                self.router.mark_down(rid, reason="heartbeat from "
                                                  "dead predecessor")
                continue
            if state == ReplicaStates.READY:
                if hb.get("port"):
                    self.router.set_replica(rid, hb["port"])
                self.router.mark_up(rid)
            elif state in (ReplicaStates.DRAINING,
                           ReplicaStates.STOPPED):
                self.router.set_draining(rid)
        self._maybe_rebalance()

    def _maybe_rebalance(self) -> None:
        """Skew-aware placement: when the router's per-model EWMA loads
        pile onto one primary past ``rebalance_skew`` (max/mean), take
        one damped re-weighting step — the ring rebalances on LOAD
        skew, not just membership change. Cooldown-limited so the EWMA
        can reflect the new placement before the next step."""
        if self.rebalance_skew <= 1.0:
            return
        load_skew = getattr(self.router, "load_skew", None)
        rebalance = getattr(self.router, "rebalance", None)
        if load_skew is None or rebalance is None:
            return
        if len(getattr(self.router, "ring", ())) < 2:
            return      # one primary owns everything by construction
        now = time.time()
        if now - self._last_rebalance < self.rebalance_cooldown_s:
            return
        skew = load_skew()
        if skew <= self.rebalance_skew:
            return
        self._last_rebalance = now
        if rebalance():
            self.metrics.count("rebalances")
            events.emit("scaleout.rebalanced", skew=round(skew, 3))

    # -- scaling --------------------------------------------------------------
    def scale_to(self, n: int, wait_ready: bool = True) -> int:
        """Converge on ``n`` replicas. Scale-up spawns; scale-down
        drains the newest replicas first (oldest keep their warm
        caches). Returns the resulting count."""
        n = int(n)
        with self._lock:
            current = len(self._procs)
        if n > current:
            self._check_chip_capacity(n)
            self.metrics.count("scale_ups")
            events.emit("scaleout.scale", direction="up",
                        fromReplicas=current, toReplicas=n)
            new_ids = [self._next_id() for _ in range(n - current)]
            for rid in new_ids:
                self._spawn(rid)
            if wait_ready:
                for rid in new_ids:
                    self._wait_ready(rid)
        elif n < current:
            self.metrics.count("scale_downs")
            events.emit("scaleout.scale", direction="down",
                        fromReplicas=current, toReplicas=n)
            victims = self.replica_ids()[n:]
            for rid in victims:
                with self._lock:
                    entry = self._procs.pop(rid, None)
                if entry is not None:
                    self._drain_admin(rid)
                    self._stop_replica(entry)
        self.desired_replicas = n
        return self.replica_count()

    def _drain_admin(self, replica_id: str) -> None:
        """Best-effort admin drain (quiesce stragglers) before SIGTERM."""
        hb = wire.read_heartbeats(self.state_dir).get(replica_id)
        if hb and hb.get("port"):
            try:
                wire.admin_call(hb["port"], "drain",
                                {"timeoutS": self.drain_timeout_s},
                                timeout_s=self.drain_timeout_s + 5)
            except AdminError:
                pass

    # -- rolling hot-swap -----------------------------------------------------
    def rolling_swap(self, model_id: str, *,
                     version: Optional[str] = None,
                     path: Optional[str] = None,
                     tolerance: Optional[float] = None,
                     shadow_rows: Optional[int] = None) -> dict:
        """Promote ``version``/``path`` of ``model_id`` across every
        live replica, one at a time, each behind its own shadow gate
        (see the module docstring for the halt-and-roll-back failure
        semantics). Returns a roll report."""
        if version is None and path is None:
            raise ValueError("rolling_swap needs a version or a path")
        t0 = time.monotonic()
        heartbeats = wire.read_heartbeats(self.state_dir)
        with self._lock:
            procs = dict(self._procs)
        targets = [rid for rid in self.replica_ids()
                   if heartbeats.get(rid, {}).get("port")
                   and wire.is_fresh(heartbeats[rid],
                                     self.heartbeat_ttl_s)
                   and self._hb_pid_matches(heartbeats[rid],
                                            procs.get(rid))]
        if not targets:
            raise RuntimeError("rolling_swap: no live replicas")
        swapped: list[tuple] = []      # (replica_id, swap report)
        events.emit("scaleout.roll_started", model=model_id,
                    version=version, path=path, replicas=targets)
        for rid in targets:
            port = heartbeats[rid]["port"]
            self.router.set_draining(rid)
            pre_state = self._pre_swap_state(port, model_id)
            try:
                fault_point("scaleout.roll")
                self._admin_drain_quiet(port)
                payload: dict = {"modelId": model_id}
                if version is not None:
                    payload["version"] = version
                if path is not None:
                    payload["path"] = path
                if tolerance is not None:
                    payload["tolerance"] = tolerance
                if shadow_rows is not None:
                    payload["shadowRows"] = shadow_rows
                report = wire.admin_call(port, "swap", payload,
                                         timeout_s=self.drain_timeout_s
                                         + 60)
            except Exception as e:  # noqa: BLE001 — halt the roll, converge back
                from transmogrifai_tpu.utils.faults import (
                    FaultHarnessError,
                )
                self.router.mark_up(rid)   # still serving the OLD version
                gate = isinstance(e, AdminError) and e.status == 409
                if not gate and pre_state is not None:
                    # a TRANSPORT-level failure (timeout, connection
                    # death) is ambiguous: the replica's in-flight
                    # hot_swap may still COMPLETE after this halt,
                    # leaving it alone on the new version — the exact
                    # split-brain the rollback exists to prevent. Force
                    # it back using the pre-swap state captured above
                    # (an "already active" refusal from a replica that
                    # never swapped is the harmless outcome).
                    swapped.append((rid, pre_state))
                rolled_back = self._rollback(model_id, swapped)
                self.metrics.count("roll_failures")
                events.emit("scaleout.roll_failed", model=model_id,
                            replica=rid, gateRejected=gate,
                            swapped=[r for r, _ in swapped],
                            rolledBack=rolled_back,
                            error=f"{type(e).__name__}: {str(e)[:200]}")
                err = RollingSwapError(
                    f"rolling swap of {model_id!r} halted at replica "
                    f"{rid}: {e}; {len(rolled_back)}/{len(swapped)} "
                    "already-swapped replica(s) rolled back — fleet "
                    "converges on the old version",
                    gate_rejected=gate, failed_replica=rid,
                    swapped=[r for r, _ in swapped],
                    rolled_back=rolled_back)
                if isinstance(e, FaultHarnessError):
                    # chaos-harness errors surface as themselves, with
                    # the converge-back already done above
                    raise e
                raise err from e
            self.router.mark_up(rid)
            swapped.append((rid, report))
            events.emit("scaleout.roll_step", model=model_id,
                        replica=rid,
                        toVersion=report.get("toVersion"))
        self._persist_alias(model_id, version, path, swapped)
        wall = time.monotonic() - t0
        self.metrics.count("rolls")
        events.emit("scaleout.roll", model=model_id, version=version,
                    replicas=[r for r, _ in swapped],
                    wallSeconds=round(wall, 6))
        return {"modelId": model_id, "version": version, "path": path,
                "replicas": [r for r, _ in swapped],
                "wallSeconds": round(wall, 6),
                "reports": {r: rep for r, rep in swapped}}

    def _admin_drain_quiet(self, port: int) -> None:
        try:
            wire.admin_call(port, "drain", {"timeoutS": 10.0},
                            timeout_s=20.0)
        except AdminError:
            pass    # drain is belt-and-braces; the swap itself drains

    def _pre_swap_state(self, port: int,
                        model_id: str) -> Optional[dict]:
        """The replica's ACTIVE version + path for ``model_id`` before
        its swap — the rollback recipe for the ambiguous transport-
        failure case (see rolling_swap). None when unreadable."""
        try:
            st = wire.admin_call(port, "status", timeout_s=20.0)
        except AdminError:
            return None
        for m in st.get("models", []):
            if m.get("modelId") == model_id and m.get("active"):
                return {"fromVersion": m.get("version"),
                        "fromPath": m.get("path")}
        return None

    def _rollback(self, model_id: str, swapped: list) -> list:
        """Force already-swapped replicas back to the old version, gate
        skipped (``shadowRows: 0`` — the old version is the known-good
        one and a symmetric parity gate would reject the restore for
        exactly the divergence that aborted the roll)."""
        rolled_back: list = []
        heartbeats = wire.read_heartbeats(self.state_dir)
        for rid, report in reversed(swapped):
            from_path = report.get("fromPath")
            from_version = report.get("fromVersion")
            port = heartbeats.get(rid, {}).get("port")
            if port is None or (from_path is None
                                and from_version is None):
                warnings.warn(
                    f"scaleout: cannot roll back replica {rid} (no "
                    "port/old-version info); it keeps the NEW version "
                    "until the next roll", RuntimeWarning)
                continue
            payload = {"modelId": model_id, "shadowRows": 0}
            if from_path is not None:
                payload["path"] = from_path
            else:
                payload["version"] = from_version
            try:
                self.router.set_draining(rid)
                wire.admin_call(port, "swap", payload,
                                timeout_s=self.drain_timeout_s + 60)
                rolled_back.append(rid)
                self.metrics.count("rollbacks")
            except AdminError as e:
                warnings.warn(
                    f"scaleout: rollback of replica {rid} failed "
                    f"({e}); it keeps the NEW version", RuntimeWarning)
            finally:
                self.router.mark_up(rid)
        return rolled_back

    def _persist_alias(self, model_id: str, version: Optional[str],
                       path: Optional[str], swapped: list) -> None:
        """Persist the durable ACTIVE alias after a COMPLETED roll so a
        respawned replica serves the promoted version. Only meaningful
        for the versioned ``<model_dir>/<id>/<version>/`` layout."""
        if self.model_dir is None:
            return
        ver = version
        if ver is None and path is not None:
            parent = os.path.dirname(os.path.normpath(path))
            if os.path.basename(parent) == model_id and \
                    os.path.dirname(parent) == \
                    os.path.normpath(self.model_dir):
                ver = os.path.basename(os.path.normpath(path))
        if ver is None and swapped:
            ver = swapped[-1][1].get("toVersion")
            # a path outside the register layout has no durable name —
            # respawns keep activating per ACTIVE/lowest as before
            if path is not None:
                return
        if ver:
            from transmogrifai_tpu.serving.registry import (
                write_active_alias,
            )
            try:
                write_active_alias(self.model_dir, model_id, ver)
            except OSError as e:
                warnings.warn(
                    f"scaleout: could not persist ACTIVE alias "
                    f"({type(e).__name__}: {e}); respawned replicas "
                    "will serve the pre-roll version", RuntimeWarning)

    # -- observability --------------------------------------------------------
    def heartbeats(self) -> dict:
        return wire.read_heartbeats(self.state_dir)

    def queue_ratio(self, queue_capacity: Optional[int] = None) -> float:
        """Mean fill ratio of replica admission queues (the autoscaler's
        load signal). Uses each heartbeat's own ``queueCapacity`` when
        present, else ``queue_capacity``."""
        heartbeats = self.heartbeats()
        ratios: list[float] = []
        for hb in heartbeats.values():
            if not wire.is_fresh(hb, self.heartbeat_ttl_s):
                continue
            depths = hb.get("queueDepths") or {}
            cap = hb.get("queueCapacity") or queue_capacity
            if not cap:
                continue
            total = sum(int(v) for v in depths.values()) \
                if isinstance(depths, dict) else 0
            ratios.append(min(total / float(cap), 1.0))
        return sum(ratios) / len(ratios) if ratios else 0.0

    def to_json(self) -> dict:
        with self._lock:
            procs = {rid: {"pid": p.proc.pid,
                           "alive": p.proc.poll() is None,
                           "respawns": p.respawns,
                           "spawnedAt": p.spawned_at}
                     for rid, p in self._procs.items()}
        return {"desiredReplicas": self.desired_replicas,
                "replicas": procs,
                "metrics": self.metrics.to_json()}
