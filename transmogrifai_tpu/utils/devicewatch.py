"""Device-execution observatory: dispatch watchdog, hang autopsy, and
compile/HBM telemetry.

PR 9 made *requests* legible (trace ids, flight recorder, SLO burn
rates); device execution stayed a black box — BENCH_r05's accelerator
probes each died with one stderr line (``hung > 240s``) and nothing to
say WHICH dispatch stalled, what was compiling, or which buffers held
HBM. This module is the accelerator-side analog of the flight recorder,
three instruments over one shared device census:

- :class:`DispatchWatchdog` — every blocking device wait (the one-sync
  sweep settle, collectives, serving batch dispatch, checkpoint
  restores) arms a deadline via :meth:`~DispatchWatchdog.guard`. A wait
  that outlives its deadline fires ONE **autopsy**: all Python thread
  stacks (faulthandler-style), the :data:`dispatch_ledger` inventory of
  in-flight device work, a live-buffer + per-device ``memory_stats``
  HBM census, compile-in-progress state, and the recent flight-recorder
  tail — emitted as a ``device.stall`` event and frozen via
  ``events.dump_incident`` when an incident dir is configured.
  Recoverable waits keep waiting (the guard never raises); expired
  *deadlines* stay the caller's contract (``run_with_deadline`` still
  raises ``CollectiveTimeoutError`` — now with an autopsy attached).
- :class:`CompileTelemetry` — every XLA backend compile (observed via
  the ``jax.monitoring`` duration listener, the program's only one)
  records wall attributed to the active
  :meth:`~CompileTelemetry.building` site as a ``compile.program:<site>``
  span (``compile.cache_load:<site>`` when the persistent cache served
  it) + ``transmogrifai_compile_*`` Prometheus series, with a
  slow-compile threshold event — a compile storm or a pathological HLO
  is visible *before* it looks like a hang.
  :func:`analyze_program` adds HLO size + cost-analysis FLOPs/bytes at
  cold seams (serving warmup) where a program handle exists.
- an **HBM timeline** — low-rate all-device census samples
  (:func:`sample_hbm`, driven by ``ResourceWatchdog.tick`` and the
  watchdog's own poll while waits are armed) merged into the
  chrome-trace export as a counter track.

The census (:func:`device_memory_census`) sums across EVERY local
device — the one shared probe behind the per-phase and per-span
peak-HBM samplers and the sweep's HBM budget, replacing three ad-hoc
``jax.local_devices()[0]`` shortcuts (a sharded run's memory lives on
all mesh devices, not device 0).

Cost discipline: a guard is two dict ops under a lock per blocking wait
(batch/settle granularity, never per row); the monitor thread polls
only while waits are armed and exits when idle; the census and
``jax.live_arrays()`` walk run only inside an autopsy — each behind its
own small deadline, because an autopsy probe that blocks on the very
hang it is diagnosing would never report. Gated by
``TRANSMOGRIFAI_DEVICEWATCH`` (default on);
``TRANSMOGRIFAI_STALL_TIMEOUT_S`` sets the default stall deadline and
``TRANSMOGRIFAI_DEVICEWATCH_DIR`` the incident directory (unset = emit
events only, write nothing).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import sys
import threading
import time
import traceback
import warnings
from collections import deque
from typing import Any, Callable, Optional

__all__ = ["device_memory_census", "device_memory_census_bounded",
           "device_memory", "device_memory_bounded", "device_bytes_limit",
           "live_buffer_census", "thread_stacks", "DispatchLedger",
           "dispatch_ledger", "CompileTelemetry", "compile_telemetry",
           "analyze_program", "DispatchWatchdog", "watchdog", "guard",
           "configure", "stall_autopsy", "build_autopsy", "sample_hbm",
           "hbm_timeline", "reset_run"]

#: master switch for the watchdog (default ON; guards become no-ops off)
ENABLE_ENV = "TRANSMOGRIFAI_DEVICEWATCH"
#: default stall deadline for guarded waits (seconds; <= 0 disables;
#: default 600 — see DispatchWatchdog.default_timeout_s)
STALL_TIMEOUT_ENV = "TRANSMOGRIFAI_STALL_TIMEOUT_S"
#: incident directory for autopsy dumps (unset = events only, no files)
INCIDENT_DIR_ENV = "TRANSMOGRIFAI_DEVICEWATCH_DIR"
#: backend compiles slower than this emit a ``compile.slow`` event
SLOW_COMPILE_ENV = "TRANSMOGRIFAI_SLOW_COMPILE_S"

#: how long an autopsy probe (census, live-arrays walk) may itself block
#: before the autopsy proceeds without it — a probe that needs the hung
#: backend must not hang the diagnosis
_PROBE_DEADLINE_S = 5.0


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return float(v)
    except ValueError:
        warnings.warn(f"{name}={v!r} is not a number; using {default}",
                      RuntimeWarning)
        return default


# -- the shared device census -------------------------------------------------

def device_memory_census() -> dict:
    """``memory_stats`` summed across EVERY local device, plus the
    per-device breakdown: ``{"bytesInUse", "peakBytesInUse",
    "bytesLimit", "devices": [{"device", "bytesInUse", "peakBytesInUse",
    "bytesLimit"}, ...]}``. All zeros when the backend exposes no memory
    stats (CPU, some plugins). THE probe behind per-phase/per-span peak
    HBM and the sweep's HBM budget — a mesh-sharded batch lives on every
    device, so a device-0-only sample undercounts by the device count."""
    out: dict = {"bytesInUse": 0, "peakBytesInUse": 0, "bytesLimit": 0,
                 "devices": []}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:  # failure-ok: no jax backend -> empty census
        return out
    for dev in devices:
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # failure-ok: backend exposes no memory stats
            stats = {}
        in_use = int(stats.get("bytes_in_use", 0))
        peak = int(stats.get("peak_bytes_in_use", 0))
        limit = int(stats.get("bytes_limit", 0))
        out["bytesInUse"] += in_use
        out["peakBytesInUse"] += peak
        out["bytesLimit"] += limit
        out["devices"].append({"device": str(dev), "bytesInUse": in_use,
                               "peakBytesInUse": peak,
                               "bytesLimit": limit})
    return out


def stacked_hbm_budget() -> float:
    """Byte budget for one family's stacked fold batch.
    ``TRANSMOGRIFAI_SWEEP_HBM_BUDGET`` overrides; otherwise half the
    reported memory limit from the census — summed across ALL local
    devices when a mesh is active (the stacked batch shards over it), but
    device 0's alone without one (un-meshed, the batch lands on a single
    device and an N-device sum would admit N×-too-large programs) — or
    4 GiB when the backend exposes none (CPU)."""
    env = os.environ.get("TRANSMOGRIFAI_SWEEP_HBM_BUDGET")
    if env:
        return float(env)
    try:
        from transmogrifai_tpu.parallel import mesh as pmesh
        census = device_memory_census()
        if pmesh.current_mesh() is not None:
            limit = float(census["bytesLimit"])
        else:
            devices = census["devices"]
            limit = float(devices[0]["bytesLimit"]) if devices else 0.0
        if limit > 0:
            return 0.5 * limit
    except Exception:  # failure-ok: memory-stats probe; conservative default
        pass
    return float(4 << 30)


def device_memory() -> tuple[int, int]:
    """``(bytes_in_use, peak_bytes_in_use)`` summed across all local
    devices — the signature ``utils.profiling`` and ``utils.tracing``
    share for their HBM high-water probes."""
    c = device_memory_census()
    return c["bytesInUse"], c["peakBytesInUse"]


def device_bytes_limit() -> int:
    """Total reported device memory limit across all local devices
    (0 when the backend exposes none) — the sweep's HBM-budget base."""
    return device_memory_census()["bytesLimit"]


def live_buffer_census(top_k: int = 10) -> dict:
    """``jax.live_arrays()`` bucketed by (shape, dtype): who is actually
    holding device memory. Returns ``{"arrays", "totalBytes",
    "buckets": [{"shape", "dtype", "count", "bytes"}, ...]}`` with the
    ``top_k`` heaviest buckets. Autopsy-time only — the walk touches
    every live buffer."""
    out: dict = {"arrays": 0, "totalBytes": 0, "buckets": []}
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:  # failure-ok: live-array introspection is optional
        return out
    buckets: dict[tuple, dict] = {}
    total = 0
    for a in arrays:
        try:
            shape = tuple(a.shape)
            dtype = str(a.dtype)
            nbytes = int(getattr(a, "nbytes", 0))
        except Exception:  # failure-ok: a deleted buffer mid-walk is skipped
            continue
        b = buckets.setdefault((shape, dtype), {
            "shape": str(shape), "dtype": dtype, "count": 0, "bytes": 0})
        b["count"] += 1
        b["bytes"] += nbytes
        total += nbytes
    out["arrays"] = len(arrays)
    out["totalBytes"] = total
    out["buckets"] = sorted(buckets.values(),
                            key=lambda b: -b["bytes"])[:top_k]
    return out


def thread_stacks(max_frames: int = 40) -> list[dict]:
    """Every Python thread's current stack (faulthandler-style, but
    structured): ``[{"threadName", "threadId", "daemon", "frames":
    ["file:line fn: code", ...]}, ...]`` innermost frame LAST. Pure
    interpreter introspection — safe to call while the process is wedged
    on a device wait."""
    names = {t.ident: (t.name, t.daemon) for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        name, daemon = names.get(ident, (str(ident), True))
        frames = [
            f"{os.path.basename(fs.filename)}:{fs.lineno} {fs.name}: "
            f"{(fs.line or '').strip()}"
            for fs in traceback.extract_stack(frame)]
        out.append({"threadName": name, "threadId": int(ident),
                    "daemon": bool(daemon),
                    "frames": frames[-max_frames:]})
    return out


def _bounded_probe(fn: Callable[[], Any], default: Any,
                   timeout_s: float = _PROBE_DEADLINE_S) -> Any:
    """Run an autopsy probe on a side thread with a deadline: if the
    probe itself blocks on the hung backend (e.g. ``jax.local_devices``
    waiting on the initialization that is the hang), report ``default``
    instead of hanging the diagnosis."""
    box: dict[str, Any] = {}

    def work() -> None:
        try:
            box["v"] = fn()
        except Exception as e:  # noqa: BLE001 — a broken probe must not lose the autopsy
            box["v"] = {"probeError": f"{type(e).__name__}: {e}"}

    t = threading.Thread(target=work, daemon=True,
                         name="transmogrifai-autopsy-probe")
    t.start()
    t.join(timeout_s)
    return box.get("v", default)


# -- bounded census (safe from monitors and scrape collectors) ---------------

_census_lock = threading.Lock()
_census_state: dict = {"census": None, "t": 0.0, "next_probe": 0.0}
#: after a census probe times out (hung backend), don't re-probe for
#: this long — each retry parks one daemon thread on the hung call, and
#: a 0.5s-cadence monitor must not accumulate them unboundedly
_CENSUS_BACKOFF_S = 30.0


def _empty_census() -> dict:
    return {"bytesInUse": 0, "peakBytesInUse": 0, "bytesLimit": 0,
            "devices": []}


def device_memory_census_bounded(max_age_s: float = 2.0,
                                 timeout_s: float = 2.0) -> dict:
    """The census through a small cache + side-thread deadline: safe to
    call from the stall monitor, the ResourceWatchdog tick, and scrape
    collectors — paths that must never block on the hung backend they
    exist to observe. A fresh cache entry is served directly; a probe
    that times out serves the last good census (zeros before any
    succeeded) and backs off ``_CENSUS_BACKOFF_S`` before probing again,
    so a wedged backend costs at most one parked daemon thread per
    backoff window."""
    now = time.monotonic()
    with _census_lock:
        cached = _census_state["census"]
        if cached is not None and now - _census_state["t"] <= max_age_s:
            return cached
        if now < _census_state["next_probe"]:
            return cached if cached is not None else _empty_census()
    probed = _bounded_probe(device_memory_census, None,
                            timeout_s=timeout_s)
    with _census_lock:
        if isinstance(probed, dict) and "probeError" not in probed:
            _census_state["census"] = probed
            _census_state["t"] = time.monotonic()
            _census_state["next_probe"] = 0.0
            return probed
        _census_state["next_probe"] = time.monotonic() + _CENSUS_BACKOFF_S
        return _census_state["census"] or _empty_census()


def device_memory_bounded() -> tuple[int, int]:
    """``(bytes_in_use, peak)`` from the bounded census — the scrape
    collectors' probe (``device_memory`` stays live/unbounded for the
    in-band per-phase/per-span samplers, which run on the thread doing
    the device work anyway)."""
    c = device_memory_census_bounded()
    return c["bytesInUse"], c["peakBytesInUse"]


# -- the dispatch ledger ------------------------------------------------------

class DispatchLedger:
    """Inventory of in-flight device work: dispatch/settle seams
    ``register`` a labeled entry when they start blocking on device
    futures and ``complete`` it when the wait resolves (or is
    abandoned). The autopsy's answer to "what was the device supposed to
    be doing" — family/group labels from the sweep's pending queue, rows
    for serving batches, names for collectives. Attrs are camelCase
    (they land verbatim in incident JSON). Disabled
    (``TRANSMOGRIFAI_DEVICEWATCH=0`` / ``configure(enabled=False)``)
    ``register`` returns ``None`` and the hot paths pay nothing — the
    whole observatory switches off together."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._entries: dict[int, dict] = {}
        self.enabled = os.environ.get(ENABLE_ENV, "1") != "0"
        self.registered = 0
        self.completed = 0

    def register(self, site: str, **attrs) -> Optional[int]:
        if not self.enabled:
            return None
        entry = {"site": site, "since": time.time()}
        entry.update(attrs)
        with self._lock:
            eid = next(self._ids)
            self._entries[eid] = entry
            self.registered += 1
        return eid

    def complete(self, eid: Optional[int]) -> None:
        if eid is None:
            return
        with self._lock:
            if self._entries.pop(eid, None) is not None:
                self.completed += 1

    def inventory(self) -> list[dict]:
        """The in-flight entries, oldest first, with ages."""
        now = time.time()
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: e["since"])
        out = []
        for e in entries:
            doc = {k: v for k, v in e.items() if k != "since"}
            doc["ageSeconds"] = round(now - e["since"], 3)
            out.append(doc)
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def reset(self) -> None:
        with self._lock:
            self._entries = {}
            self.registered = 0
            self.completed = 0


dispatch_ledger = DispatchLedger()


# -- compile telemetry --------------------------------------------------------

class CompileTelemetry:
    """XLA compile observability, the ONE ``jax.monitoring`` listener of
    the program: wall per backend compile attributed to the active
    :meth:`building` site, recorded as a retroactive
    ``compile.program:<site>`` span and the ``transmogrifai_compile_*``
    series; compiles slower than the ``TRANSMOGRIFAI_SLOW_COMPILE_S``
    threshold (default 10s) additionally emit a ``compile.slow``
    flight-recorder event + warning.

    A persistent-cache hit fires the SAME duration event on this JAX
    (``pxla`` times ``compile_or_get_cached`` as a whole), preceded on
    the same thread by ``/jax/compilation_cache/cache_hits``. The
    listener hears both and tells them apart: a duration event that
    follows a hit is a cache LOAD (``compile.cache_load:<site>`` span,
    ``cacheLoads``/``loadSeconds`` in ``by_site``), any other a compile
    — so a warm re-run reports 0 compiles and its loads by site.
    ``by_family`` counts real compiles per sweep family (``building(...,
    family=)``; nested sites inherit it) — ``SweepCounters`` reads its
    per-family ``compiles`` from there. ``record_program_cost`` stores
    :func:`analyze_program` results (FLOPs, bytes, HLO size) from cold
    seams that hold a program handle."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self, max_records: int = 512):
        self._lock = threading.Lock()
        self._listening = False
        #: (site, family) of the innermost open ``building`` block
        self._site: contextvars.ContextVar[tuple] = \
            contextvars.ContextVar("transmogrifai_compile_site",
                                   default=(None, None))
        #: cache hits heard on this thread since its last duration event
        self._hits = threading.local()
        self.records: deque = deque(maxlen=int(max_records))
        self.programs = 0
        self.cache_loads = 0
        self.wall_s = 0.0
        self.max_wall_s = 0.0
        self.slow = 0
        self.in_progress = 0
        self.by_site: dict[str, dict] = {}
        self.by_family: dict[str, int] = {}
        self.program_costs: dict[str, dict] = {}

    def reset(self) -> None:
        with self._lock:
            self.records.clear()
            self.programs = 0
            self.cache_loads = 0
            self.wall_s = 0.0
            self.max_wall_s = 0.0
            self.slow = 0
            self.by_site = {}
            self.by_family = {}
            self.program_costs = {}

    @staticmethod
    def slow_threshold_s() -> float:
        return _env_float(SLOW_COMPILE_ENV, 10.0)

    def ensure_listener(self) -> None:
        """Register the process-wide monitoring listeners once. Compiles
        stay 0 when the API is absent (never retried). The check-and-set
        runs under the lock: listeners can never unregister, so a double
        registration would double-count every compile for the process
        lifetime."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        try:
            import jax.monitoring as monitoring
            monitoring.register_event_listener(self._on_cache_event)
            monitoring.register_event_duration_secs_listener(
                self._on_event)
        except Exception:  # failure-ok: monitoring API absent — compiles stay 0
            pass

    @contextlib.contextmanager
    def building(self, site: str, family: Optional[str] = None):
        """Attribute backend compiles and cache loads to ``site`` while
        the block runs (thread/task-local; the innermost block wins), and
        mark a program build in progress — the autopsy's "what was
        compiling" answer. ``family`` names the sweep family the build
        belongs to; a nested block without one keeps its parent's."""
        self.ensure_listener()
        if family is None:
            family = self._site.get()[1]
        token = self._site.set((site, family))
        with self._lock:
            self.in_progress += 1
        try:
            yield
        finally:
            with self._lock:
                self.in_progress -= 1
            self._site.reset(token)

    def family_compiles(self) -> dict[str, int]:
        """Real compiles per sweep family, process lifetime."""
        with self._lock:
            return dict(self.by_family)

    def _on_cache_event(self, event: str, **kw) -> None:
        if event == self.CACHE_HIT_EVENT:
            self._hits.n = getattr(self._hits, "n", 0) + 1

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event != self.COMPILE_EVENT:
            return
        loaded = getattr(self._hits, "n", 0) > 0
        if loaded:
            self._hits.n -= 1
        site, family = self._site.get()
        site = site or "unattributed"
        program = kw.get("fun_name")
        now = time.time()
        wall = float(duration)
        slow = False
        with self._lock:
            per = self.by_site.setdefault(
                site, {"programs": 0, "wallSeconds": 0.0,
                       "cacheLoads": 0, "loadSeconds": 0.0})
            if loaded:
                self.cache_loads += 1
                per["cacheLoads"] += 1
                per["loadSeconds"] += wall
            else:
                self.programs += 1
                self.wall_s += wall
                self.max_wall_s = max(self.max_wall_s, wall)
                per["programs"] += 1
                per["wallSeconds"] += wall
                if family is not None:
                    self.by_family[family] = \
                        self.by_family.get(family, 0) + 1
                slow = wall >= self.slow_threshold_s()
                if slow:
                    self.slow += 1
            self.records.append({"site": site, "wallSeconds": wall,
                                 "ts": now, "cacheLoad": loaded,
                                 "program": program})
        kind = "compile.cache_load" if loaded else "compile.program"
        try:
            from transmogrifai_tpu.utils.tracing import recorder
            # the site rides in the NAME: consumers that keep span names
            # only (the benchmark's idle-gap table) still read it
            recorder.add(f"{kind}:{site}", now - wall, now, site=site,
                         program=program)
        except Exception:  # failure-ok: span recording is optional telemetry
            pass
        if slow:
            try:
                from transmogrifai_tpu.utils.events import events
                events.emit("compile.slow", site=site,
                            wallSeconds=round(wall, 3),
                            thresholdSeconds=self.slow_threshold_s())
            except Exception:  # failure-ok: event emission is optional telemetry
                pass
            warnings.warn(
                f"slow XLA compile at {site}: {wall:.1f}s (threshold "
                f"{self.slow_threshold_s():g}s) — a compile storm or a "
                "pathological HLO shape", RuntimeWarning)

    def record_program_cost(self, site: str, cost: dict) -> None:
        """Store one program's :func:`analyze_program` result and emit
        the ``compile.program`` event carrying it (cold seams only)."""
        if not cost:
            return
        with self._lock:
            self.program_costs[site] = dict(cost)
        try:
            from transmogrifai_tpu.utils.events import events
            events.emit("compile.program", site=site, **cost)
        except Exception:  # failure-ok: event emission is optional telemetry
            pass

    def to_json(self) -> dict:
        with self._lock:
            return {"programs": self.programs,
                    "cacheLoads": self.cache_loads,
                    "wallSeconds": round(self.wall_s, 4),
                    "maxWallSeconds": round(self.max_wall_s, 4),
                    "slowCompiles": self.slow,
                    "inProgress": self.in_progress,
                    "bySite": {k: dict(v)
                               for k, v in sorted(self.by_site.items())},
                    "programCosts": {k: dict(v) for k, v
                                     in sorted(self.program_costs.items())}}


compile_telemetry = CompileTelemetry()


def analyze_program(fn, *args, **kwargs) -> dict:
    """Best-effort static cost report for a jitted callable at concrete
    args: ``{"flops", "bytesAccessed", "hloTextBytes"}`` (whichever are
    available; ``{}`` when the callable exposes no ``lower``). Lowering
    re-traces on host (no backend compile) — call from cold seams
    (warmup, program build), never per dispatch."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return {}
    try:
        lowered = lower(*args, **kwargs)
    except Exception:  # failure-ok: cost analysis is optional telemetry
        return {}
    out: dict = {}
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if "flops" in ca:
                out["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                out["bytesAccessed"] = float(ca["bytes accessed"])
    except Exception:  # failure-ok: cost analysis is version-dependent
        pass
    try:
        out["hloTextBytes"] = len(lowered.as_text())
    except Exception:  # failure-ok: HLO text rendering is optional
        pass
    return out


# -- the HBM timeline ---------------------------------------------------------

_timeline_lock = threading.Lock()
_timeline: deque = deque(maxlen=4096)


def sample_hbm(t: Optional[float] = None) -> int:
    """One all-device bytes-in-use sample appended to the bounded HBM
    timeline (merged into the chrome-trace export as a counter track).
    Low-rate by construction: callers are the ResourceWatchdog tick and
    the stall monitor's poll — never a hot path. Routed through the
    BOUNDED census: a monitor sampling a hung backend must serve the
    last good value, not wedge on the hang it is watching."""
    used = device_memory_census_bounded()["bytesInUse"]
    with _timeline_lock:
        _timeline.append((t if t is not None else time.time(), used))
    return used


def hbm_timeline() -> list[tuple[float, int]]:
    with _timeline_lock:
        return list(_timeline)


def reset_run() -> None:
    """Per-run state reset (called by ``profiler.reset``): the HBM
    timeline covers exactly one run's chrome trace. Watchdog/ledger/
    compile counters are process-lifetime (Prometheus monotonicity)."""
    with _timeline_lock:
        _timeline.clear()


# -- the autopsy --------------------------------------------------------------

def build_autopsy(wait: Optional[dict] = None) -> dict:
    """Assemble the autopsy document (pure — no events, no counters, no
    files; the watchdog and the metric-name lint both call this).
    Thread stacks and the dispatch ledger are pure interpreter state;
    the HBM/live-buffer probes run behind their own small deadlines so a
    hung backend cannot hang its own diagnosis."""
    doc: dict = {
        "at": time.time(),
        "threadStacks": thread_stacks(),
        "pendingDispatches": dispatch_ledger.inventory(),
        "hbmCensus": _bounded_probe(device_memory_census,
                                    {"unavailable": True}),
        "liveBuffers": _bounded_probe(live_buffer_census,
                                      {"unavailable": True}),
        "compile": compile_telemetry.to_json(),
    }
    if wait is not None:
        doc["wait"] = {
            "name": wait.get("name"),
            "site": wait.get("site"),
            "timeoutSeconds": wait.get("timeoutS"),
            "elapsedSeconds": round(time.time() - wait.get("t0",
                                                           time.time()), 3),
            "thread": wait.get("thread"),
            "attrs": dict(wait.get("attrs") or {}),
        }
    return doc


# -- the dispatch watchdog ----------------------------------------------------

class DispatchWatchdog:
    """Deadline monitor for blocking device waits (module docstring).

    One monitor thread polls the armed-wait registry; an expired wait
    fires ONE autopsy (``device.stall`` event + optional incident dump)
    and the wait keeps waiting — raising stays the caller's own deadline
    logic. Exiting a :meth:`guard` block, normally OR via an exception
    (an OOM-rung retry re-dispatching down the degradation ladder),
    disarms its deadline. Per-wait cost: two dict ops under a lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._waits: dict[int, dict] = {}
        self._monitor: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self.enabled = os.environ.get(ENABLE_ENV, "1") != "0"
        self.incident_dir: Optional[str] = \
            os.environ.get(INCIDENT_DIR_ENV) or None
        self.poll_interval_s = 0.5
        self._default_timeout_s: Optional[float] = None
        self.scrape_fn: Optional[Callable[[], str]] = None
        # counters (exported as transmogrifai_device_* series)
        self.guards = 0
        self.stalls = 0
        self.stalls_by_site: dict[str, int] = {}
        self.autopsies = 0
        self.last_autopsy: Optional[dict] = None

    # -- configuration -------------------------------------------------------
    def configure(self, *, enabled: Optional[bool] = None,
                  incident_dir: Optional[str] = None,
                  stall_timeout_s: Optional[float] = None,
                  poll_interval_s: Optional[float] = None,
                  scrape_fn: Optional[Callable[[], str]] = None
                  ) -> "DispatchWatchdog":
        if enabled is not None:
            self.enabled = bool(enabled)
        if incident_dir is not None:
            self.incident_dir = incident_dir or None
        if stall_timeout_s is not None:
            self._default_timeout_s = float(stall_timeout_s)
        if poll_interval_s is not None:
            self.poll_interval_s = max(float(poll_interval_s), 0.01)
            # interrupt a monitor mid-sleep so a shortened interval
            # takes effect now, not after the previous (longer) wait
            self._wake.set()
        if scrape_fn is not None:
            self.scrape_fn = scrape_fn
        return self

    def default_timeout_s(self) -> float:
        """Default stall deadline: 600s, deliberately matched to the
        collective deadline default (``TRANSMOGRIFAI_COLLECTIVE_TIMEOUT_S``)
        — a healthy large-shape settle on a slow CPU fallback can block
        for minutes, and a fired autopsy on a merely-slow wait is
        misleading evidence. Accelerator deployments (where a settle is
        seconds) should LOWER it via ``TRANSMOGRIFAI_STALL_TIMEOUT_S``;
        note expiry only observes — the wait always continues."""
        if self._default_timeout_s is not None:
            return self._default_timeout_s
        return _env_float(STALL_TIMEOUT_ENV, 600.0)

    def reset_counters(self) -> None:
        with self._lock:
            self.guards = 0
            self.stalls = 0
            self.stalls_by_site = {}
            self.autopsies = 0
            self.last_autopsy = None

    def active_waits(self) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self._waits.values()]

    # -- arming --------------------------------------------------------------
    @contextlib.contextmanager
    def guard(self, name: str, *, timeout_s: Optional[float] = None,
              site: Optional[str] = None, **attrs):
        """Arm a stall deadline around a blocking device wait. Expiry
        fires one autopsy and the block keeps waiting; exit (normal or
        exceptional) disarms. ``attrs`` are camelCase labels for the
        autopsy's wait record."""
        if not self.enabled:
            yield None
            return
        timeout = (timeout_s if timeout_s is not None
                   else self.default_timeout_s())
        if timeout <= 0:
            yield None
            return
        entry = {"name": name, "site": site or name,
                 "timeoutS": float(timeout), "t0": time.time(),
                 "deadline": time.monotonic() + timeout,
                 "thread": threading.current_thread().name,
                 "fired": False, "attrs": attrs}
        with self._lock:
            wid = next(self._ids)
            self._waits[wid] = entry
            self.guards += 1
        self._ensure_monitor()
        try:
            yield wid
        finally:
            with self._lock:
                self._waits.pop(wid, None)

    # -- the monitor ---------------------------------------------------------
    def _ensure_monitor(self) -> None:
        # unlocked fast path: at batch-dispatch rate the monitor is
        # almost always already alive, and waking it per guard arm would
        # make it iterate per BATCH instead of per poll interval (a
        # deadline is seconds-scale; the 0.5s poll covers a fresh wait).
        # The benign race falls through to the locked re-check.
        m = self._monitor
        if m is not None and m.is_alive():
            return
        with self._lock:
            if self._monitor is not None and self._monitor.is_alive():
                return
            self._monitor = threading.Thread(
                target=self._monitor_loop,
                name="transmogrifai-dispatch-watchdog", daemon=True)
            self._monitor.start()

    def _monitor_loop(self) -> None:
        idle_since: Optional[float] = None
        while True:
            self._wake.wait(timeout=self.poll_interval_s)
            self._wake.clear()
            now = time.monotonic()
            to_fire: list[dict] = []
            with self._lock:
                if not self._waits:
                    if idle_since is None:
                        idle_since = now
                    elif now - idle_since > 60.0:
                        # nothing armed for a minute: the thread exits;
                        # the next guard restarts it lazily
                        self._monitor = None
                        return
                    continue
                idle_since = None
                for e in self._waits.values():
                    if not e["fired"] and now >= e["deadline"]:
                        e["fired"] = True
                        to_fire.append(e)
            # autopsies fire BEFORE the timeline sample: on a hung
            # backend even the bounded sample spends its deadline, and
            # the diagnosis must never queue behind telemetry
            for e in to_fire:
                try:
                    self._fire(e)
                except Exception as ex:  # noqa: BLE001 — a broken autopsy must not kill the monitor
                    warnings.warn(
                        f"devicewatch autopsy failed "
                        f"({type(ex).__name__}: {ex})", RuntimeWarning)
            # low-rate HBM timeline while waits are armed (autopsy-free
            # runs still get the counter track around their settles)
            try:
                sample_hbm()
            except Exception:  # failure-ok: the timeline is optional telemetry
                pass

    def _fire(self, entry: dict) -> None:
        self.stall_autopsy(
            f"device.stall:{entry['site']}", site=entry["site"],
            wait=entry)

    # -- the autopsy surface -------------------------------------------------
    def stall_autopsy(self, reason: str, *, site: str,
                      wait: Optional[dict] = None,
                      extra: Optional[dict] = None) -> dict:
        """Fire one autopsy for a stalled/expired wait: count the stall,
        emit the ``device.stall`` event, warn, and freeze an incident
        dump when an incident dir is configured. Called by the monitor
        on guard expiry and by ``run_with_deadline`` before raising
        ``CollectiveTimeoutError``. Returns the autopsy document (with
        ``incidentPath`` when one was written)."""
        doc = build_autopsy(wait=wait)
        doc["reason"] = reason
        if extra:
            doc.update(extra)
        with self._lock:
            self.stalls += 1
            self.stalls_by_site[site] = self.stalls_by_site.get(site, 0) + 1
            self.autopsies += 1
            self.last_autopsy = doc
        census = doc.get("hbmCensus") or {}
        try:
            from transmogrifai_tpu.utils.events import events
            events.emit(
                "device.stall", site=site,
                waitName=(wait or {}).get("name"),
                elapsedSeconds=(doc.get("wait") or {}).get(
                    "elapsedSeconds"),
                pendingDispatches=len(doc.get("pendingDispatches") or []),
                hbmBytesInUse=census.get("bytesInUse"),
                threads=len(doc.get("threadStacks") or []))
        except Exception:  # failure-ok: event emission is optional telemetry
            pass
        warnings.warn(
            f"device stall at {site}: blocking wait exceeded its "
            f"deadline ({reason}); autopsy captured "
            f"{len(doc.get('pendingDispatches') or [])} pending "
            "dispatch(es)", RuntimeWarning)
        if self.incident_dir:
            from transmogrifai_tpu.utils.events import dump_incident
            path = dump_incident(self.incident_dir, reason,
                                 scrape_fn=self.scrape_fn,
                                 extra={"autopsy": doc})
            doc["incidentPath"] = path
        return doc

    def to_json(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "guards": self.guards,
                    "stalls": self.stalls,
                    "stallsBySite": dict(self.stalls_by_site),
                    "autopsies": self.autopsies,
                    "activeWaits": len(self._waits),
                    "incidentDir": self.incident_dir}


watchdog = DispatchWatchdog()


def guard(name: str, *, timeout_s: Optional[float] = None,
          site: Optional[str] = None, **attrs):
    """Module-level convenience over the process-global watchdog."""
    return watchdog.guard(name, timeout_s=timeout_s, site=site, **attrs)


def configure(**kw) -> DispatchWatchdog:
    """Configure the process-global observatory. ``enabled`` flips the
    watchdog AND the dispatch ledger together — off means the hot paths
    pay nothing at all."""
    if kw.get("enabled") is not None:
        dispatch_ledger.enabled = bool(kw["enabled"])
    return watchdog.configure(**kw)


def stall_autopsy(reason: str, *, site: str,
                  wait: Optional[dict] = None,
                  extra: Optional[dict] = None) -> dict:
    return watchdog.stall_autopsy(reason, site=site, wait=wait,
                                  extra=extra)
