"""Prometheus text-exposition rendering of the process's metrics.

A tiny pull-model registry (no client_golang-style dependency): every
counter the framework already keeps — ``AppMetrics`` phases/stages,
``RunCounters``, ``SweepCounters``, and a server's ``ServingMetrics``
(latency-histogram buckets, queue depth, degraded gauge, per-padding-
bucket compiles) — renders into Prometheus text exposition format 0.0.4
on demand. ``serving/http.py`` serves the output at ``GET /metrics``.

Naming contract (linted by ``scripts/check_metric_names.py``):

- every metric name is ``snake_case`` with the ``transmogrifai_`` prefix,
- names are registry-unique,
- counters (monotonic within a run) end in ``_total``; gauges don't;
  histograms expose the standard ``_bucket``/``_sum``/``_count`` series.

Collection is lazy: each metric holds a ``collect()`` closure over the
live objects, so a scrape always reads current values and registering
costs nothing on the serving hot path.
"""

from __future__ import annotations

import functools
import os
import re
import time
from typing import Callable, Optional

__all__ = ["PromRegistry", "build_registry", "CONTENT_TYPE"]

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

#: process birth (this module imports with the package): the uptime
#: gauge's zero — restarts reset it, which is exactly what makes fleet
#: scrapes correlatable across restarts (a counter that dropped AND
#: uptime near zero = the process bounced, not the workload)
_PROCESS_T0 = time.monotonic()

_NAME_RE = re.compile(r"^transmogrifai_[a-z0-9]+(_[a-z0-9]+)*$")
_TYPES = ("counter", "gauge", "histogram")


def _escape(v) -> str:
    return (str(v).replace("\\", r"\\").replace('"', r"\"")
            .replace("\n", r"\n"))


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_value(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Metric:
    def __init__(self, name: str, mtype: str, help_: str,
                 collect: Callable[[], list]):
        self.name = name
        self.mtype = mtype
        self.help = help_
        self.collect = collect


class PromRegistry:
    """Named metrics + their collectors; renders text exposition."""

    def __init__(self):
        self._metrics: dict[str, _Metric] = {}

    def register(self, name: str, mtype: str, help_: str,
                 collect: Callable[[], list]) -> None:
        """``collect()`` returns ``[(labels_dict, value), ...]``; for
        histograms the value is ``{"buckets": {le: cumulative}, "sum":
        s, "count": n}``. Registration enforces the naming contract —
        a bad name is a bug, not a formatting choice."""
        if mtype not in _TYPES:
            raise ValueError(f"metric type {mtype!r}: one of {_TYPES}")
        if not _NAME_RE.match(name):
            raise ValueError(
                f"metric name {name!r} must be snake_case with the "
                "transmogrifai_ prefix")
        if mtype == "counter" and not name.endswith("_total"):
            raise ValueError(
                f"counter {name!r} must carry the _total suffix "
                "(monotonic-counter naming convention)")
        if mtype != "counter" and name.endswith("_total"):
            raise ValueError(
                f"{mtype} {name!r} must NOT end in _total (reserved for "
                "counters)")
        if name in self._metrics:
            raise ValueError(f"metric {name!r} already registered")
        self._metrics[name] = _Metric(name, mtype, help_, collect)

    def names(self) -> list[str]:
        return sorted(self._metrics)

    def metric_types(self) -> dict[str, str]:
        return {m.name: m.mtype for m in self._metrics.values()}

    def render(self) -> str:
        """The whole registry in exposition format; a collector that
        raises is skipped with a comment line instead of failing the
        scrape (one broken gauge must not take down /metrics)."""
        lines: list[str] = []
        for name in self.names():
            m = self._metrics[name]
            lines.append(f"# HELP {m.name} {_escape(m.help)}")
            lines.append(f"# TYPE {m.name} {m.mtype}")
            try:
                samples = m.collect()
            except Exception as e:  # noqa: BLE001 — surfaced as a scrape comment
                lines.append(f"# collect failed: {type(e).__name__}: "
                             f"{_escape(e)}")
                continue
            for labels, value in samples:
                if m.mtype == "histogram":
                    for le, n in value["buckets"].items():
                        lines.append(
                            f"{m.name}_bucket"
                            f"{_fmt_labels({**labels, 'le': le})} {int(n)}")
                    lines.append(f"{m.name}_sum{_fmt_labels(labels)} "
                                 f"{_fmt_value(value['sum'])}")
                    lines.append(f"{m.name}_count{_fmt_labels(labels)} "
                                 f"{int(value['count'])}")
                else:
                    lines.append(f"{m.name}{_fmt_labels(labels)} "
                                 f"{_fmt_value(value)}")
        return "\n".join(lines) + "\n"


@functools.lru_cache(maxsize=1)
def _build_info_labels() -> dict:
    """One stable label set per process (version/platform provenance);
    cached — VersionInfo shells out to git on first call."""
    try:
        from transmogrifai_tpu.utils.version import VersionInfo
        info = VersionInfo.to_json()
    except Exception:  # noqa: BLE001 — build info must never break a scrape
        info = {}
    import platform as _platform
    return {"version": str(info.get("version") or "unknown"),
            "git_commit": str(info.get("gitCommit") or "unknown"),
            "jax_version": str(info.get("jaxVersion") or "unknown"),
            "backend": str(info.get("backend") or "unknown"),
            "python_version": _platform.python_version()}


def _process_collectors(reg: PromRegistry) -> None:
    """Series every registry carries: build provenance + uptime, so any
    fleet member's scrape is correlatable across restarts and versions
    (the Prometheus ``*_build_info`` convention: constant 1, labels
    carry the facts, dashboards ``join`` on them)."""
    reg.register(
        "transmogrifai_build_info", "gauge",
        "constant 1; labels carry version/git/jax/backend provenance",
        lambda: [(_build_info_labels(), 1)])
    reg.register(
        "transmogrifai_process_uptime_seconds", "gauge",
        "seconds since this process imported the framework",
        lambda: [({}, time.monotonic() - _PROCESS_T0)])


def _event_collectors(reg: PromRegistry) -> None:
    """The flight recorder's own accounting (``utils/events.py``): how
    much history the black box holds and whether it is losing any."""
    from transmogrifai_tpu.utils.events import events

    for attr, name, help_ in (
            ("emitted", "emitted", "wide events recorded"),
            ("dropped", "dropped", "events evicted from the bounded "
                                   "ring (oldest-first)"),
            ("spilled", "spilled", "events written to the durable JSONL "
                                   "spill"),
            ("spill_lost", "spill_lost", "events lost to spill write "
                                         "failures (the JSONL has "
                                         "holes)"),
            ("suppressed", "suppressed", "events withheld by rate "
                                         "limiting")):
        reg.register(f"transmogrifai_events_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(events, a))])
    reg.register("transmogrifai_events_ring_size", "gauge",
                 "events currently retained in the ring",
                 lambda: [({}, len(events))])


def _resource_collectors(reg: PromRegistry) -> None:
    """The ``transmogrifai_resource_*`` surface (``utils/resources.py``):
    degradation-ladder rungs taken (labeled by site), OOM/ENOSPC event
    counts, skipped best-effort writes, and live host-pressure gauges
    (RSS, free disk, 0/1 pressure against the configured budgets).
    Carried by EVERY registry, like the flight-recorder series — an
    operator must see pressure on whatever endpoint they already
    scrape."""
    from transmogrifai_tpu.utils import resources
    rc = resources.resource_counters

    reg.register(
        "transmogrifai_resource_degradations_total", "counter",
        "degradation-ladder rungs taken, by failing site",
        lambda: [({"site": s}, n)
                 for s, n in sorted(rc.to_json()
                                    ["degradationsBySite"].items())]
                or [({"site": "none"}, 0)])
    for attr, name, help_ in (
            ("oom_events", "oom_events",
             "RESOURCE_EXHAUSTED / allocator-OOM errors observed"),
            ("enospc_events", "enospc_events",
             "full-disk (ENOSPC) write failures observed"),
            ("writes_skipped", "writes_skipped",
             "best-effort durable writes skipped under the ENOSPC "
             "cooldown")):
        reg.register(f"transmogrifai_resource_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(rc, a))])
    reg.register(
        "transmogrifai_resource_rss_bytes", "gauge",
        "resident set size of this process",
        lambda: [({}, resources.rss_bytes())])
    reg.register(
        "transmogrifai_resource_disk_free_bytes", "gauge",
        "free bytes on the working filesystem (-1 = probe failed)",
        lambda: [({}, resources.disk_free_bytes())])
    def _pressure_samples():
        state = resources.pressure_state()
        return [({"kind": "rss"}, 1 if state["rssPressure"] else 0),
                ({"kind": "disk"}, 1 if state["diskPressure"] else 0)]

    reg.register(
        "transmogrifai_resource_pressure", "gauge",
        "1 while the sampled value breaches its configured budget",
        _pressure_samples)
    reg.register(
        "transmogrifai_resource_ladder_enabled", "gauge",
        "1 while the adaptive degradation ladder is enabled "
        "(TRANSMOGRIFAI_RESOURCE_LADDER)",
        lambda: [({}, 1 if resources.ladder_enabled() else 0)])


def _net_collectors(reg: PromRegistry) -> None:
    """The network data plane's ``transmogrifai_net_*`` surface
    (``serving/aiohttp_core.net_counters``): slow-client sheds, idle
    reaps, write-deadline aborts, connection-gate sheds, injected
    socket faults, idempotency dedupe hits/waits, and the router's
    hedge/retry classification counters. Carried by EVERY registry —
    chaos drills read these off whatever endpoint is already
    scraped."""
    from transmogrifai_tpu.serving.aiohttp_core import net_counters

    for attr, help_ in (
            ("accepted", "connections accepted by the event-loop "
                         "front"),
            ("shed_connections", "connections shed at the bounded "
                                 "accept gate (503 + Retry-After)"),
            ("slow_clients_shed", "requests shed by the header/body "
                                  "read deadline (slowloris defense; "
                                  "answered 408)"),
            ("idle_closed", "idle keep-alive connections reaped "
                            "silently"),
            ("write_timeouts", "replies aborted by the write deadline "
                               "(dead/slow peer)"),
            ("faults_injected", "socket faults delivered by the "
                                "netchaos proxy in this process"),
            ("dedupe_hits", "retried requests answered from the "
                            "idempotency ring instead of re-scored"),
            ("dedupe_waits", "duplicate requests that waited on the "
                             "original in-flight execution"),
            ("hedges", "tail-latency hedge requests launched to a "
                       "ring successor"),
            ("resets_retried", "mid-request transport failures "
                               "retried under an idempotency key"),
            ("refusals_spilled", "connect-refused replicas spilled "
                                 "past immediately (no retry budget "
                                 "charged)")):
        reg.register(f"transmogrifai_net_{attr}_total", "counter",
                     help_,
                     lambda a=attr: [({}, getattr(net_counters, a))])


def _ingest_collectors(reg: PromRegistry) -> None:
    """The fused-ingest/FE surface (round 14, ``utils/profiling.
    IngestCounters``): fused vs host-side FE stage-rows, fused program
    dispatches + OOM fallbacks, streaming prefetch accounting (chunks,
    decode seconds, consumer blocked seconds, live overlap ratio), the
    device-frame cache's reuse/store/pressure-drop counters, and the
    already-sharded device_put skips the pre-partitioned sweep handoff
    counts. Carried by EVERY registry, like the resource series."""
    from transmogrifai_tpu.dag import fe_fused_enabled
    from transmogrifai_tpu.utils.profiling import ingest_counters as ic

    for attr, name, help_ in (
            ("fe_fused_programs", "fe_fused_programs",
             "fused FE segment programs dispatched"),
            ("fe_fused_stages", "fe_fused_stages",
             "device transformer stages executed inside fused programs"),
            ("fe_fused_rows", "fe_fused_rows",
             "stage-rows (rows x stages) transformed by fused programs"),
            ("fe_host_rows", "fe_host_rows",
             "stage-rows transformed by the stagewise/host FE path"),
            ("fe_host_fallbacks", "fe_host_fallbacks",
             "fused segments degraded to the stagewise rung (OOM)"),
            ("chunks_prefetched", "chunks_prefetched",
             "ingest chunks decoded ahead by the prefetch thread"),
            ("frame_cache_reuses", "frame_cache_reuses",
             "device-frame cache hits (host->device transfer skipped)"),
            ("frame_cache_stores", "frame_cache_stores",
             "device frames registered in the cache"),
            ("frame_cache_drops", "frame_cache_drops",
             "cached device frames released under memory pressure"),
            ("presharded_skips", "presharded_skips",
             "device_puts skipped because the operand already carried "
             "the target sharding")):
        reg.register(f"transmogrifai_ingest_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(ic, a))])
    reg.register(
        "transmogrifai_ingest_prefetch_wait_seconds", "gauge",
        "cumulative consumer seconds blocked waiting on the prefetch "
        "queue", lambda: [({}, ic.prefetch_wait_s)])
    reg.register(
        "transmogrifai_ingest_decode_seconds", "gauge",
        "cumulative background decode seconds spent by the prefetcher",
        lambda: [({}, ic.decode_s)])

    def _overlap():
        # decode seconds the consumer did NOT wait for = overlapped work;
        # 1.0 = decode fully hidden behind device compute
        d = ic.decode_s
        if d <= 0:
            return [({}, 0.0)]
        return [({}, max(0.0, min(1.0, (d - ic.prefetch_wait_s) / d)))]

    reg.register(
        "transmogrifai_ingest_overlap_ratio", "gauge",
        "fraction of prefetch decode seconds hidden behind consumer "
        "compute (1 = fully overlapped)", _overlap)
    reg.register(
        "transmogrifai_ingest_fe_fused_enabled", "gauge",
        "1 while fused FE is enabled (TRANSMOGRIFAI_FE_FUSED)",
        lambda: [({}, 1 if fe_fused_enabled() else 0)])


def _devicewatch_collectors(reg: PromRegistry) -> None:
    """The device-execution observatory (``utils/devicewatch.py``):
    dispatch-watchdog stall accounting, the in-flight dispatch ledger,
    the all-device HBM census gauges, and the ``transmogrifai_compile_*``
    compile-telemetry series. Carried by EVERY registry, like the
    flight-recorder and resource series — a wedged device must be
    visible on whatever endpoint an operator already scrapes."""
    from transmogrifai_tpu.utils import devicewatch as dw

    # collectors go through the LOCKED to_json() copies, never the live
    # dicts: a scrape iterating by_site while a compile lands would raise
    # dictionary-changed-size (same discipline as the resource series)
    reg.register(
        "transmogrifai_device_stalls_total", "counter",
        "blocking device waits that exceeded their stall deadline, by "
        "guarded site",
        lambda: [({"site": s}, n)
                 for s, n in sorted(
                     dw.watchdog.to_json()["stallsBySite"].items())]
                or [({"site": "none"}, 0)])
    reg.register(
        "transmogrifai_device_guarded_waits_total", "counter",
        "blocking device waits armed under the dispatch watchdog",
        lambda: [({}, dw.watchdog.guards)])
    reg.register(
        "transmogrifai_device_autopsies_total", "counter",
        "stall autopsies fired (device.stall events / incident dumps)",
        lambda: [({}, dw.watchdog.autopsies)])
    reg.register(
        "transmogrifai_device_watch_enabled", "gauge",
        "1 while the dispatch watchdog is enabled "
        "(TRANSMOGRIFAI_DEVICEWATCH)",
        lambda: [({}, 1 if dw.watchdog.enabled else 0)])
    reg.register(
        "transmogrifai_device_pending_dispatches", "gauge",
        "device dispatches currently in flight (ledger entries)",
        lambda: [({}, len(dw.dispatch_ledger))])
    # bounded census: a scrape of a wedged backend serves the last good
    # sample instead of hanging /metrics exactly when it matters most
    reg.register(
        "transmogrifai_device_hbm_bytes_in_use", "gauge",
        "bytes in use summed across every local device (bounded census; "
        "0 when the backend exposes no memory stats)",
        lambda: [({}, dw.device_memory_bounded()[0])])
    reg.register(
        "transmogrifai_device_hbm_peak_bytes", "gauge",
        "peak bytes in use summed across every local device",
        lambda: [({}, dw.device_memory_bounded()[1])])
    # one locked snapshot shared by both compile collectors per scrape
    # (the same short-memo trick the SLO collectors use) — to_json()
    # copies the whole telemetry map, and doing it twice per scrape
    # doubles lock contention with the compile path's _on_event
    memo = {"t": 0.0, "v": None}

    def _by_site():
        now = time.monotonic()
        if memo["v"] is None or now - memo["t"] > 0.25:
            memo["v"] = dw.compile_telemetry.to_json()["bySite"]
            memo["t"] = now
        return memo["v"]

    reg.register(
        "transmogrifai_compile_programs_total", "counter",
        "XLA backend compiles observed (cache loads apart), by attributed "
        "site",
        lambda: [({"site": s}, v["programs"])
                 for s, v in sorted(_by_site().items())]
                or [({"site": "none"}, 0)])
    reg.register(
        "transmogrifai_compile_wall_seconds_total", "counter",
        "XLA backend compile wall seconds, by attributed site",
        lambda: [({"site": s}, v["wallSeconds"])
                 for s, v in sorted(_by_site().items())]
                or [({"site": "none"}, 0)])
    reg.register(
        "transmogrifai_compile_cache_loads_total", "counter",
        "programs served by the persistent compilation cache, by "
        "attributed site",
        lambda: [({"site": s}, v["cacheLoads"])
                 for s, v in sorted(_by_site().items())]
                or [({"site": "none"}, 0)])
    reg.register(
        "transmogrifai_compile_cache_load_seconds_total", "counter",
        "wall seconds spent loading programs from the persistent "
        "compilation cache, by attributed site",
        lambda: [({"site": s}, v["loadSeconds"])
                 for s, v in sorted(_by_site().items())]
                or [({"site": "none"}, 0)])
    reg.register(
        "transmogrifai_compile_slow_total", "counter",
        "backend compiles over the slow threshold "
        "(TRANSMOGRIFAI_SLOW_COMPILE_S)",
        lambda: [({}, dw.compile_telemetry.slow)])
    reg.register(
        "transmogrifai_compile_in_progress", "gauge",
        "program builds currently in flight (building() blocks open)",
        lambda: [({}, dw.compile_telemetry.in_progress)])
    reg.register(
        "transmogrifai_compile_max_wall_seconds", "gauge",
        "slowest backend compile observed this process",
        lambda: [({}, dw.compile_telemetry.max_wall_s)])


def _slo_collectors(reg: PromRegistry, engine) -> None:
    """The ``transmogrifai_slo_*`` surface over a ``utils.slo.SLOEngine``:
    targets, per-(alert, window) burn rates, and 0/1 alert states —
    enough for dashboards to chart budget burn and for an external
    alertmanager to mirror the engine's own firing decisions. The three
    gauge collectors share one short-lived memo so a single scrape runs
    a single engine evaluation (not three)."""
    memo = {"t": 0.0, "v": None}

    def samples(key):
        now = time.monotonic()
        if memo["v"] is None or now - memo["t"] > 0.25:
            memo["v"] = engine.gauge_samples()
            memo["t"] = now
        return memo["v"][key]

    reg.register(
        "transmogrifai_slo_target", "gauge",
        "configured good-fraction target per ratio objective",
        lambda: samples("targets"))
    reg.register(
        "transmogrifai_slo_burn_rate", "gauge",
        "error-budget burn rate per objective, alert and window "
        "(1.0 = exactly sustainable)",
        lambda: samples("burns"))
    reg.register(
        "transmogrifai_slo_alert_firing", "gauge",
        "1 while the objective's multi-window alert fires",
        lambda: samples("firing"))
    reg.register(
        "transmogrifai_slo_evaluations_total", "counter",
        "SLO engine evaluations",
        lambda: [({}, engine.evaluations)])


def _app_collectors(reg: PromRegistry) -> None:
    from transmogrifai_tpu.utils import profiling

    def phases(field: str):
        def collect():
            return [({"phase": k}, getattr(p, field))
                    for k, p in profiler_metrics().phases.items()]
        return collect

    def profiler_metrics():
        return profiling.profiler.metrics

    reg.register("transmogrifai_phase_wall_seconds_total", "counter",
                 "exclusive wall seconds per OpStep phase", phases("wall_s"))
    reg.register("transmogrifai_phase_device_seconds_total", "counter",
                 "attributed device-busy seconds per phase",
                 phases("device_s"))
    reg.register("transmogrifai_phase_runs_total", "counter",
                 "phase occurrences", phases("count"))
    reg.register("transmogrifai_phase_peak_hbm_bytes", "gauge",
                 "peak device HBM high-water mark attributed to the phase",
                 phases("peak_hbm_bytes"))
    reg.register(
        "transmogrifai_stage_wall_seconds_total", "counter",
        "inclusive wall seconds per DAG stage (tracing span rollup)",
        lambda: [({"stage": k}, v.get("wallSeconds", 0.0))
                 for k, v in profiler_metrics().stages.items()])
    reg.register(
        "transmogrifai_stage_device_seconds_total", "counter",
        "attributed device seconds per DAG stage",
        lambda: [({"stage": k}, v.get("deviceSeconds", 0.0))
                 for k, v in profiler_metrics().stages.items()])

    rc = profiling.run_counters
    for attr, help_ in (("layers_fitted", "DAG layers fit live"),
                        ("layers_resumed", "DAG layers replayed from a "
                                           "train checkpoint"),
                        ("stages_resumed", "stages restored from a train "
                                           "checkpoint"),
                        ("retries", "transient device retries"),
                        ("faults_injected", "chaos-plan faults delivered")):
        reg.register(f"transmogrifai_run_{attr}_total", "counter", help_,
                     lambda a=attr: [({}, getattr(rc, a))])

    sc = profiling.sweep_counters
    reg.register(
        "transmogrifai_sweep_compiles_total", "counter",
        "XLA backend compiles during the family's sweep",
        lambda: [({"family": name}, sc.compiles(name))
                 for name in sc.families])
    for attr, help_ in (("device_dispatches", "sweep device program "
                                              "dispatches"),
                        ("host_syncs", "sweep device->host metric pulls"),
                        ("stacked_groups", "tree depth-groups dispatched "
                                           "fold x grid-stacked"),
                        ("lane_chunks", "HBM-guard lane chunks dispatched "
                                        "on the stacked tree path")):
        reg.register(
            f"transmogrifai_sweep_{attr}_total", "counter", help_,
            lambda a=attr: [({"family": name}, getattr(fc, a))
                            for name, fc in sc.families.items()])
    # run-level one-sync counters (round 9): unlabeled — they describe the
    # WHOLE sweep (the per-family host_syncs above count each family's
    # metric pull; run_host_syncs counts blocking settle barriers, 1 on
    # the async overlapped path however many families dispatched)
    for attr, name, help_ in (
            ("sweep_host_syncs", "run_host_syncs",
             "blocking device->host settle barriers for the whole sweep"),
            ("async_families", "async_families",
             "families dispatched asynchronously (metrics held as device "
             "futures until the single settle)"),
            ("refit_warm_starts", "refit_warm_starts",
             "winner refits warm-started from sweep state (stacked fold "
             "parameters / reused tree bin codes)"),
            ("linear_onepass_lanes", "linear_onepass_lanes",
             "linear descent lanes whose every Adam step read the matrix "
             "once")):
        reg.register(f"transmogrifai_sweep_{name}_total", "counter", help_,
                     lambda a=attr: [({}, getattr(sc, a))])


def _serving_collectors(reg: PromRegistry, lanes_fn) -> None:
    """The serving series over ``lanes_fn() -> [(labels, ServingMetrics),
    ...]`` — one sample set per lane. A single ``ScoringServer`` is the
    one-lane, no-labels case; a ``FleetServer`` emits the SAME series
    once per model with a ``model`` label, so dashboards aggregate or
    split without a second naming scheme."""
    def per_lane(attr: str):
        def collect():
            return [(labels, getattr(m, attr)) for labels, m in lanes_fn()]
        return collect

    for attr, name, help_ in (
            ("admitted", "requests_admitted", "requests accepted at the "
                                              "door"),
            ("completed", "requests_completed", "requests settled ok"),
            ("failed", "requests_failed", "requests settled with an error"),
            ("expired", "requests_expired", "requests whose queue deadline "
                                            "expired"),
            ("batches", "batches", "dispatched micro-batches"),
            ("degraded_batches", "degraded_batches", "batches served on "
                                                     "the row path"),
            ("data_error_batches", "data_error_batches",
             "batches row-scored for a malformed row (no degradation)"),
            ("batch_rows", "batch_rows", "rows dispatched in batches"),
            ("degraded_entries", "degraded_entries", "degraded-mode "
                                                     "entries"),
            ("recoveries", "recoveries", "compiled-path recoveries"),
            ("dispatch_retries", "dispatch_retries", "transient dispatch "
                                                     "retries"),
            ("batch_wall_s", "batch_wall_seconds", "cumulative batch "
                                                   "dispatch wall")):
        reg.register(f"transmogrifai_serving_{name}_total", "counter",
                     help_, per_lane(attr))
    reg.register(
        "transmogrifai_serving_rejected_total", "counter",
        "requests rejected at admission, by reason",
        lambda: [({**labels, "reason": "backpressure"},
                  m.rejected_backpressure)
                 for labels, m in lanes_fn()]
               + [({**labels, "reason": "invalid"}, m.rejected_invalid)
                  for labels, m in lanes_fn()])
    reg.register(
        "transmogrifai_serving_latency_seconds", "histogram",
        "request latency, admission to settlement",
        lambda: [(labels, m.latency_histogram())
                 for labels, m in lanes_fn()])
    reg.register(
        "transmogrifai_serving_queue_depth", "gauge",
        "requests waiting in the admission queue",
        lambda: [(labels, (m.queue_depth_fn or (lambda: 0))())
                 for labels, m in lanes_fn()])
    reg.register(
        "transmogrifai_serving_queue_capacity", "gauge",
        "admission queue bound",
        lambda: [(labels, m.queue_capacity or 0)
                 for labels, m in lanes_fn()])
    reg.register(
        "transmogrifai_serving_degraded", "gauge",
        "1 while the server is on the degraded row path",
        lambda: [(labels, m.degraded_active)
                 for labels, m in lanes_fn()])
    reg.register(
        "transmogrifai_serving_throughput_rolling_rps", "gauge",
        "completions/s over the rolling window",
        lambda: [(labels, m.rolling_rps()) for labels, m in lanes_fn()])
    reg.register(
        "transmogrifai_serving_throughput_lifetime_rps", "gauge",
        "completions/s since server start",
        lambda: [(labels, m.throughput_rps())
                 for labels, m in lanes_fn()])

    def per_bucket(attr: str):
        def collect():
            out = []
            for labels, m in lanes_fn():
                cc = m.compile_counters
                if cc is None:
                    continue
                out.extend(({**labels, "bucket": str(b)},
                            getattr(c, attr))
                           for b, c in sorted(cc.buckets.items()))
            return out
        return collect

    # precision-ladder lifecycle: the counters carry the bare
    # transmogrifai_precision_ prefix — the ladder is ONE surface
    # whether a lane or a fleet runs it — and the bits gauge rides the
    # serving namespace per lane (32 = f32 master, 16 = bf16, 8 = int8)
    for attr, name, help_ in (
            ("precision_promotions", "promotions",
             "precision-rung promotions accepted by the shadow gate "
             "(candidate within score-diff tolerance of f32)"),
            ("precision_rejections", "rejections",
             "candidate rungs rejected by the shadow gate (the batch "
             "served the f32 scores bit-identically)"),
            ("precision_demotions", "demotions",
             "gate-skipping precision demotions forced by resource "
             "pressure")):
        reg.register(f"transmogrifai_precision_{name}_total", "counter",
                     help_, per_lane(attr))
    reg.register(
        "transmogrifai_serving_precision_bits", "gauge",
        "active precision-rung width in bits per lane",
        per_lane("precision_bits"))
    reg.register("transmogrifai_serving_compiles_total", "counter",
                 "fused-program compiles per padding bucket",
                 per_bucket("compiles"))
    reg.register("transmogrifai_serving_dispatches_total", "counter",
                 "batch dispatches per padding bucket",
                 per_bucket("dispatches"))
    reg.register("transmogrifai_serving_cache_evictions_total", "counter",
                 "shared-cache entries evicted per padding bucket (the "
                 "next dispatch at that bucket recompiles)",
                 per_bucket("evictions"))


def _explain_collectors(reg: PromRegistry, servers_fn) -> None:
    """The explain-lane series over ``servers_fn() -> [(labels,
    ScoringServer), ...]`` (only servers whose explain lane is enabled).
    Same shape discipline as the serving series: one sample set per
    lane, ``model``-labeled under a fleet, unlabeled standalone — the
    ``transmogrifai_explain_*`` namespace is the explained-traffic half
    of every dashboard."""
    def lanes():
        return [(labels, srv.explain_metrics)
                for labels, srv in servers_fn()
                if srv.explain_metrics is not None]

    def per_lane(attr: str):
        def collect():
            return [(labels, getattr(m, attr)) for labels, m in lanes()]
        return collect

    for attr, name, help_ in (
            ("admitted", "requests_admitted", "explain requests accepted "
                                              "at the door"),
            ("completed", "requests_completed", "explain requests settled "
                                                "ok"),
            ("failed", "requests_failed", "explain requests settled with "
                                          "an error"),
            ("expired", "requests_expired", "explain requests whose queue "
                                            "deadline expired"),
            ("batches", "batches", "dispatched explain micro-batches"),
            ("degraded_batches", "degraded_batches",
             "explain batches served as row-path scores without "
             "attributions (ladder exhausted)"),
            ("batch_rows", "batch_rows", "rows dispatched in explain "
                                         "batches"),
            ("dispatch_retries", "dispatch_retries", "transient explain "
                                                     "dispatch retries"),
            ("batch_wall_s", "batch_wall_seconds", "cumulative explain "
                                                   "batch dispatch wall")):
        reg.register(f"transmogrifai_explain_{name}_total", "counter",
                     help_, per_lane(attr))
    reg.register(
        "transmogrifai_explain_rejected_total", "counter",
        "explain requests rejected at admission, by reason",
        lambda: [({**labels, "reason": "backpressure"},
                  m.rejected_backpressure)
                 for labels, m in lanes()]
               + [({**labels, "reason": "invalid"}, m.rejected_invalid)
                  for labels, m in lanes()])
    reg.register(
        "transmogrifai_explain_latency_seconds", "histogram",
        "explain request latency, admission to settlement",
        lambda: [(labels, m.latency_histogram())
                 for labels, m in lanes()])
    reg.register(
        "transmogrifai_explain_queue_depth", "gauge",
        "requests waiting in the explain admission queue",
        lambda: [(labels, (m.queue_depth_fn or (lambda: 0))())
                 for labels, m in lanes()])
    reg.register(
        "transmogrifai_explain_throughput_rolling_rps", "gauge",
        "explained completions/s over the rolling window",
        lambda: [(labels, m.rolling_rps()) for labels, m in lanes()])
    reg.register(
        "transmogrifai_explain_mask_chunk", "gauge",
        "current LOCO mask-chunk width (the serving.explain ladder rung "
        "halves it under memory pressure)",
        lambda: [(labels, srv.explainer.mask_chunk)
                 for labels, srv in servers_fn()
                 if srv.explainer is not None])
    reg.register(
        "transmogrifai_explain_groups", "gauge",
        "LOCO feature groups of the served vector (0 until the first "
        "explain dispatch resolves them)",
        lambda: [(labels, srv.explainer.n_groups or 0)
                 for labels, srv in servers_fn()
                 if srv.explainer is not None])

    def per_bucket(attr: str):
        def collect():
            out = []
            for labels, m in lanes():
                cc = m.compile_counters
                if cc is None:
                    continue
                out.extend(({**labels, "bucket": str(b)},
                            getattr(c, attr))
                           for b, c in sorted(cc.buckets.items()))
            return out
        return collect

    reg.register("transmogrifai_explain_compiles_total", "counter",
                 "explain-program compiles per padding bucket",
                 per_bucket("compiles"))
    reg.register("transmogrifai_explain_dispatches_total", "counter",
                 "explain batch dispatches per padding bucket",
                 per_bucket("dispatches"))


#: cap on `model`-labeled tenant series per scrape: the K busiest lanes
#: keep their own label, the tail aggregates into ONE `_other` sample
#: set. <= 0 = unlimited (the pre-tiering behavior)
TENANT_TOPK_ENV = "TRANSMOGRIFAI_METRICS_TENANT_TOPK"
TENANT_TOPK_DEFAULT = 20

#: the model=_other rollup series — per-tenant label cardinality is
#: bounded; everything still SUMS correctly across the label
TENANT_OTHER_LABEL = "_other"

_ROLLUP_SUM_ATTRS = frozenset({
    "admitted", "completed", "failed", "expired", "batches",
    "degraded_batches", "data_error_batches", "batch_rows",
    "degraded_entries", "recoveries", "dispatch_retries",
    "batch_wall_s", "rejected_backpressure", "rejected_invalid",
    "precision_promotions", "precision_rejections",
    "precision_demotions"})


class _ServingRollup:
    """The ``model="_other"`` aggregate over the tail lanes' metrics:
    counters sum, the latency histogram merges bucket-wise, gauges take
    the honest aggregate (sum for depth/capacity/rps, any() for the
    degraded flag). ``compile_counters`` is None — per-bucket compile
    series stay per-model-only: a bucket histogram summed across
    heterogeneous tail models would chart nothing anyone can act on."""

    compile_counters = None

    def __init__(self, members):
        self._members = list(members)

    def __getattr__(self, attr):
        if attr in _ROLLUP_SUM_ATTRS:
            return sum(getattr(m, attr) for m in self._members)
        raise AttributeError(attr)

    @property
    def degraded_active(self):
        return int(any(m.degraded_active for m in self._members))

    @property
    def precision_bits(self):
        # the honest aggregate is the WORST (narrowest) rung: a single
        # demoted tail lane must show through the rollup
        return min((m.precision_bits for m in self._members), default=32)

    @property
    def queue_capacity(self):
        return sum(m.queue_capacity or 0 for m in self._members)

    @property
    def queue_depth_fn(self):
        members = self._members
        return lambda: sum((m.queue_depth_fn or (lambda: 0))()
                           for m in members)

    def latency_histogram(self) -> dict:
        buckets: dict = {}
        total_sum = 0.0
        total_count = 0
        for m in self._members:
            h = m.latency_histogram()
            for le, cum in h["buckets"].items():
                buckets[le] = buckets.get(le, 0) + cum
            total_sum += h["sum"]
            total_count += h["count"]
        return {"buckets": buckets, "sum": total_sum,
                "count": total_count}

    def rolling_rps(self) -> float:
        return sum(m.rolling_rps() for m in self._members)

    def throughput_rps(self) -> float:
        return sum(m.throughput_rps() for m in self._members)


class _ExplainRollupLane:
    """Server-shaped wrapper carrying the tail lanes' explain rollup
    (``explainer`` stays None: mask-chunk/group gauges are
    per-model-only, like the compile buckets)."""

    explainer = None

    def __init__(self, members):
        self.explain_metrics = _ServingRollup(members)


def tenant_topk() -> int:
    env = os.environ.get(TENANT_TOPK_ENV)
    if env is None or not env.strip():
        return TENANT_TOPK_DEFAULT
    try:
        return int(float(env))
    except ValueError:
        return TENANT_TOPK_DEFAULT


def _split_topk_lanes(fleet, k: int) -> tuple:
    """``(top, tail)`` over the fleet's active lanes: the ``k`` busiest
    (lifetime admitted — stable under scrape-to-scrape load wiggle,
    unlike a rolling rate) keep their own ``model`` label; the rest
    roll up. Top is re-sorted by id so scrape output stays diff-able."""
    lanes = sorted(fleet.active_lanes().items())
    if k <= 0 or len(lanes) <= k:
        return lanes, []
    ranked = sorted(lanes,
                    key=lambda kv: (-kv[1].metrics.admitted, kv[0]))
    return sorted(ranked[:k]), ranked[k:]


def _fleet_collectors(reg: PromRegistry, fleet) -> None:
    """Fleet-level series: swap lifecycle, shared compiled-program cache
    accounting, per-model state — plus every serving series labeled
    ``model=<id>`` via ``_serving_collectors`` over the active lanes.

    Label cardinality is BOUNDED: at 1000 tenants, per-model series
    make every scrape megabytes, so only the top-K busiest lanes
    (``TRANSMOGRIFAI_METRICS_TENANT_TOPK``, default 20) keep their own
    ``model`` label and the tail aggregates into ``model="_other"``
    (fleet-wide sums over the label stay exact)."""
    topk = tenant_topk()

    def serving_lanes():
        top, tail = _split_topk_lanes(fleet, topk)
        out = [({"model": mid}, lane.metrics) for mid, lane in top]
        if tail:
            out.append(({"model": TENANT_OTHER_LABEL},
                        _ServingRollup([ln.metrics for _, ln in tail])))
        return out

    def explain_lanes():
        top, tail = _split_topk_lanes(fleet, topk)
        out = [({"model": mid}, lane) for mid, lane in top
               if getattr(lane, "explain_metrics", None) is not None]
        tail_m = [ln.explain_metrics for _, ln in tail
                  if getattr(ln, "explain_metrics", None) is not None]
        if tail_m:
            out.append(({"model": TENANT_OTHER_LABEL},
                        _ExplainRollupLane(tail_m)))
        return out

    _serving_collectors(reg, serving_lanes)
    _explain_collectors(reg, explain_lanes)
    fm = fleet.metrics
    for attr, name, help_ in (
            ("swaps", "swaps", "completed zero-downtime hot-swaps"),
            ("swap_failures", "swap_failures", "aborted hot-swaps (old "
                                               "version kept serving)"),
            ("shadow_parity_failures", "shadow_parity_failures",
             "hot-swaps aborted by the shadow-scoring parity gate"),
            ("models_registered", "models_registered", "registry "
                                                       "registrations"),
            ("models_unloaded", "models_unloaded", "registry unloads")):
        reg.register(f"transmogrifai_fleet_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(fm, a))])
    cache = fleet.program_cache
    for attr, name, help_ in (
            ("hits", "cache_hits", "shared compiled-program cache hits"),
            ("insertions", "cache_insertions", "shared-cache compiled "
                                               "entries inserted"),
            ("evictions", "cache_evictions", "shared-cache entries "
             "evicted by the HBM budget LRU")):
        reg.register(f"transmogrifai_fleet_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(cache, a))])
    reg.register("transmogrifai_fleet_cache_bytes", "gauge",
                 "accounted HBM bytes of cached compiled programs",
                 lambda: [({}, cache.current_bytes)])
    reg.register("transmogrifai_fleet_cache_budget_bytes", "gauge",
                 "configured shared-cache HBM budget (0 = unbounded)",
                 lambda: [({}, cache.budget_bytes or 0)])
    reg.register("transmogrifai_fleet_cache_entries", "gauge",
                 "live shared-cache entries",
                 lambda: [({}, len(cache))])
    reg.register("transmogrifai_fleet_models", "gauge",
                 "models with a running active lane",
                 lambda: [({}, len(fleet.active_lanes()))])
    def model_state():
        top, tail = _split_topk_lanes(fleet, topk)
        out = [({"model": mid, "state": lane.state}, 1)
               for mid, lane in top]
        if tail:
            counts: dict = {}
            for _, lane in tail:
                counts[lane.state] = counts.get(lane.state, 0) + 1
            out.extend(({"model": TENANT_OTHER_LABEL, "state": s}, n)
                       for s, n in sorted(counts.items()))
        return out

    reg.register(
        "transmogrifai_fleet_model_state", "gauge",
        "1 for each model's current readiness state (top-K lanes by "
        "traffic; the tail aggregates per state under model=\"_other\")",
        model_state)


def _tenancy_collectors(reg: PromRegistry, fleet) -> None:
    """Multi-tenant tiering series over a tenancy-enabled fleet: the
    residency ladder (RAM-tier bytes/budget, promotion and demotion
    counters per tier edge, cold starts) plus — when admission is on —
    the per-tenant fairness surface, top-K-capped with a
    ``tenant="_other"`` rollup exactly like the serving series."""
    store = fleet.tenancy_store
    tm = store.metrics
    reg.register("transmogrifai_tenancy_ram_bytes", "gauge",
                 "accounted host-RAM bytes of resident decoded models",
                 lambda: [({}, store.ram_bytes)])
    reg.register("transmogrifai_tenancy_ram_budget_bytes", "gauge",
                 "configured RAM-tier byte budget (0 = unbounded)",
                 lambda: [({}, store.ram_budget_bytes or 0)])
    reg.register("transmogrifai_tenancy_models_resident", "gauge",
                 "models resident in the host-RAM tier",
                 lambda: [({}, store.resident_count)])
    reg.register(
        "transmogrifai_tenancy_models_cold", "gauge",
        "registered models currently COLD (path-only; page in on "
        "first score)",
        lambda: [({}, sum(1 for d in fleet.registry.list()
                          if d.get("state") == "cold"))])
    reg.register(
        "transmogrifai_tenancy_promotions_total", "counter",
        "residency promotions, by tier edge (disk->RAM page-ins, "
        "RAM->HBM lane starts)",
        lambda: [({"tier": "ram"}, tm.promotions_disk_ram),
                 ({"tier": "hbm"}, tm.promotions_ram_hbm)])
    reg.register(
        "transmogrifai_tenancy_demotions_total", "counter",
        "residency demotions, by tier (RAM records dropped; HBM "
        "program entries evicted by a RAM demotion)",
        lambda: [({"tier": "ram"}, tm.demotions_ram),
                 ({"tier": "hbm"}, tm.demotions_hbm)])
    reg.register(
        "transmogrifai_tenancy_sheds_total", "counter",
        "pressure-rung shed passes (tier demotion under host "
        "RSS/disk pressure)",
        lambda: [({}, tm.sheds)])
    reg.register(
        "transmogrifai_tenancy_prewarms_total", "counter",
        "popularity-driven background page-ins",
        lambda: [({}, tm.prewarms)])
    reg.register(
        "transmogrifai_tenancy_cold_starts_total", "counter",
        "demand page-ins on first score (disk -> RAM -> lane)",
        lambda: [({}, tm.cold_starts)])
    reg.register(
        "transmogrifai_tenancy_cold_start_wall_seconds_total",
        "counter",
        "cumulative cold-start wall (first-score page-in latency)",
        lambda: [({}, tm.cold_start_wall_s)])
    admission = getattr(fleet, "admission", None)
    if admission is None:
        return
    topk = tenant_topk()

    def fairness(field: str):
        def collect():
            top, other = admission.metrics.topk(topk)
            out = [({"tenant": t}, row[field])
                   for t, row in sorted(top.items())]
            if other is not None:
                out.append(({"tenant": TENANT_OTHER_LABEL},
                            other[field]))
            return out
        return collect

    reg.register("transmogrifai_fairness_admitted_total", "counter",
                 "requests admitted through the tenant token bucket "
                 "(top-K tenants; tail under tenant=\"_other\")",
                 fairness("admitted"))
    reg.register("transmogrifai_fairness_throttled_total", "counter",
                 "requests throttled by the tenant token bucket "
                 "(answered 503 + Retry-After)",
                 fairness("throttled"))
    reg.register("transmogrifai_fairness_debt_seconds_total", "counter",
                 "cumulative suggested-wait seconds per tenant (how "
                 "hard each pushed past its fair share)",
                 fairness("debtSeconds"))
    reg.register(
        "transmogrifai_fairness_cold_start_waits_total", "counter",
        "requests that waited on a cold-start page-in",
        lambda: [({}, admission.metrics.cold_start_waits)])


def _router_collectors(reg: PromRegistry, router) -> None:
    """The scale-out router's series (``scaleout/router.py``): request
    outcomes, per-replica proxy counts, spillover/markdown/retry
    accounting, router-observed latency, and the routing table as a
    per-replica state gauge."""
    rm = router.metrics
    for attr, name, help_ in (
            ("completed", "requests_completed",
             "requests proxied to a 2xx reply"),
            ("failed", "requests_failed",
             "requests answered 5xx after every candidate"),
            ("client_errors", "requests_client_error",
             "4xx replies proxied back (caller errors)"),
            ("spillovers", "spillovers",
             "503-backpressured requests spilled to a ring successor"),
            ("retries", "retries",
             "requests retried on the next replica after a transport "
             "failure (replica kill = retries, not drops)"),
            ("markdowns", "markdowns",
             "replicas marked down by the router"),
            ("no_replica", "no_replica",
             "requests with no routable replica at all"),
            ("rebalances", "rebalances",
             "skew-triggered ring re-weightings applied"),
            ("refusals", "refusals",
             "connect-refused attempts spilled to the next candidate "
             "(provably undelivered; no retry budget charged)"),
            ("resets", "resets",
             "mid-request transport failures retried under the "
             "request's idempotency key"),
            ("hedges", "hedges",
             "tail-latency hedges launched past the replica's "
             "observed p99")):
        reg.register(f"transmogrifai_router_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(rm, a))])
    if getattr(router, "load_skew", None) is not None:
        reg.register(
            "transmogrifai_router_load_skew", "gauge",
            "max/mean primary EWMA load over ring members (1.0 = "
            "balanced; the supervisor's rebalance trigger)",
            lambda: [({}, router.load_skew())])
        reg.register(
            "transmogrifai_router_ring_weight", "gauge",
            "per-replica consistent-hash placement weight (vnode "
            "multiplier; rebalancing moves these)",
            lambda: [({"replica": rid}, w)
                     for rid, w in sorted(
                         router.ring.weights().items())]
                    or [({"replica": "none"}, 0)])
    reg.register(
        "transmogrifai_router_proxied_total", "counter",
        "requests proxied, by serving replica",
        lambda: [({"replica": rid}, n)
                 for rid, n in sorted(rm.to_json()["byReplica"]
                                      .items())]
                or [({"replica": "none"}, 0)])
    reg.register(
        "transmogrifai_router_latency_seconds", "histogram",
        "request latency through the router (proxy hop included)",
        lambda: [({}, rm.latency_histogram())])
    reg.register(
        "transmogrifai_router_replica_state", "gauge",
        "1 per replica in its current routing state (up/down/draining)",
        lambda: [({"replica": rid, "state": doc["state"]}, 1)
                 for rid, doc in sorted(router.replicas().items())])
    reg.register(
        "transmogrifai_router_replicas", "gauge",
        "replicas currently routable (state up)",
        lambda: [({}, sum(1 for d in router.replicas().values()
                          if d["state"] == "up"))])


def _scaleout_collectors(reg: PromRegistry, supervisor) -> None:
    """Supervisor lifecycle series (``scaleout/supervisor.py``):
    spawn/respawn/scale/roll counters plus desired-vs-live replica
    gauges."""
    sm = supervisor.metrics
    for attr, name, help_ in (
            ("spawns", "spawns", "replica processes spawned"),
            ("respawns", "respawns", "replica processes respawned "
                                     "after a crash"),
            ("scale_ups", "scale_ups", "fleet scale-up actions"),
            ("scale_downs", "scale_downs", "fleet scale-down actions"),
            ("rolls", "rolls", "completed rolling hot-swaps"),
            ("roll_failures", "roll_failures",
             "rolling hot-swaps halted (fleet converged on the old "
             "version)"),
            ("rollbacks", "rollbacks",
             "already-swapped replicas forced back to the old version "
             "by a halted roll"),
            ("rebalances", "rebalances",
             "skew-triggered ring rebalances the supervisor applied")):
        reg.register(f"transmogrifai_scaleout_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(sm, a))])
    reg.register(
        "transmogrifai_scaleout_desired_replicas", "gauge",
        "replica count the supervisor converges on",
        lambda: [({}, supervisor.desired_replicas)])
    reg.register(
        "transmogrifai_scaleout_live_replicas", "gauge",
        "replica processes currently alive",
        lambda: [({}, sum(1 for d in supervisor.to_json()["replicas"]
                          .values() if d["alive"]))])
    reg.register(
        "transmogrifai_scaleout_queue_ratio", "gauge",
        "mean replica admission-queue fill ratio (heartbeat-reported; "
        "the autoscaler's load signal)",
        lambda: [({}, supervisor.queue_ratio())])


def _continuous_collectors(reg: PromRegistry, cont) -> None:
    """The continuous-loop series over a ``ContinuousLoop``-shaped
    object: lifecycle counters from its ``metrics``
    (``ContinuousMetrics``), per-feature drift-score gauges from
    ``drift_scores()``, and window/staleness/buffer gauges."""
    cm = cont.metrics
    for attr, name, help_ in (
            ("batches", "batches", "stream micro-batches consumed"),
            ("rows", "rows", "stream rows consumed"),
            ("skipped_batches", "skipped_batches",
             "unreadable micro-batches dropped from training"),
            ("drift_triggers", "drift_triggers",
             "drift-window triggers (post hysteresis/cooldown)"),
            ("retrains", "retrains", "retrain attempts launched"),
            ("retrain_failures", "retrain_failures",
             "retrain attempts that failed (old model kept serving)"),
            ("promotions", "promotions",
             "retrained versions promoted through the hot-swap gate"),
            ("rollbacks", "rollbacks",
             "promotions rolled back by the shadow parity gate")):
        reg.register(f"transmogrifai_continuous_{name}_total", "counter",
                     help_, lambda a=attr: [({}, getattr(cm, a))])
    reg.register(
        "transmogrifai_continuous_drift_score", "gauge",
        "per-feature drift score of the last closed window (the "
        "configured metric: JS divergence or PSI; __label__ = label "
        "mean delta)",
        lambda: [({"feature": k}, v)
                 for k, v in sorted(cont.drift_scores().items())])
    reg.register(
        "transmogrifai_continuous_staleness_seconds", "gauge",
        "age of the serving model's training data (seconds since the "
        "last promotion)",
        lambda: [({}, cont.staleness_s())])
    reg.register(
        "transmogrifai_continuous_window", "gauge",
        "drift windows closed over the loop's lifetime",
        lambda: [({}, cont.window_seq())])
    reg.register(
        "transmogrifai_continuous_buffer_rows", "gauge",
        "rows accumulated in the retrain buffer",
        lambda: [({}, cont.buffer_rows())])


def build_registry(serving=None, server=None, fleet=None, continuous=None,
                   router=None, scaleout=None,
                   slo=None, include_app: bool = True) -> PromRegistry:
    """The standard registry: process-wide training/run/sweep series
    (``include_app``) plus the full serving surface — unlabeled for one
    ``ServingMetrics`` (``serving``), ``model``-labeled per lane plus the
    fleet swap/cache series for a ``FleetServer`` (``fleet``; mutually
    exclusive with ``serving``). ``continuous`` (a ``ContinuousLoop``)
    adds the ``transmogrifai_continuous_*`` drift/retrain/promotion
    series and composes with ``fleet`` — the loop's scrape endpoint
    exposes both. ``router`` (a ``scaleout.Router``) adds the
    ``transmogrifai_router_*`` proxy surface and ``scaleout`` (a
    ``scaleout.ReplicaSupervisor``) the ``transmogrifai_scaleout_*``
    lifecycle series — the scale-out control process scrapes both on
    one endpoint. ``slo`` (a ``utils.slo.SLOEngine``) adds the
    ``transmogrifai_slo_*`` burn-rate surface. ``server`` (a
    ``ScoringServer``) adds the ``transmogrifai_explain_*`` lane series
    when its explain lane is enabled (fleets get the model-labeled
    variant automatically). EVERY registry carries
    ``transmogrifai_build_info``, the
    process-uptime gauge, the flight recorder's
    ``transmogrifai_events_*`` accounting, the resource-pressure
    ``transmogrifai_resource_*`` series (degradation-ladder rungs,
    OOM/ENOSPC events, RSS/disk gauges), and the device-execution
    observatory's ``transmogrifai_device_*`` / ``transmogrifai_compile_*``
    series (watchdog stalls, in-flight dispatches, all-device HBM,
    compile walls), so any scrape is correlatable across restarts."""
    if serving is not None and fleet is not None:
        raise ValueError("pass serving= or fleet=, not both (the serving "
                         "series would collide)")
    reg = PromRegistry()
    _process_collectors(reg)
    _event_collectors(reg)
    _resource_collectors(reg)
    _net_collectors(reg)
    _devicewatch_collectors(reg)
    _ingest_collectors(reg)
    if include_app:
        _app_collectors(reg)
    if serving is not None:
        _serving_collectors(reg, lambda: [({}, serving)])
        if server is not None and \
                getattr(server, "explain_metrics", None) is not None:
            # the standalone server's explain lane (fleets wire their
            # model-labeled explain series via _fleet_collectors)
            _explain_collectors(reg, lambda: [({}, server)])
    if fleet is not None:
        _fleet_collectors(reg, fleet)
        if getattr(fleet, "tenancy_store", None) is not None:
            # multi-tenant tiering: residency-ladder + fairness series
            _tenancy_collectors(reg, fleet)
    if continuous is not None:
        _continuous_collectors(reg, continuous)
    if router is not None:
        # the scale-out front door (scaleout/router.py): the
        # transmogrifai_router_* proxy/markdown/latency surface
        _router_collectors(reg, router)
    if scaleout is not None:
        # the replica supervisor (scaleout/supervisor.py):
        # spawn/respawn/scale/roll lifecycle + replica gauges
        _scaleout_collectors(reg, scaleout)
    if slo is not None:
        _slo_collectors(reg, slo)
    return reg
