"""Phase-scoped profiling & run metrics with device-time attribution.

Parity: reference ``utils/.../spark/OpSparkListener.scala:52-418``
(AppMetrics) + ``core/.../utils/spark/JobGroupUtil.scala`` (OpStep
job-group taxonomy) — every workflow phase is attributed to an ``OpStep``,
wall time collected, and the aggregate ``AppMetrics`` is queryable/
serializable at the end of the run.

TPU-first: where the reference attributes *executor* time to phases via
Spark job groups, this attributes *device* time via one ``jax.profiler``
trace spanning the run. At ``finalize()`` the trace file is read
(``trace_device_events``: every leaf device op with its module, op kind and
``jax.named_scope`` path, on the host's clock) and device time is owned two
ways. PHASES own by time: ``profiler.phase`` drains the device at each
phase's end while a trace is on, so a phase's wall window does hold its
ops, and each op goes to the innermost phase containing its midpoint.
Everything finer owns by what the op IS: the ``(module, scope)`` table
``AppMetrics.device_scopes`` — host spans are not fenced (the sweep
dispatches every program asynchronously and blocks once), so a span's
window says nothing about which ops ran for it. Only spans opened with
``device_window=True`` get ``Span.device_s``: device intervals by
construction (``sweep.device``, ``refit.device``) and spans that end in a
blocking pull (``selector.sweep``, ``sweep.settle``, ``selector.refit``).
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import NamedTuple, Optional

__all__ = ["OpStep", "AppMetrics", "profiler", "phase",
           "DeviceEvent", "trace_device_events", "scope_of",
           "aggregate_across_hosts", "SweepCounters", "sweep_counters",
           "ServingCounters", "RunCounters", "run_counters",
           "IngestCounters", "ingest_counters"]


class OpStep(Enum):
    DATA_READING_AND_FILTERING = "DataReadingAndFiltering"
    FEATURE_ENGINEERING = "FeatureEngineering"
    CROSS_VALIDATION = "CrossValidation"
    MODEL_TRAINING = "ModelTraining"
    SCORING = "Scoring"
    EVALUATION = "Evaluation"
    RESULTS_SAVING = "ResultsSaving"
    OTHER = "Other"


@dataclass
class PhaseMetrics:
    step: str
    wall_s: float = 0.0
    count: int = 0
    peak_hbm_bytes: int = 0   # device peak_bytes_in_use high-water mark
    device_s: float = 0.0     # attributed device busy seconds (finalize())


def _device_memory() -> tuple[int, int]:
    """(bytes_in_use, peak_bytes_in_use) summed across EVERY local
    device (the shared ``utils/devicewatch.py`` census — a mesh-sharded
    phase's memory lives on all devices, not device 0), or zeros when
    the backend exposes no memory_stats (CPU, some plugins)."""
    from transmogrifai_tpu.utils.devicewatch import device_memory
    return device_memory()


#: name of the host annotation ``profiler.reset(trace_dir=...)`` opens right
#: after the trace starts: its place on the trace's clock against the host
#: clock read beside it puts every device event on the host's clock
TRACE_ANCHOR = "transmogrifai.trace_anchor"

_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
#: ops that only enclose other ops of the same line: their time is their
#: children's
_CONTAINER_OPS = ("while", "conditional", "call")
#: ``op_name`` path components that are structure, not a named scope
_STRUCTURAL = frozenset(("while", "body", "cond", "closed_call",
                         "checkpoint", "remat"))


class DeviceEvent(NamedTuple):
    """One leaf op of a device's op timeline."""
    start_s: float      # host clock (epoch seconds)
    duration_s: float
    module: str         # the jitted program, e.g. ``jit_train_score_stacked``
    op: str             # op kind, e.g. ``fusion``, ``reduce-window``
    scope: str          # ``jax.named_scope`` path, "" where the op has none
    program_id: int     # tells two programs of one module name apart


def scope_of(op_name: str) -> str:
    """The named-scope path in an op's ``op_name`` metadata:
    ``jit(f)/vmap(jit(g))/while/body/tree.L0/hist/dot_general:`` ->
    ``tree.L0/hist``. Transformation wrappers are unwrapped (a scope opened
    under ``vmap`` reads ``vmap(tree.predict)``), ``jit(...)`` calls,
    loop structure and the trailing primitive are dropped."""
    parts, depth, cur = [], 0, ""
    for ch in op_name.partition(":")[0]:
        if ch == "/" and depth == 0:
            parts.append(cur)
            cur = ""
            continue
        depth += (ch == "(") - (ch == ")")
        cur += ch
    out = []
    for part in parts:              # the last component is the primitive
        while part.endswith(")") and "(" in part:
            head, _, inner = part.partition("(")
            if head in ("jit", "pjit"):
                part = ""
                break
            part = inner[:-1]
        if part and part not in _STRUCTURAL:
            out.append(part)
    return "/".join(out)


def _op_kind(display_name: str) -> str:
    """``fusion.123`` -> ``fusion``."""
    head, _, tail = display_name.rpartition(".")
    return head if head and tail.isdigit() else display_name


def trace_device_events(trace_dir: str,
                        anchor_epoch_s: Optional[float] = None
                        ) -> list[DeviceEvent]:
    """The leaf device ops of a ``jax.profiler`` trace directory, each with
    its module, op kind and named-scope path, on the host's clock.

    Reads the newest ``.xplane.pb`` under ``trace_dir`` with
    ``utils/xplane.py`` (the installed ``jax.profiler.ProfileData`` yields
    the timeline but not an event's metadata statistics, where the TPU
    profiler keeps ``tf_op``, the op's ``op_name``, and ``program_id``).
    Accelerator planes only (``/device:...`` with an ``XLA Ops`` line);
    container ops (``while``, ``conditional``, ``call``) are left out, so
    durations sum to device-busy time. ``anchor_epoch_s`` is the host clock
    read when the ``TRACE_ANCHOR`` annotation was opened
    (``profiler.reset(trace_dir=...)`` does both): device and host planes
    share the trace's clock, so the anchor's offset moves every event onto
    the host's. Without it events stay on the trace's own clock (seconds
    since the trace began).

    Returns [] only for a trace with no accelerator plane (a CPU backend).
    Raises when ``trace_dir`` holds no trace file, when the file cannot be
    decoded, or when an accelerator plane is there and the anchor that was
    asked for is not."""
    from transmogrifai_tpu.utils.xplane import read_xspace
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir!r}")
    planes = read_xspace(paths[-1])
    offset_s = None
    if anchor_epoch_s is not None:
        for plane in planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for mid, off_ps, _dur in line.events:
                    if plane.event_names.get(mid) == TRACE_ANCHOR:
                        offset_s = anchor_epoch_s - (
                            line.timestamp_ns * 1e-9 + off_ps * 1e-12)
                        break
    out: list[DeviceEvent] = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if _OPS_LINE not in lines:
            continue
        if anchor_epoch_s is not None and offset_s is None:
            raise ValueError(
                f"trace {paths[-1]!r} has device plane {plane.name!r} but "
                f"no {TRACE_ANCHOR!r} annotation to place it on the host "
                "clock (host_tracer_level must be >= 1)")
        shift = offset_s or 0.0
        modules = {}    # program id -> module name
        mods = lines.get(_MODULES_LINE)
        for mid, _off, _dur in (mods.events if mods else ()):
            name, _, pid = plane.event_names.get(mid, "").partition("(")
            if pid.rstrip(")").isdigit():
                modules[int(pid.rstrip(")"))] = name
        ops = lines[_OPS_LINE]
        base_s = ops.timestamp_ns * 1e-9 + shift
        described: dict = {}   # metadata id -> (module, kind, scope, pid)
        for mid, off_ps, dur_ps in ops.events:
            what = described.get(mid)
            if what is None:
                stats = plane.event_stats.get(mid, {})
                pid = int(stats.get("program_id", 0) or 0)
                kind = _op_kind(plane.event_display.get(mid)
                                or plane.event_names.get(mid, "?"))
                what = described[mid] = (
                    modules.get(pid, "?"), kind,
                    scope_of(str(stats.get("tf_op", ""))), pid)
            if what[1] in _CONTAINER_OPS:
                continue
            out.append(DeviceEvent(base_s + off_ps * 1e-12, dur_ps * 1e-12,
                                   *what))
    return out


@dataclass
class AppMetrics:
    app_name: str = "transmogrifai_tpu"
    start_time: float = field(default_factory=time.time)
    #: frozen at ``profiler.finalize()`` — a saved run json must report the
    #: run's wall, not the wall at whatever moment ``to_json`` was called
    end_time: Optional[float] = None
    phases: dict = field(default_factory=dict)  # step -> PhaseMetrics
    #: phase occurrence intervals [(step, t0, t1)], enter order — the
    #: timeline device events are attributed against at finalize()
    spans: list = field(default_factory=list)
    #: per-DAG-stage rollup (tracing span aggregation, finalize()):
    #: label -> {"wallSeconds", "deviceSeconds", "count", "phase"}
    stages: dict = field(default_factory=dict)
    #: leaf device ops (``DeviceEvent``) retained at finalize() for trace
    #: export
    device_events: list = field(default_factory=list)
    #: device seconds by what the op is: ``(module, scope) -> [seconds,
    #: op count]`` (finalize(); scope "" = ops staged under no named scope)
    device_scopes: dict = field(default_factory=dict)

    def record(self, step: OpStep, wall_s: float,
               peak_hbm: int = 0) -> None:
        pm = self.phases.setdefault(step.value, PhaseMetrics(step.value))
        pm.wall_s += wall_s
        pm.count += 1
        pm.peak_hbm_bytes = max(pm.peak_hbm_bytes, peak_hbm)

    def attribute_device_time(self,
                              intervals: list[tuple[float, float]]) -> float:
        """Bucket device-op intervals into the innermost containing phase
        span (latest-started span whose wall window contains the op's
        midpoint). Returns total attributed device seconds."""
        total = 0.0
        for start, dur in intervals:
            mid = start + dur / 2.0
            owner = None
            for step, t0, t1 in self.spans:
                if t0 <= mid <= t1 and (owner is None or t0 >= owner[1]):
                    owner = (step, t0)
            if owner is not None:
                pm = self.phases.setdefault(owner[0], PhaseMetrics(owner[0]))
                pm.device_s += dur
                total += dur
        return total

    def attribute_device_scopes(self, events: list) -> float:
        """Roll leaf device ops up by ``(module, scope)``. Returns total
        device seconds (every op lands in exactly one row)."""
        total = 0.0
        for ev in events:
            row = self.device_scopes.setdefault((ev.module, ev.scope),
                                                [0.0, 0])
            row[0] += ev.duration_s
            row[1] += 1
            total += ev.duration_s
        return total

    def top_device_scopes(self, k: int = 10) -> list[tuple[tuple, list]]:
        """The K ``(module, scope)`` rows with most device seconds."""
        return sorted(self.device_scopes.items(),
                      key=lambda kv: -kv[1][0])[:k]

    @property
    def total_wall_s(self) -> float:
        return (self.end_time if self.end_time is not None
                else time.time()) - self.start_time

    def top_stages(self, k: int = 10) -> list[tuple[str, dict]]:
        """The K slowest DAG stages by inclusive wall (finalize() fills
        ``stages`` from the tracing recorder's per-stage spans)."""
        return sorted(self.stages.items(),
                      key=lambda kv: -kv[1].get("wallSeconds", 0.0))[:k]

    def to_json(self) -> dict:
        return {
            "appName": self.app_name,
            "totalWallSeconds": self.total_wall_s,
            "phases": {k: {"wallSeconds": p.wall_s, "count": p.count,
                           "peakHbmBytes": p.peak_hbm_bytes,
                           "deviceSeconds": p.device_s}
                       for k, p in self.phases.items()},
            "stages": {k: dict(v) for k, v in self.stages.items()},
            # device seconds by (module, named scope), most first: which
            # program, and which part of it, the device spent its time in
            "deviceScopes": [
                {"module": m, "scope": sc, "deviceSeconds": v[0],
                 "opCount": v[1]}
                for (m, sc), v in self.top_device_scopes(
                    len(self.device_scopes))],
            # fault-tolerance counters ride in every run summary — resume
            # and retry behavior is asserted from the same json operators
            # already collect (module global: one run's counters, reset
            # alongside the profiler)
            "runCounters": run_counters.to_json(),
            # resource-pressure accounting (utils/resources.py): every
            # degradation rung the run took, OOM/ENOSPC events, skipped
            # best-effort writes — the ladder's ground truth in the same
            # json
            "resourceCounters": _resource_counters_json(),
            # fused-ingest/FE accounting (round 14): fused vs host-side
            # FE stage-rows, prefetch overlap, frame-cache hits
            "ingestCounters": ingest_counters.to_json(),
        }

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh, indent=2)

    def pretty(self, top_k: int = 10) -> str:
        from transmogrifai_tpu.utils.table import Table
        rows = [(k, f"{p.wall_s:.2f}",
                 f"{p.device_s:.2f}" if p.device_s else "-", p.count,
                 f"{p.peak_hbm_bytes / 1e6:.0f}" if p.peak_hbm_bytes
                 else "-")
                for k, p in sorted(self.phases.items())]
        out = str(Table(["Phase", "Wall (s)", "Device (s)", "Count",
                         "Peak HBM (MB)"],
                        rows, title=f"{self.app_name} metrics"))
        if self.stages:
            srows = [(label, f"{v['wallSeconds']:.3f}",
                      f"{v['deviceSeconds']:.3f}"
                      if v.get("deviceSeconds") else "-",
                      f"{v['peakHbmBytes'] / 1e6:.0f}"
                      if v.get("peakHbmBytes") else "-",
                      v.get("count", 0), v.get("phase", "") or "-")
                     for label, v in self.top_stages(top_k)]
            out += "\n" + str(Table(
                ["Stage", "Wall (s)", "Device (s)", "Peak HBM (MB)",
                 "Count", "Phase"],
                srows, title=f"top {len(srows)} slowest stages"))
        if self.device_scopes:
            drows = [(m, sc or "(unscoped)", f"{v[0]:.3f}", v[1])
                     for (m, sc), v in self.top_device_scopes(top_k)]
            out += "\n" + str(Table(
                ["Module", "Scope", "Device (s)", "Ops"], drows,
                title=f"top {len(drows)} device scopes"))
        return out

    def export_chrome_trace(self, path: str) -> dict:
        """Write one Perfetto/chrome://tracing-compatible JSON merging the
        host span tree (``utils.tracing.recorder``), the coarse OpStep
        phase timeline, and (when a device plane was traced) the named
        device slices retained at ``finalize()``. Returns a small summary
        {"hostSpans": n, "deviceSlices": n, "phases": n}. Open the file at
        chrome://tracing or https://ui.perfetto.dev."""
        from transmogrifai_tpu.utils.tracing import recorder
        events: list[dict] = [
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": f"{self.app_name} host"}},
            {"name": "process_name", "ph": "M", "pid": 2,
             "args": {"name": "device"}},
        ]
        for step, t0, t1 in self.spans:
            events.append({"name": step, "ph": "X", "pid": 1, "tid": 0,
                           "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
                           "args": {"kind": "phase"}})
        events.append({"name": "thread_name", "ph": "M", "pid": 1,
                       "tid": 0, "args": {"name": "phases"}})
        host_events = recorder.chrome_trace_events(pid=1)
        events.extend(host_events)
        for ev in self.device_events:
            events.append({"name": f"{ev.module}/{ev.op}", "ph": "X",
                           "pid": 2, "tid": 0, "ts": ev.start_s * 1e6,
                           "dur": ev.duration_s * 1e6,
                           "args": {"kind": "device", "scope": ev.scope}})
        # the HBM timeline (utils/devicewatch.py low-rate census) renders
        # as a chrome-trace counter track on the device process
        from transmogrifai_tpu.utils.devicewatch import hbm_timeline
        hbm = hbm_timeline()
        for ts, used in hbm:
            events.append({"name": "hbm_bytes_in_use", "ph": "C",
                           "pid": 2, "tid": 0, "ts": ts * 1e6,
                           "args": {"bytesInUse": used}})
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"appName": self.app_name,
                             "totalWallSeconds": self.total_wall_s}}
        from transmogrifai_tpu.utils.durable import atomic_json_dump
        atomic_json_dump(doc, path)
        n_host = sum(1 for e in host_events if e["ph"] == "X")
        return {"hostSpans": n_host,
                "deviceSlices": len(self.device_events),
                "phases": len(self.spans),
                "hbmSamples": len(hbm)}


def _resource_counters_json() -> dict:
    """Lazy import seam: profiling is imported by nearly everything, and
    resources imports retry — keep the module graph acyclic."""
    from transmogrifai_tpu.utils.resources import resource_counters
    return resource_counters.to_json()


@dataclass
class SweepFamilyCounters:
    """Per-candidate-family sweep observability (see ``SweepCounters``)."""
    #: "fold_stacked" | "tree_stacked" | "fold_loop" | "resumed"
    mode: str = ""
    device_dispatches: int = 0  # train/score/metric program invocations
    host_syncs: int = 0         # device->host materializations (metric pulls)
    #: tree depth-groups dispatched fold x grid-stacked (round 8): on the
    #: tree fast path a group costs <= 1 dispatch + 1 sync per lane chunk
    stacked_groups: int = 0
    #: HBM-guard lane chunks dispatched (== stacked_groups unless the
    #: guard split a too-wide group; each chunk is one dispatch + sync)
    lane_chunks: int = 0


class SweepCounters:
    """ModelSelector sweep observability: per family, how many XLA
    compiles, device program dispatches, and host syncs the sweep paid.

    Dispatches/syncs are counted at the SELECTOR's call granularity (one
    ``grid_fit_arrays*`` / scoring call = one dispatch; one metric
    ``np.asarray`` pull = one sync) — the contract the stacked fast
    paths optimize: k folds x |grid| points in one dispatch and ONE host
    sync per family (linear fold-stacking), or per depth-group/lane
    chunk (tree fold x grid stacking, ``stacked_groups``/``lane_chunks``),
    vs k (or k x L) of each on the per-fold loop. Compiles are read from
    ``devicewatch.compile_telemetry`` (the program's one ``jax.monitoring``
    listener): real backend compiles inside the family's
    ``building(site, family=...)`` blocks since this run's ``reset()``
    (0 when the monitoring API is unavailable; loads from the persistent
    XLA cache don't count — a warm re-run reports 0 compiles).

    Surfaced by ``bench.py`` under ``device_time_breakdown.sweep`` and
    asserted in tests (fast path == 1 sync per family).

    Run-level fields (round 9, the one-sync sweep): per-family
    ``host_syncs`` counts each family's metric PULL (the materialization
    that family paid for), while ``sweep_host_syncs`` counts blocking
    device->host settle BARRIERS for the whole sweep — on the async
    overlapped path every family's metrics settle behind ONE
    ``jax.block_until_ready``, so the run-level count stays 1 however
    many families/depth-groups dispatched (the tentpole assertion:
    O(1) syncs per ``train()``, not O(families + depth-groups)).
    ``async_families`` counts families whose metrics were held as device
    futures past their dispatch; ``refit_warm_starts`` counts winner
    refits that reused sweep state (stacked fold parameters or the
    dataset-level tree bin codes) instead of cold-starting. The O(1)
    scalar label-stat pull at dispatch start (max/mean of y, shared by
    every family) stays uncounted, as the per-family lnb pulls always
    were."""

    def __init__(self):
        self.families: dict = {}  # family name -> SweepFamilyCounters
        self.sweep_host_syncs = 0   # blocking settle barriers, whole sweep
        self.async_families = 0     # families overlapped past dispatch
        self.refit_warm_starts = 0  # winner refits reusing sweep state
        #: bytes of matrix-sized operands the selector materialised beyond
        #: the resident training matrix (split, fold batch, standardized
        #: copies)
        self.operand_bytes = 0
        #: host feature engineering of string columns: hashed columns
        #: that fell to the per-row loop
        self.fe_hash_fallbacks = 0
        #: hashed columns whose values do not repeat (free text): tokens
        #: hashed, (row, slot) entries they made, and rows that took the
        #: Python tokenizer (not ASCII, or past the native row length)
        self.fe_text_tokens = 0
        self.fe_text_entries = 0
        self.fe_text_python_rows = 0
        #: ``models/trees.py::predict_tree`` traces that kept a per-row
        #: gather (a frame wider, or a tree deeper, than its
        #: compare-and-select lookups take)
        self.tree_gather_walks = 0
        #: rows ``predict_tree`` added to whole tiles of the TPU's lanes
        #: before a walk that compares against whole tables, summed over
        #: its traces
        self.tree_walk_padded_rows = 0
        #: families (or tree lane groups) that left the fold-stacked path
        #: for the per-fold loop, by the reason the selector observed
        self.loop_fallbacks: dict = {}
        #: forest programs dispatched: rows times trees they were given,
        #: and rows times trees their growers carried (the rows a round's
        #: bootstrap can draw: ``models/trees.py::forest_rows_carried``)
        self.forest_rows_total = 0
        self.forest_rows_carried = 0
        #: linear lanes (row weighting x grid point, or one warm refit)
        #: trained by ``models/linear.py::_linear_descent``, the Adam
        #: descent, and not solved
        self.linear_descent_lanes = 0
        #: those of them whose every Adam step read the matrix once
        #: (``models/linear.py::_onepass_terms``)
        self.linear_onepass_lanes = 0
        #: the telemetry's process-lifetime per-family compile counts when
        #: this run began
        self._compiles_at_reset: dict = {}

    def reset(self) -> None:
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        self.families = {}
        self.sweep_host_syncs = 0
        self.async_families = 0
        self.refit_warm_starts = 0
        self.operand_bytes = 0
        self.fe_hash_fallbacks = 0
        self.fe_text_tokens = 0
        self.fe_text_entries = 0
        self.fe_text_python_rows = 0
        self.tree_gather_walks = 0
        self.tree_walk_padded_rows = 0
        self.loop_fallbacks = {}
        self.forest_rows_total = 0
        self.forest_rows_carried = 0
        self.linear_descent_lanes = 0
        self.linear_onepass_lanes = 0
        self._compiles_at_reset = compile_telemetry.family_compiles()

    def compiles(self, name: str) -> int:
        """XLA backend compiles attributed to family ``name`` this run."""
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        return (compile_telemetry.family_compiles().get(name, 0)
                - self._compiles_at_reset.get(name, 0))

    def family(self, name: str) -> SweepFamilyCounters:
        return self.families.setdefault(name, SweepFamilyCounters())

    def count(self, name: str, *, dispatches: int = 0,
              host_syncs: int = 0, stacked_groups: int = 0,
              lane_chunks: int = 0, mode: Optional[str] = None) -> None:
        fc = self.family(name)
        fc.device_dispatches += dispatches
        fc.host_syncs += host_syncs
        fc.stacked_groups += stacked_groups
        fc.lane_chunks += lane_chunks
        if mode is not None:
            fc.mode = mode

    def count_run(self, *, host_syncs: int = 0, async_families: int = 0,
                  refit_warm_starts: int = 0, operand_bytes: int = 0,
                  fe_hash_fallbacks: int = 0,
                  fe_text_tokens: int = 0,
                  fe_text_entries: int = 0, fe_text_python_rows: int = 0,
                  tree_gather_walks: int = 0,
                  tree_walk_padded_rows: int = 0,
                  forest_rows_total: int = 0, forest_rows_carried: int = 0,
                  linear_descent_lanes: int = 0,
                  linear_onepass_lanes: int = 0,
                  loop_fallback: Optional[str] = None) -> None:
        """Run-level accounting (see class docstring): settle barriers,
        overlapped families, warm-started refits, operand copies, the
        host string work that fed the sweep, tree walks traced with a
        per-row gather and the rows tree walks padded, the rows a
        dispatched forest program was given and carried, the linear lanes
        that took the Adam descent and those of them that took its one-pass
        step, and (``loop_fallback``: the reason) one unit that left the
        stacked path for the per-fold loop."""
        self.sweep_host_syncs += host_syncs
        self.async_families += async_families
        self.refit_warm_starts += refit_warm_starts
        self.operand_bytes += int(operand_bytes)
        self.fe_hash_fallbacks += int(fe_hash_fallbacks)
        self.fe_text_tokens += int(fe_text_tokens)
        self.fe_text_entries += int(fe_text_entries)
        self.fe_text_python_rows += int(fe_text_python_rows)
        self.tree_gather_walks += int(tree_gather_walks)
        self.tree_walk_padded_rows += int(tree_walk_padded_rows)
        self.forest_rows_total += int(forest_rows_total)
        self.forest_rows_carried += int(forest_rows_carried)
        self.linear_descent_lanes += int(linear_descent_lanes)
        self.linear_onepass_lanes += int(linear_onepass_lanes)
        if loop_fallback is not None:
            self.loop_fallbacks[loop_fallback] = \
                self.loop_fallbacks.get(loop_fallback, 0) + 1

    def to_json(self) -> dict:
        return {name: {"mode": fc.mode, "compiles": self.compiles(name),
                       "deviceDispatches": fc.device_dispatches,
                       "hostSyncs": fc.host_syncs,
                       "stackedGroups": fc.stacked_groups,
                       "laneChunks": fc.lane_chunks}
                for name, fc in self.families.items()}

    def run_to_json(self) -> dict:
        """The run-level one-sync counters (separate from the per-family
        ``to_json`` map so existing consumers keep their shape), and the
        span recorder's garbage-collector counts since its ``reset()``:
        collections by generation and their pause seconds."""
        from transmogrifai_tpu.utils.tracing import recorder
        # a run that dispatched no forest program reports no forest rows
        forest = ({"forestRowsTotal": self.forest_rows_total,
                   "forestRowsCarried": self.forest_rows_carried}
                  if self.forest_rows_total else {})
        return {"sweepHostSyncs": self.sweep_host_syncs,
                "asyncFamilies": self.async_families,
                "refitWarmStarts": self.refit_warm_starts,
                "sweepOperandBytes": self.operand_bytes,
                "feHashPerRowFallbacks": self.fe_hash_fallbacks,
                "feTextTokens": self.fe_text_tokens,
                "feTextEntries": self.fe_text_entries,
                "feTextPythonRows": self.fe_text_python_rows,
                "treeGatherWalks": self.tree_gather_walks,
                "treeWalkPaddedRows": self.tree_walk_padded_rows,
                "linearDescentLanes": self.linear_descent_lanes,
                "linearOnePassLanes": self.linear_onepass_lanes,
                "sweepLoopFallbacks": sum(self.loop_fallbacks.values()),
                "sweepLoopFallbackReasons": dict(self.loop_fallbacks),
                "gcCollections": {str(g): n for g, n in
                                  sorted(recorder.gc_collections.items())},
                "gcPauseSeconds": recorder.gc_pause_s,
                **forest}


sweep_counters = SweepCounters()


@dataclass
class RunCounters:
    """Fault-tolerance observability for one run (reset with the profiler).

    The resumable-training and retry contracts are asserted through these:
    a checkpoint-resumed ``Workflow.train`` reports how many DAG layers it
    replayed from disk instead of refitting (``layers_resumed`` /
    ``stages_resumed``) vs fit live (``layers_fitted``), every transient
    device retry performed by ``utils.retry.with_device_retry`` counts in
    ``retries``, and every fault injected by an active ``utils.faults``
    plan counts in ``faults_injected``. Surfaced in ``AppMetrics.to_json``
    (runner result jsons) — the chaos suite's ground truth for "resumed
    without refitting".

    Process-global, like ``sweep_counters``: a ScoringServer retrying on
    its worker thread while a training run executes lands in the same
    ``retries`` total (serving has its own exact per-server retry metric,
    ``ServingMetrics.dispatch_retries`` — use that for serving). One
    runner/workflow run per process is the accounting model."""

    layers_fitted: int = 0
    layers_resumed: int = 0
    stages_resumed: int = 0
    retries: int = 0
    faults_injected: int = 0

    def reset(self) -> None:
        self.layers_fitted = 0
        self.layers_resumed = 0
        self.stages_resumed = 0
        self.retries = 0
        self.faults_injected = 0

    def to_json(self) -> dict:
        return {"layersFitted": self.layers_fitted,
                "layersResumed": self.layers_resumed,
                "stagesResumed": self.stages_resumed,
                "retries": self.retries,
                "faultsInjected": self.faults_injected}


run_counters = RunCounters()


@dataclass
class IngestCounters:
    """Fused-ingest/FE observability for one run (round 14; reset with the
    profiler, process-global like ``run_counters``).

    The device-resident FE contract is asserted through these: with
    ``TRANSMOGRIFAI_FE_FUSED=1`` every all-device DAG segment runs as one
    fused program (``fe_fused_programs``/``fe_fused_stages``; OFF must
    leave both at exactly 0 — the byte-for-byte pre-fusion path), an OOM
    inside a segment takes the stagewise rung (``fe_host_fallbacks``, rows
    re-applied stage-by-stage land in ``fe_host_rows``), the streaming
    double buffer prefetches chunk N+1 while chunk N computes
    (``chunks_prefetched``, blocked-consumer seconds in
    ``prefetch_wait_s``, background decode seconds in ``decode_s``), the
    fingerprint-keyed device-frame cache skips identical host->device
    re-transfers (``frame_cache_reuses``/``stores``; pressure drops in
    ``frame_cache_drops``), and mesh placement skips device_puts whose
    operand already carries the target sharding (``presharded_skips`` —
    the "sweep consumes pre-partitioned operands" handoff).

    Row counts are stage-rows (rows x stages applied), so fused vs
    host-side FE shares compare directly however segments split."""

    fe_fused_programs: int = 0
    fe_fused_stages: int = 0
    fe_fused_rows: int = 0
    fe_host_rows: int = 0
    fe_host_fallbacks: int = 0
    chunks_prefetched: int = 0
    prefetch_wait_s: float = 0.0
    decode_s: float = 0.0
    frame_cache_reuses: int = 0
    frame_cache_stores: int = 0
    frame_cache_drops: int = 0
    presharded_skips: int = 0

    def reset(self) -> None:
        self.fe_fused_programs = 0
        self.fe_fused_stages = 0
        self.fe_fused_rows = 0
        self.fe_host_rows = 0
        self.fe_host_fallbacks = 0
        self.chunks_prefetched = 0
        self.prefetch_wait_s = 0.0
        self.decode_s = 0.0
        self.frame_cache_reuses = 0
        self.frame_cache_stores = 0
        self.frame_cache_drops = 0
        self.presharded_skips = 0

    def to_json(self) -> dict:
        return {"feFusedPrograms": self.fe_fused_programs,
                "feFusedStages": self.fe_fused_stages,
                "feFusedRows": self.fe_fused_rows,
                "feHostRows": self.fe_host_rows,
                "feHostFallbacks": self.fe_host_fallbacks,
                "chunksPrefetched": self.chunks_prefetched,
                "prefetchWaitSeconds": self.prefetch_wait_s,
                "decodeSeconds": self.decode_s,
                "frameCacheReuses": self.frame_cache_reuses,
                "frameCacheStores": self.frame_cache_stores,
                "frameCacheDrops": self.frame_cache_drops,
                "preshardedSkips": self.presharded_skips}


ingest_counters = IngestCounters()


@dataclass
class ServingBucketCounters:
    """Per-padding-bucket online-serving observability (``ServingCounters``)."""
    compiles: int = 0    # XLA backend compiles while this bucket dispatched
    dispatches: int = 0  # fused-program invocations padded to this bucket
    #: shared-cache entries for this bucket dropped by the fleet cache's
    #: HBM-budget LRU (serving/fleet.ProgramCache) — a nonzero steady
    #: state means the budget is too small for the working set and the
    #: next dispatch at this bucket pays a recompile
    evictions: int = 0


class ServingCounters:
    """Online-serving compile observability per padding bucket.

    The serving compile-cache contract (``serving/compiled.py``): batches
    pad to power-of-two buckets, so after one warmup dispatch per bucket
    the fused layer programs are all jit-cache hits — steady-state serving
    never recompiles. Counters here make that assertable: the bench and
    tests snapshot per-bucket compiles after warmup and require 0 new ones
    under traffic. Dispatches are counted at the batch granularity (one
    ``score_batch`` = one dispatch, however many fused layers it runs).

    One instance per ``CompiledScorer``, fed by the SCORER measuring its
    own fused programs' jit-cache growth per dispatch — NOT the global
    ``jax.monitoring`` compile listener (``devicewatch.CompileTelemetry``,
    which ``SweepCounters`` reads): monitoring
    events are process-wide, so two servers dispatching concurrently would
    cross-attribute each other's compiles (and per-instance listeners can
    never unregister). Cache-entry deltas are exact, per-program, and
    leak-free; "compiles" here means new fused-program instantiations
    (shape-keyed traces), the thing steady-state serving must not do."""

    def __init__(self):
        self.buckets: dict[int, ServingBucketCounters] = {}

    def reset(self) -> None:
        self.buckets = {}

    def bucket(self, size: int) -> ServingBucketCounters:
        return self.buckets.setdefault(int(size), ServingBucketCounters())

    def count(self, size: int, *, dispatches: int = 0,
              compiles: int = 0, evictions: int = 0) -> None:
        c = self.bucket(size)
        c.dispatches += dispatches
        c.compiles += compiles
        c.evictions += evictions

    def compiles_by_bucket(self) -> dict:
        return {b: c.compiles for b, c in sorted(self.buckets.items())}

    def evictions_by_bucket(self) -> dict:
        return {b: c.evictions for b, c in sorted(self.buckets.items())}

    def to_json(self) -> dict:
        return {str(b): {"compiles": c.compiles, "dispatches": c.dispatches,
                         "evictions": c.evictions}
                for b, c in sorted(self.buckets.items())}


def aggregate_across_hosts(metrics: AppMetrics, ctx=None,
                           timeout_s: Optional[float] = None) -> dict:
    """One run summary from per-host metrics: phase and stage wall /
    device / count totals summed across every host of the mesh through
    ``parallel.collectives.reduce_host_metrics`` (the same deadline-guarded
    all-reduce training statistics ride). Each host calls this with ITS
    ``AppMetrics`` after ``finalize()``; the returned json carries the
    pod-wide sums plus ``hosts``. With no mesh context the local summary
    returns unchanged (``hosts`` reflects ``jax.process_count()``) —
    single-host runs pay nothing."""
    doc = metrics.to_json()
    try:
        import jax
        doc["hosts"] = int(jax.process_count())
    except Exception:  # failure-ok: no jax backend -> single host
        doc["hosts"] = 1
    if ctx is None:
        return doc
    from transmogrifai_tpu.parallel.collectives import reduce_host_metrics
    flat: dict[str, float] = {}
    for ph, p in metrics.phases.items():
        flat[f"phase\t{ph}\twallSeconds"] = p.wall_s
        flat[f"phase\t{ph}\tdeviceSeconds"] = p.device_s
        flat[f"phase\t{ph}\tcount"] = float(p.count)
    for st, v in metrics.stages.items():
        flat[f"stage\t{st}\twallSeconds"] = v.get("wallSeconds", 0.0)
        flat[f"stage\t{st}\tdeviceSeconds"] = v.get("deviceSeconds", 0.0)
        flat[f"stage\t{st}\tcount"] = float(v.get("count", 0))
    reduced = reduce_host_metrics(ctx, flat, timeout_s=timeout_s)
    for key, val in reduced.items():
        kind, name, field_ = key.split("\t")
        dst = doc["phases"] if kind == "phase" else doc["stages"]
        entry = dst.setdefault(name, {})
        entry[field_] = int(round(val)) if field_ == "count" else val
    return doc


class _Profiler:
    def __init__(self):
        self.metrics = AppMetrics()
        self.trace_dir: Optional[str] = None
        self._tracing = False
        self._anchor_epoch_s: Optional[float] = None
        #: per-open-phase accumulated child seconds (exclusive-wall stack)
        self._stack: list[float] = []

    def reset(self, app_name: str = "transmogrifai_tpu",
              trace_dir: Optional[str] = None) -> AppMetrics:
        """New metrics object; with ``trace_dir``, starts one jax.profiler
        trace spanning everything until ``finalize()``. Sweep and run
        counters reset alongside so a run's counters cover exactly that
        run."""
        from transmogrifai_tpu.utils.devicewatch import reset_run
        from transmogrifai_tpu.utils.resources import resource_counters
        from transmogrifai_tpu.utils.tracing import recorder
        sweep_counters.reset()
        run_counters.reset()
        ingest_counters.reset()
        resource_counters.reset()
        recorder.reset()
        reset_run()  # the HBM timeline covers exactly this run's trace
        self.metrics = AppMetrics(app_name=app_name)
        self.trace_dir = trace_dir
        if self._tracing:  # a previous run never finalized: stop its trace
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:  # failure-ok: stale-trace stop is best-effort
                pass
            self._tracing = False
        if trace_dir is not None:
            try:
                import jax
                # lean trace: the device timeline and the program's own
                # annotations (host level 1; no python events, no HLO
                # protos) so post-run parsing stays cheap even for
                # multi-minute runs
                opts = None
                try:
                    opts = jax.profiler.ProfileOptions()
                    opts.host_tracer_level = 1
                    opts.python_tracer_level = 0
                    opts.enable_hlo_proto = False
                except Exception:  # failure-ok: ProfileOptions API is version-dependent
                    opts = None
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                self._tracing = True
                # the anchor: one annotation whose place on the trace's
                # clock, against the host clock read beside it, puts the
                # device events on the host's clock at finalize()
                self._anchor_epoch_s = time.time()
                with jax.profiler.TraceAnnotation(TRACE_ANCHOR):
                    pass
            except Exception:  # failure-ok: tracing optional; run continues untraced
                self.trace_dir = None
        return self.metrics

    def finalize(self) -> AppMetrics:
        """Stop the run trace (if any), read it, and attribute device time:
        to phases by time, to the ``(module, scope)`` table by what each op
        is, and to the spans that are device windows (module docstring).
        Freezes the run's end timestamp and rolls the span recorder's
        per-stage aggregation into ``metrics.stages``. Idempotent; safe
        without a trace (device_s stays 0). A trace that was written and
        cannot be read raises."""
        from transmogrifai_tpu.utils.tracing import recorder
        if self._tracing:
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                self._tracing = False
            events = trace_device_events(self.trace_dir,
                                         self._anchor_epoch_s)
            self.metrics.device_events = events
            self.metrics.attribute_device_time(
                [(ev.start_s, ev.duration_s) for ev in events])
            self.metrics.attribute_device_scopes(events)
            recorder.attribute_device_windows(events)
        if self.metrics.end_time is None:
            self.metrics.end_time = time.time()
        self.metrics.stages = stages = recorder.stage_table()
        # a stage's ops inside a fused FE program are staged under the
        # scope "<operation>[<uid>]": what the op is names its stage
        by_uid = {label.rpartition(" (")[2][:-1]: row
                  for label, row in stages.items()}
        for (_module, scope), (seconds, _n) in \
                self.metrics.device_scopes.items():
            uid = scope.partition("[")[2].partition("]")[0]
            if uid in by_uid:
                by_uid[uid]["deviceSeconds"] += seconds
        return self.metrics

    @contextlib.contextmanager
    def phase(self, step: OpStep):
        t0 = time.time()
        _, peak_before = _device_memory()
        self._stack.append(0.0)
        try:
            yield
        finally:
            if self._tracing:
                # JAX dispatch is async: without a fence, device ops
                # enqueued near phase end can execute after the wall
                # window closes and be misattributed to the next phase.
                # Each device executes programs in enqueue order, so
                # blocking on one trivial computation PER local device
                # drains everything enqueued before it (sharded runs
                # enqueue on every mesh device, not just device 0).
                try:
                    import jax
                    from transmogrifai_tpu.utils.devicewatch import (
                        compile_telemetry,
                    )
                    with compile_telemetry.building("profiler.fence"):
                        jax.block_until_ready(
                            [jax.device_put(0.0, dev) + 0
                             for dev in jax.local_devices()])
                except Exception:  # failure-ok: drain fence is best-effort
                    pass
            # record on the error path too — a failed run's post-mortem
            # must still account the time spent before the failure
            t1 = time.time()
            _, peak_after = _device_memory()
            # peak_bytes_in_use is a process-lifetime high-water mark:
            # attribute it to this phase only when THIS phase raised it
            grew = peak_after if peak_after > peak_before else 0
            child_s = self._stack.pop()
            if self._stack:  # bubble own elapsed up to the enclosing phase
                self._stack[-1] += t1 - t0
            # exclusive wall: nested phases (e.g. the selector's CV inside
            # the workflow's FeatureEngineering) don't double-count
            self.metrics.record(step, (t1 - t0) - child_s, peak_hbm=grew)
            self.metrics.spans.append((step.value, t0, t1))


profiler = _Profiler()
phase = profiler.phase
