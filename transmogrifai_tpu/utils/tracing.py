"""Hierarchical run-wide span tracing — the Dapper-style host timeline.

Where ``utils/profiling.py`` buckets wall/device time into the eight
coarse ``OpStep`` phases (the reference's OpSparkListener granularity),
this module records the *tree*: every DAG stage fit, every fused layer
apply, every sweep family, every reader ingest, checkpoint write and
serving dispatch opens a :func:`span` whose parent is whatever span is
open on the same logical call context. The result answers "which
vectorizer is slow" the way the Spark UI's per-stage drill-down does —
and because each span also wraps a ``jax.profiler.TraceAnnotation`` (host
plane) and device dispatches run under ``jax.named_scope``, a
``jax.profiler`` run trace can be fused with this host tree into one
Perfetto/chrome://tracing JSON (``AppMetrics.export_chrome_trace``).

A span times the HOST. JAX dispatch is asynchronous, so a span around a
dispatch (``sweep.family``, ``fe.fused``) is milliseconds whatever the
program costs; device time is owned elsewhere: by the spans the selector
stamps from the device's own progress (``sweep.device``,
``refit.device``) and, under a trace, by ``(module, named scope)``
(``utils/profiling.py``).

Design constraints:

- **cheap when idle**: a disabled recorder costs one attribute check per
  instrumented call; an enabled one costs two ``time.time()`` calls and
  one list append per span. No locks on the hot enter path — the parent
  stack is a ``contextvars.ContextVar`` (thread- and task-local), and the
  finished-span list append holds a lock only briefly.
- **thread-safe by construction**: each thread/context gets its own
  parent stack, so serving worker spans interleave with a concurrent
  training run without corrupting either tree. Closed spans land in one
  shared, locked list.
- **bounded**: at most ``max_spans`` closed spans are retained in a ring
  — overflow evicts the OLDEST and counts ``dropped``, so a long-lived
  serving process (which records spans per batch with no consumer until
  someone exports a trace) holds bounded memory and always keeps its
  most recent activity.

The module-level :data:`recorder` is process-global like ``profiler``;
``profiler.reset()`` resets it so a run's span tree covers exactly that
run.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import contextvars
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

__all__ = ["Span", "SpanRecorder", "recorder", "span", "device_scope",
           "new_trace_id"]

#: request-scoped trace ids: a process-random prefix + a monotonic
#: counter. Collision-safe across processes (48 random bits) and ~0.2us
#: to mint — cheap enough for every admitted serving request (the
#: uuid module costs ~10x and the hot path pays per request).
_TRACE_PREFIX = None
_trace_ids = itertools.count(1)
_TRACE_RE = None


def new_trace_id() -> str:
    """Mint a request trace id (22 lowercase hex chars). Minted at HTTP
    ingress for requests without an inbound ``X-Trace-Id`` and carried
    through admission -> batch fan-in -> dispatch -> reply (see
    docs/OBSERVABILITY.md "Request-scoped tracing")."""
    global _TRACE_PREFIX
    if _TRACE_PREFIX is None:
        import os
        _TRACE_PREFIX = os.urandom(6).hex()
    return f"{_TRACE_PREFIX}{next(_trace_ids):010x}"


def sanitize_trace_id(raw) -> Optional[str]:
    """An inbound trace header is attacker-controlled text that lands in
    log lines and response headers: accept only modest [A-Za-z0-9._-]
    tokens, else ``None`` (the caller mints a fresh id)."""
    global _TRACE_RE
    if not isinstance(raw, str):
        return None
    if _TRACE_RE is None:
        import re
        _TRACE_RE = re.compile(r"^[A-Za-z0-9._\-]{1,64}$")
    raw = raw.strip()
    return raw if _TRACE_RE.match(raw) else None


@dataclass
class Span:
    """One closed span: a named wall interval with attributes and lineage."""
    span_id: int
    parent_id: Optional[int]
    name: str
    t0: float                   # epoch seconds (aligned with device events)
    t1: float
    thread: str
    attrs: dict = field(default_factory=dict)
    device_s: float = 0.0       # finalize(): device-window spans only
    peak_hbm_bytes: int = 0     # device peak growth while open (hbm=True)

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


#: per-context stack of open span ids — contextvars give each thread (and
#: each asyncio task, if one ever hosts spans) an isolated parent chain
_stack: contextvars.ContextVar[tuple[int, ...]] = contextvars.ContextVar(
    "transmogrifai_span_stack", default=())


@contextlib.contextmanager
def device_scope(name: str):
    """Best-effort ``jax.named_scope`` so ops staged out inside the block
    carry ``name`` in their XLA metadata (and thus in the device plane of
    a profiler trace). A plain no-op when jax is unavailable."""
    try:
        import jax
        cm = jax.named_scope(name)
    except Exception:  # failure-ok: naming device ops is optional polish
        cm = contextlib.nullcontext()
    with cm:
        yield


class SpanRecorder:
    """Thread-safe hierarchical span recorder (see module docstring)."""

    def __init__(self, max_spans: int = 200_000):
        self.max_spans = int(max_spans)
        self.enabled = True
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._spans: collections.deque = collections.deque(
            maxlen=self.max_spans)
        self.dropped = 0

    # -- lifecycle -----------------------------------------------------------
    def reset(self) -> None:
        with self._lock:
            self._spans = collections.deque(maxlen=self.max_spans)
            self._ids = itertools.count(1)
            self.dropped = 0

    def enable(self, on: bool = True) -> None:
        self.enabled = bool(on)

    @property
    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    # -- recording -----------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, hbm: bool = False, **attrs):
        """Open a span around the block. Attributes are arbitrary JSON-able
        values (stage uid, class, fold index, ...). Also opens a
        ``jax.profiler.TraceAnnotation`` so the host plane of a device
        trace shows the same interval. ``hbm=True`` additionally samples
        the device peak-memory high-water mark at enter/exit and records
        growth the block caused (used by per-stage spans; off by default —
        the serving hot path shouldn't pay the memory_stats probe)."""
        if not self.enabled:
            yield None
            return
        parent_stack = _stack.get()
        sid = next(self._ids)
        token = _stack.set(parent_stack + (sid,))
        annotation = self._annotation(name)
        peak_before = self._device_peak() if hbm else 0
        t0 = time.time()
        try:
            yield sid
        finally:
            t1 = time.time()
            if annotation is not None:
                try:
                    annotation.__exit__(None, None, None)
                except Exception:  # failure-ok: annotation teardown is best-effort
                    pass
            _stack.reset(token)
            grew = 0
            if hbm:
                peak_after = self._device_peak()
                # the peak is a process-lifetime high-water mark: charge
                # it to this span only when THIS span raised it
                grew = peak_after if peak_after > peak_before else 0
            self._store(Span(
                span_id=sid,
                parent_id=parent_stack[-1] if parent_stack else None,
                name=name, t0=t0, t1=t1,
                thread=threading.current_thread().name, attrs=attrs,
                peak_hbm_bytes=grew))

    @staticmethod
    def _device_peak() -> int:
        # the shared ALL-device census (utils/devicewatch.py): a sharded
        # span's memory lives on every mesh device, not device 0
        from transmogrifai_tpu.utils.devicewatch import device_memory
        return device_memory()[1]

    def add(self, name: str, t0: float, t1: float, *,
            parent_id: Optional[int] = None, thread: Optional[str] = None,
            **attrs) -> None:
        """Record a span retroactively from explicit epoch timestamps —
        for intervals measured elsewhere (e.g. a request's queue wait,
        which only becomes known when the batch picks it up)."""
        if not self.enabled:
            return
        self._store(Span(
            span_id=next(self._ids), parent_id=parent_id, name=name,
            t0=float(t0), t1=float(t1),
            thread=thread or threading.current_thread().name, attrs=attrs))

    def _annotation(self, name: str):
        try:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            return ann
        except Exception:  # failure-ok: host-plane annotation is optional
            return None

    def _store(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1  # ring: the oldest span is evicted
            self._spans.append(s)

    # -- device attribution ---------------------------------------------------
    def attribute_device_windows(self, events) -> None:
        """Fill ``device_s`` of the spans opened with ``device_window=True``:
        those whose wall window does hold the device ops run for them —
        a program's device interval stamped from the device's own progress
        (``sweep.device``, ``refit.device``), or a span that ends in a
        blocking device->host pull, so that everything enqueued inside has
        run by its end (``selector.sweep``, ``sweep.settle``,
        ``selector.refit``). Each gets the device seconds of the leaf ops
        (``.start_s``, ``.duration_s``) whose midpoint lies in its window;
        windows may nest, so these are inclusive sums like ``wall_s``.
        Every other span stays 0: spans are not fenced, and an op's time
        tells nothing about which open span it ran for — device time
        below the phases is owned through what the op is
        (``AppMetrics.device_scopes``)."""
        mids = sorted((ev.start_s + ev.duration_s / 2.0, ev.duration_s)
                      for ev in events)
        times = [m for m, _ in mids]
        cum = [0.0]
        for _, dur in mids:
            cum.append(cum[-1] + dur)
        for s in self.spans:
            if s.attrs.get("device_window"):
                s.device_s = (cum[bisect.bisect_right(times, s.t1)]
                              - cum[bisect.bisect_left(times, s.t0)])

    # -- aggregation ----------------------------------------------------------
    def aggregate(self, key: str = "name") -> dict[str, dict]:
        """Roll closed spans up by ``key`` (``"name"`` or any attr name).
        Returns ``{group: {"wallSeconds", "deviceSeconds", "count",
        "maxWallSeconds"}}`` — wall here is INCLUSIVE (each span's own
        window), the right units for a top-K slowest-stages table."""
        out: dict[str, dict] = {}
        for s in self.spans:
            group = s.name if key == "name" else s.attrs.get(key)
            if group is None:
                continue
            g = out.setdefault(str(group), {
                "wallSeconds": 0.0, "deviceSeconds": 0.0, "count": 0,
                "maxWallSeconds": 0.0})
            g["wallSeconds"] += s.wall_s
            g["deviceSeconds"] += s.device_s
            g["count"] += 1
            g["maxWallSeconds"] = max(g["maxWallSeconds"], s.wall_s)
        return out

    def stage_table(self) -> dict[str, dict]:
        """Per-DAG-stage rollup: spans carrying a ``stage_uid`` attr,
        keyed ``"<operation> (<uid>)"`` so two instances of the same
        vectorizer stay distinguishable.

        Wall/count/HBM come only from spans with no ANCESTOR span carrying
        the same uid — the selector's ``selector.sweep``/``selector.refit``
        nest inside its ``stage.fit`` span, and summing parent and children
        would double-count the stage's wall. Device seconds sum over every
        span of the uid that carries any: only ``device_window`` spans do
        (``attribute_device_windows``), and the selector's two
        (``selector.sweep``, ``selector.refit``) are disjoint."""
        by_id = {s.span_id: s for s in self.spans}

        def has_same_uid_ancestor(s: Span, uid) -> bool:
            pid = s.parent_id
            while pid is not None:
                parent = by_id.get(pid)
                if parent is None:
                    return False
                if parent.attrs.get("stage_uid") == uid:
                    return True
                pid = parent.parent_id
            return False

        out: dict[str, dict] = {}
        for s in by_id.values():
            uid = s.attrs.get("stage_uid")
            if uid is None:
                continue
            label = f"{s.attrs.get('stage_cls', s.name)} ({uid})"
            g = out.setdefault(label, {
                "wallSeconds": 0.0, "deviceSeconds": 0.0, "count": 0,
                "peakHbmBytes": 0, "phase": s.attrs.get("phase", "")})
            g["deviceSeconds"] += s.device_s
            if has_same_uid_ancestor(s, uid):
                continue
            g["wallSeconds"] += s.wall_s
            g["count"] += 1
            g["peakHbmBytes"] = max(g["peakHbmBytes"], s.peak_hbm_bytes)
            if s.attrs.get("phase"):
                g["phase"] = s.attrs["phase"]
        return out

    # -- export ----------------------------------------------------------------
    def chrome_trace_events(self, pid: int = 1) -> list[dict]:
        """Closed spans as chrome://tracing complete ('X') events.
        Timestamps are epoch microseconds; one tid per recording thread."""
        tids: dict[str, int] = {}
        events: list[dict] = []
        for s in self.spans:
            tid = tids.setdefault(s.thread, len(tids) + 1)
            args = {k: v for k, v in s.attrs.items()}
            if s.device_s:
                args["device_s"] = round(s.device_s, 6)
            events.append({
                "name": s.name, "ph": "X", "pid": pid, "tid": tid,
                "ts": s.t0 * 1e6, "dur": max(s.t1 - s.t0, 0.0) * 1e6,
                "args": args})
        for thread, tid in tids.items():
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": thread}})
        return events


#: process-global recorder; ``profiler.reset()`` resets it per run
recorder = SpanRecorder()
span = recorder.span
