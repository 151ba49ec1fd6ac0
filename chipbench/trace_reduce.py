"""From a profiler trace to device metrics: busy share, kernel time, the
operations that took most time and the longest idle gaps by what the host
was doing. Reads ``.xplane.pb`` with nothing but ``jax.profiler``.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW_MARK = "chipbench.window"
_OPS_LINE = "XLA Ops"
#: ops that only enclose other ops: their time is their children's
CONTAINERS = ("while", "conditional", "call")
_MODULES_LINE = "XLA Modules"


@dataclass
class TraceSummary:
    window_ns: tuple                     # (start, end) on the trace's clock
    ops: dict = field(default_factory=dict)      # device -> [(s, e, name)]
    busy_ns: dict = field(default_factory=dict)  # device -> busy ns in window

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def kernel_seconds(self, match) -> float:
        """Device seconds of the ops whose ``module/op`` name ``match``
        accepts, summed over devices' ops and averaged over devices."""
        if not self.ops:
            return 0.0
        tot = sum(e - s for evs in self.ops.values()
                  for s, e, name in evs
                  if not _is_container(name) and match(name))
        return tot / len(self.ops) / 1e9

    def top_ops(self, k: int = 10) -> list:
        agg: dict = {}
        for evs in self.ops.values():
            for s, e, name in evs:
                if not _is_container(name):
                    agg[name] = agg.get(name, 0) + (e - s)
        n_dev = max(len(self.ops), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / n_dev / 1e9] for name, ns in top]

    def gaps(self, device=None) -> list:
        """Idle intervals ``[(start, end)]`` of one device in the window."""
        if not self.ops:
            return [self.window_ns]
        dev = device if device is not None else sorted(self.ops)[0]
        return _complement(_union(self.ops[dev]), self.window_ns)


def _is_container(name: str) -> bool:
    return name.rpartition("/")[2] in CONTAINERS


def _union(events) -> list:
    out = []
    for s, e, *_ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _complement(merged, window) -> list:
    lo, hi = window
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _clip(events, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def _short(op_name: str) -> str:
    """``fusion.123`` -> ``fusion``; keeps ``%`` and scope text out."""
    base = op_name.split(" ")[0].lstrip("%")
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def _module_name(name: str) -> str:
    return name.split("(")[0]


def summarize(planes, window_mark: str = WINDOW_MARK) -> TraceSummary:
    """Reduce the planes of a trace (``ProfileData.planes`` or objects of
    the same shape: ``.name``, ``.lines`` -> ``.name``, ``.events`` ->
    ``.name``, ``.start_ns``, ``.duration_ns``)."""
    window = None
    raw: dict = {}
    for plane in planes:
        is_dev = plane.name.startswith("/device:TPU")
        mods, ops = [], []
        for line in plane.lines:
            if is_dev and line.name == _MODULES_LINE:
                mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               _module_name(ev.name)) for ev in line.events)
            elif is_dev and line.name == _OPS_LINE:
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
            elif not is_dev and window is None:
                for ev in line.events:
                    if ev.name == window_mark:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        break
        if not is_dev or not ops:
            continue
        starts = [m[0] for m in mods]
        named = []
        for s, e, name in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
            named.append((s, e, f"{mod}/{_short(name)}"))
        raw[plane.name] = named
    if window is None:
        if not raw:
            raise ValueError("the trace holds neither the window mark nor "
                             "any device operation")
        window = (min(s for evs in raw.values() for s, _, _ in evs),
                  max(e for evs in raw.values() for _, e, _ in evs))
    out = TraceSummary(window_ns=window)
    for dev, evs in raw.items():
        evs = _clip(evs, window)
        out.ops[dev] = evs
        out.busy_ns[dev] = sum(e - s for s, e in _union(evs))
    return out


def load(trace_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(ProfileData.from_file(paths[-1]).planes)


def attribute_gaps(gaps_s: list, spans: list, k: int = 10) -> list:
    """Idle seconds by what the host was doing. ``gaps_s`` are ``(start,
    end)`` in seconds on the HOST's clock; ``spans`` are ``(start, end,
    name)`` host spans on the same clock, possibly nested: each instant of
    a gap goes to the innermost span that covers it (the one that started
    last), or to ``outside-any-span``."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)}
                  | {t for g in gaps_s for t in g})
    spans = sorted(spans)
    agg: dict = {}
    gi = 0
    gaps_s = sorted(gaps_s)
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        while gi < len(gaps_s) and gaps_s[gi][1] <= mid:
            gi += 1
        if gi >= len(gaps_s):
            break
        if not (gaps_s[gi][0] <= mid < gaps_s[gi][1]):
            continue
        inner = None
        for s, e, name in spans:
            if s > mid:
                break
            if e > mid:
                inner = name        # later start wins: innermost
        key = f"host:{inner}" if inner else "host:outside-any-span"
        agg[key] = agg.get(key, 0.0) + (b - a)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, sec] for name, sec in top]
