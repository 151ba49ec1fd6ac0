"""Reader for the ``.xplane.pb`` file a ``jax.profiler`` trace leaves.

``jax.profiler.ProfileData`` (the reader that ships with JAX) yields a
trace's planes, lines and events, but not the statistics of an event's
METADATA entry — and that is where the TPU profiler keeps what an op *is*:
``tf_op`` (the op's ``op_name``: the ``jax.named_scope`` path it was staged
under), ``program_id`` (the module it belongs to), ``hlo_category``. So the
file is read here directly, from its protobuf wire format (the ``XSpace``
schema of ``tsl/profiler/protobuf/xplane.proto``), with no protobuf
runtime: only the handful of fields this program reads are decoded, every
other field is skipped by its length.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field

__all__ = ["XPlane", "XLine", "read_xspace"]


@dataclass
class XLine:
    name: str = ""
    timestamp_ns: int = 0
    #: ``(metadata_id, offset_ps, duration_ps)``, offsets from the line's
    #: ``timestamp_ns``
    events: list = field(default_factory=list)


@dataclass
class XPlane:
    name: str = ""
    lines: list = field(default_factory=list)
    event_names: dict = field(default_factory=dict)     # metadata id -> name
    event_display: dict = field(default_factory=dict)   # id -> display name
    #: metadata id -> {stat name: value} (what ``ProfileData`` leaves out)
    event_stats: dict = field(default_factory=dict)


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        if i > n:
            raise ValueError("truncated protobuf field: not an XSpace file")
        yield key >> 3, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf):
    """``(stat metadata id, value)`` of one ``XStat``; a ``ref_value`` comes
    back as ``("ref", id)`` for the caller to resolve."""
    sid, val = 0, None
    for no, wire, v in _fields(buf):
        if no == 1:
            sid = v
        elif no == 2:
            val = struct.unpack("<d", v)[0]
        elif no == 3:
            val = v
        elif no == 4:
            val = _signed(v)
        elif no == 5:
            val = str(v, "utf-8", "replace")
        elif no == 6:
            val = bytes(v)
        elif no == 7:
            val = ("ref", v)
    return sid, val


def _named_stats(raw: list, stat_names: dict) -> dict:
    out = {}
    for sid, val in raw:
        if isinstance(val, tuple):
            val = stat_names.get(val[1], "")
        out[stat_names.get(sid, str(sid))] = val
    return out


def _map_entry(buf):
    key, val = 0, b""
    for no, _wire, v in _fields(buf):
        if no == 1:
            key = _signed(v)
        elif no == 2:
            val = v
    return key, val


def _line(buf) -> XLine:
    line = XLine()
    for no, _wire, v in _fields(buf):
        if no == 2:
            line.name = str(v, "utf-8", "replace")
        elif no == 3:
            line.timestamp_ns = _signed(v)
        elif no == 4:
            mid = off = dur = 0
            for eno, _w, ev in _fields(v):
                if eno == 1:
                    mid = _signed(ev)
                elif eno == 2:
                    off = _signed(ev)
                elif eno == 3:
                    dur = _signed(ev)
            line.events.append((mid, off, dur))
    return line


def _plane(buf) -> XPlane:
    plane = XPlane()
    stat_names: dict = {}
    raw_meta: dict = {}      # id -> raw XStat list, named once all are read
    for no, _wire, v in _fields(buf):
        if no == 2:
            plane.name = str(v, "utf-8", "replace")
        elif no == 3:
            plane.lines.append(_line(v))
        elif no == 4:
            mid, meta = _map_entry(v)
            stats = []
            for mno, _w, mv in _fields(meta):
                if mno == 2:
                    plane.event_names[mid] = str(mv, "utf-8", "replace")
                elif mno == 4:
                    plane.event_display[mid] = str(mv, "utf-8", "replace")
                elif mno == 5:
                    stats.append(_stat(mv))
            raw_meta[mid] = stats
        elif no == 5:
            sid, meta = _map_entry(v)
            for mno, _w, mv in _fields(meta):
                if mno == 2:
                    stat_names[sid] = str(mv, "utf-8", "replace")
    plane.event_stats = {mid: _named_stats(stats, stat_names)
                         for mid, stats in raw_meta.items() if stats}
    return plane


def read_xspace(path: str) -> list[XPlane]:
    """The planes of the ``XSpace`` stored at ``path``. Raises on a file
    that is not one (``ValueError``/``IndexError`` from the decoder)."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    return [_plane(v) for no, wire, v in _fields(buf)
            if no == 1 and wire == 2]
