"""Dictionary encoding of text columns: the Criteo-scale ingest hot loop.

PipelineData turns categorical text columns into int32 codes + a sorted
vocabulary on first device use. The naive path (Python ``sorted(set)`` +
per-row dict lookups) crawls at Criteo widths (SURVEY §6: 26 categorical
columns x 10M+ rows), so the heavy pass is native:

- ASCII columns: one C++ pass (``native/dict_encode.cpp``) — open-addressing
  FNV hash over row byte-slices assigning first-seen ids; Python then sorts
  only the (small) unique set and remaps codes with one vectorized gather.
- everything else: ``np.unique(..., return_inverse=True)`` over a unicode
  array — C-speed sort-based encoding, no per-row interpreter work.
- tiny/ineligible columns: the original dict loop (also the parity oracle).

All three produce IDENTICAL output: codes into the sorted vocabulary, None
-> -1 (the contract ``pipeline_data._encode_text`` always had).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["dict_encode", "dict_encode_py", "scan_column"]

_native_lib = None
_native_tried = False


class _TrailingNul(Exception):
    """Column contains strings that differ only by trailing NUL bytes —
    they collapse in ANY fixed-width numpy layout ('a' == 'a\\x00' once
    zero-padded), so only the object-loop oracle encodes them correctly."""


def _check_trailing_nul(pvals: np.ndarray, fixed: np.ndarray) -> None:
    """Raise if zero-padding lost trailing NULs: compare true object
    lengths (one C loop) against the fixed-width readback lengths (which
    numpy strips trailing zeros from). Non-string objects (e.g. floats
    leaking into a text column — astype stringifies them) can't carry
    NULs, so they are exempt from the comparison."""
    if len(pvals) == 0:
        return
    try:
        # the common case in one C pass: strings that hold no NUL at all
        # lose none (a Python call a row took 0.3 s a million rows)
        if "\x00" not in "".join(pvals.tolist()):
            return
    except TypeError:       # not all strings: compare them one by one
        pass

    def _len(v):
        return len(v) if isinstance(v, (str, bytes)) else -1

    lens = np.frompyfunc(_len, 1, 1)(pvals).astype(np.int64)
    strings = lens >= 0
    if (np.char.str_len(fixed)[strings] != lens[strings]).any():
        raise _TrailingNul

#: below this row count the setup cost beats the native win
_NATIVE_MIN_ROWS = 4096


def _native():
    global _native_lib, _native_tried
    if not _native_tried:
        _native_tried = True
        from transmogrifai_tpu.native import build_and_load
        lib = build_and_load("dictenc")
        if lib is not None:
            import ctypes
            lib.dict_encode.argtypes = [
                ctypes.c_char_p,
                ctypes.c_int64,
                np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
                ctypes.c_int64,
                np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                ctypes.c_int64,
            ]
            lib.dict_encode.restype = ctypes.c_int64
        _native_lib = lib
    return _native_lib


def dict_encode_py(values) -> tuple[np.ndarray, list[str]]:
    """The original Python loop — parity oracle and small-column path."""
    vocab = sorted({v for v in values if v is not None})
    index = {v: i for i, v in enumerate(vocab)}
    codes = np.fromiter(
        (index.get(v, -1) if v is not None else -1 for v in values),
        count=len(values), dtype=np.int32)
    return codes, vocab


def _encode_ascii(values, null_mask: np.ndarray
                  ) -> Optional[tuple[np.ndarray, list[str]]]:
    """C++ path for all-ASCII string columns; None when ineligible.

    The buffer is built with ONE vectorized ``astype('S')`` (numpy encodes
    every row in C) into a fixed-width zero-padded matrix — no per-row
    Python anywhere on this path."""
    lib = _native()
    if lib is None:
        return None
    n = len(values)
    present = null_mask == 0
    pvals = values[present]
    try:
        strs = pvals.astype("S")  # raises on non-ASCII
    except (TypeError, ValueError, UnicodeEncodeError):
        return None
    _check_trailing_nul(pvals, strs)
    width = strs.dtype.itemsize
    if width == 0:  # all-empty column
        width = 1
        strs = strs.astype("S1")
    buf = np.zeros(n, dtype=f"S{width}")
    buf[present] = strs
    codes = np.empty(n, dtype=np.int32)
    max_u = min(n, 1 << 22)
    rep_rows = np.empty(max_u, dtype=np.int64)
    import ctypes
    n_unique = lib.dict_encode(
        buf.ctypes.data_as(ctypes.c_char_p),  # zero-copy view of the matrix
        np.int64(width), null_mask, np.int64(n), codes, rep_rows,
        np.int64(max_u))
    if n_unique < 0:  # cardinality blew the cap: sort path handles it
        return None
    if n_unique == 0:  # all-null column
        return np.full(n, -1, dtype=np.int32), []
    # sort the uniques (small) and remap first-seen ids -> sorted ranks
    reps = rep_rows[:n_unique]
    vocab_bytes = buf[reps]
    order = np.argsort(vocab_bytes)
    rank = np.empty(n_unique, dtype=np.int32)
    rank[order] = np.arange(n_unique, dtype=np.int32)
    out = np.where(codes >= 0, rank[np.clip(codes, 0, None)],
                   np.int32(-1)).astype(np.int32)
    return out, [v.decode("ascii") for v in vocab_bytes[order]]


def dict_encode(values) -> tuple[np.ndarray, list[str]]:
    """codes (int32, -1 for missing) + sorted vocabulary for a text column."""
    n = len(values)
    if n < _NATIVE_MIN_ROWS:
        return dict_encode_py(values)
    vals = np.asarray(values, dtype=object)
    null_mask = np.equal(vals, None).astype(np.uint8)
    try:
        native = _encode_ascii(vals, null_mask)
    except _TrailingNul:
        return dict_encode_py(values)
    if native is not None:
        return native
    # numpy sort-based fallback (non-ASCII / no toolchain): still C-speed
    present = null_mask == 0
    pvals = vals[present]
    try:
        strs = pvals.astype("U")
    except (TypeError, ValueError):
        return dict_encode_py(values)
    try:
        _check_trailing_nul(pvals, strs)
    except _TrailingNul:
        return dict_encode_py(values)
    vocab, inv = np.unique(strs, return_inverse=True)
    codes = np.full(n, -1, dtype=np.int32)
    codes[present] = inv.astype(np.int32)
    return codes, [str(v) for v in vocab]


def scan_column(vals: np.ndarray) -> tuple[np.ndarray, bool]:
    """ONE Python-level pass over an object column -> (null_mask,
    all_strings).

    ``all_strings`` gates the vectorized dict-encode-backed paths
    (SmartText fit/apply, keyed-map pivot fills): the encoder stringifies
    non-string objects, which would skew category matching between batch
    sizes and against the per-row paths. Folding the null mask into the
    same pass keeps per-column object traffic to a single sweep on the
    Criteo-scale hot path."""
    kind = np.frompyfunc(
        lambda v: 0 if v is None else (1 if isinstance(v, str) else 2),
        1, 1)(vals).astype(np.int8)
    return kind == 0, not (kind == 2).any()
