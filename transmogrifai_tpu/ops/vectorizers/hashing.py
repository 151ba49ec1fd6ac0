"""Hashing-trick vectorizer for unbounded-cardinality text.

Parity: reference ``core/.../stages/impl/feature/OPCollectionHashingVectorizer
.scala`` / ``OpHashingTF.scala`` — tokens hash into a fixed number of bins
(default 512, max 2^17 in the reference Transmogrifier defaults), shared or
separate hash space per input, optional binary (presence) vs count values,
plus a null-indicator per input.

Host/device split (SURVEY §7 hard part #2): tokenization + hashing are
string work and run on host into a dense [n, bins] block; everything
downstream consumes the device VectorColumn. The hash is crc32 (stable,
seedable by bin count) — numeric parity with Spark's murmur3 is not a
behavioral contract, bin distribution quality is.
"""

from __future__ import annotations

import os
import re
import zlib
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.stages.base import DeviceTransformer, HostTransformer
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.vector_metadata import (
    parent_of,
    NULL_INDICATOR, VectorColumnMetadata, VectorMetadata,
)

__all__ = ["TextHashingVectorizer", "DeviceTextHashingVectorizer",
           "hash_token", "encode_ascii_rows", "ColumnEntries",
           "text_column_entries"]

_TOKEN_RE = re.compile(r"[^\W_]+", re.UNICODE)

#: ctypes handle to the native tokenizer+hasher (None -> pure Python)
_native_lib = None
_native_tried = False


def _native():
    """Build/load the C++ tokenizer-hasher once (None when unavailable).
    Registers BOTH entry points (per-row batch + corpus histogram) so every
    consumer shares one loader and one tokenizer contract."""
    global _native_lib, _native_tried
    if not _native_tried:
        _native_tried = True
        from transmogrifai_tpu.native import build_and_load
        lib = build_and_load("texthash")
        if lib is not None:
            import ctypes
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.hash_tokens_batch.argtypes = [
                ctypes.c_char_p, i64p,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32,
                np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
                ctypes.c_int64, ctypes.c_int64,
            ]
            lib.hash_tokens_batch.restype = None
            lib.hash_tokens_hist.argtypes = [
                ctypes.c_char_p, i64p,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
            ]
            lib.hash_tokens_hist.restype = None
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
            lib.hash_tokens_entries.argtypes = [
                ctypes.c_char_p, i64p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, i32p, i32p, i32p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
            ]
            lib.hash_tokens_entries.restype = ctypes.c_int64
        _native_lib = lib
    return _native_lib


#: native-eligibility row-length cap (protects the C 4096-byte token buffer
#: with margin; longer rows take the Python path)
_NATIVE_MAX_LEN = 4000


def encode_ascii_rows(values) -> Optional[tuple[bytes, np.ndarray, int]]:
    """(concatenated buffer, [n+1] offsets, null count) for the native
    tokenizer, or None when any row is ineligible (non-str/non-ASCII/too
    long — parity with the Python regex path is a contract). Shared by the
    vectorizer and the RawFeatureFilter distribution pass."""
    if not all(v is None or (isinstance(v, str) and v.isascii()
                             and len(v) <= _NATIVE_MAX_LEN) for v in values):
        return None
    n = len(values)
    parts: list[bytes] = []
    lens = np.zeros(n + 1, dtype=np.int64)
    nulls = 0
    for r in range(n):
        v = values[r]
        if v is None:
            nulls += 1
            continue  # zero-length row: no tokens
        b = v.encode("ascii")
        parts.append(b)
        lens[r + 1] = len(b)
    return b"".join(parts), np.cumsum(lens).astype(np.int64), nulls


@dataclass
class ColumnEntries:
    """A text column's hashed token counts that are not zero, in row order:
    row ``r`` owns ``slot[row_start[r]:row_start[r + 1]]`` (distinct within
    the row, ascending) and the counts beside them; ``length`` is each
    string's length in characters (0 for a null) and ``null`` the null
    flag. ``tokens`` counts the column's tokens, ``python_rows`` the rows
    that took the Python tokenizer."""
    row_start: np.ndarray
    slot: np.ndarray
    count: np.ndarray
    length: np.ndarray
    null: np.ndarray
    tokens: int
    python_rows: int


#: rows a native call tokenizes at a time: a chunk's joined text stays a
#: few megabytes, and the chunks run side by side (ctypes drops the GIL)
_ENTRY_CHUNK_ROWS = 2048


def _python_row_entries(text: str, num_bins: int) -> tuple[dict, int]:
    """``({slot: count}, tokens)`` of one string by the row path's own
    ``tokenize`` / ``hash_token``."""
    per: dict[int, int] = {}
    toks = tokenize(text)
    for tok in toks:
        b = hash_token(tok, num_bins)
        per[b] = per.get(b, 0) + 1
    return per, len(toks)


def text_column_entries(vals: np.ndarray, null: np.ndarray,
                        num_bins: int) -> Optional[ColumnEntries]:
    """The :class:`ColumnEntries` of an object column of strings (``null``
    marks its ``None``s) in ONE native pass: every eligible row is
    tokenized and hashed in C++ (``hash_tokens_entries``), a chunk of rows
    a call, with no Python a row. Eligible means what
    :func:`encode_ascii_rows` means, a ROW: a row that is not ASCII or is
    longer than ``_NATIVE_MAX_LEN`` goes through ``tokenize`` /
    ``hash_token`` alone and is counted in ``python_rows``; the rest of its
    column stays native. Equal to the row path to the bit. None where the
    column holds objects that are not strings."""
    n = len(vals)
    present = ~null
    try:
        chars = np.frompyfunc(len, 1, 1)(vals[present]).astype(np.int64)
        ascii_ = np.frompyfunc(str.isascii, 1, 1)(vals[present]).astype(bool)
    except TypeError:
        return None
    length = np.zeros(n, np.int64)
    length[present] = chars
    lib = _native()
    native = np.zeros(n, bool)
    if lib is not None:
        native[present] = ascii_ & (chars <= _NATIVE_MAX_LEN)
    per_row = np.zeros(n, np.int32)
    narrow = np.uint16 if num_bins <= 1 << 16 else np.int32
    slots, counts, tokens = [], [], 0
    if native.any():
        import ctypes
        import threading
        from concurrent.futures import ThreadPoolExecutor
        nbytes = np.where(native, length, 0)
        text = np.where(native, vals, "")
        starts = np.arange(0, n, _ENTRY_CHUNK_ROWS)
        # a row of L bytes holds at most (L + 1) / 2 tokens, and no more
        # distinct slots than there are bins
        caps = np.add.reduceat(np.minimum((nbytes + 1) // 2, num_bins),
                               starts)
        scratch = threading.local()   # a worker's output pages, touched once

        def chunk(i: int):
            s = int(starts[i])
            e = min(s + _ENTRY_CHUNK_ROWS, n)
            if not hasattr(scratch, "slot"):
                scratch.slot = np.empty(max(int(caps.max()), 1), np.int32)
                scratch.count = np.empty_like(scratch.slot)
            buf = "".join(text[s:e].tolist()).encode("ascii")
            offsets = np.zeros(e - s + 1, np.int64)
            np.cumsum(nbytes[s:e], out=offsets[1:])
            toks = ctypes.c_int64(0)
            wrote = lib.hash_tokens_entries(
                buf, offsets, e - s, num_bins, 1, per_row[s:e],
                scratch.slot, scratch.count, int(caps[i]),
                ctypes.byref(toks))
            assert wrote >= 0, "hash_tokens_entries: capacity too small"
            # a native row is at most _NATIVE_MAX_LEN characters: 16 bits
            return (scratch.slot[:wrote].astype(narrow),
                    scratch.count[:wrote].astype(np.uint16), toks.value)

        with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) \
                as pool:
            for slot, count, toks in pool.map(chunk, range(len(starts))):
                slots.append(slot)
                counts.append(count)
                tokens += toks
    slot = np.concatenate(slots) if slots else np.zeros(0, narrow)
    count = np.concatenate(counts) if counts else np.zeros(0, np.uint16)
    python_rows = np.nonzero(present & ~native)[0]
    if python_rows.size:
        py_slot, py_count = [], []
        for r in python_rows:
            per, toks = _python_row_entries(vals[r], num_bins)
            per_row[r] = len(per)
            for b, c in sorted(per.items()):
                py_slot.append(b)
                py_count.append(c)
            tokens += toks
        is_py = np.zeros(n, bool)
        is_py[python_rows] = True
        owner = np.repeat(is_py, per_row)
        native_slot, native_count = slot, count
        # a row of any length may count one token past 16 bits
        slot = np.empty(owner.size, narrow)
        count = np.empty(owner.size, np.int32)
        slot[~owner], count[~owner] = native_slot, native_count
        slot[owner], count[owner] = py_slot, py_count
    row_start = np.zeros(n + 1, np.int64)
    np.cumsum(per_row, out=row_start[1:])
    return ColumnEntries(row_start, slot, count, length, null, int(tokens),
                         int(python_rows.size))


def hash_token(token: str, num_bins: int) -> int:
    return zlib.crc32(token.encode("utf-8")) % num_bins


def tokenize(text: str, lowercase: bool = True) -> list[str]:
    if lowercase:
        text = text.lower()
    if text.isascii():
        return _TOKEN_RE.findall(text)
    # space-less scripts (CJK/Thai) segment into character bigrams; the
    # script-aware analyzer lives with the text chain (never reaches the
    # native path, which is ASCII-only by contract)
    from transmogrifai_tpu.ops.text import simple_tokenize
    return simple_tokenize(text, lowercase=False)


class TextHashingVectorizer(HostTransformer):
    """N text inputs -> [n, N*(bins[+1])] hashed token counts."""

    variadic = True
    in_types = (ft.Text,)
    out_type = ft.OPVector

    def __init__(self, num_features: int = 512, binary_freq: bool = False,
                 lowercase: bool = True, track_nulls: bool = True,
                 shared_hash_space: bool = False,
                 uid: Optional[str] = None):
        self.num_features = num_features
        self.binary_freq = binary_freq
        self.lowercase = lowercase
        self.track_nulls = track_nulls
        self.shared_hash_space = shared_hash_space
        super().__init__(uid=uid)

    # -- hashing core --------------------------------------------------------
    def _accumulate(self, text: Optional[str], row: np.ndarray, offset: int):
        if text is None:
            return
        for tok in tokenize(text, self.lowercase):
            b = offset + hash_token(tok, self.num_features)
            if self.binary_freq:
                row[b] = 1.0
            else:
                row[b] += 1.0

    def _layout(self, n_inputs: int) -> tuple[int, list[int], int]:
        """(hash_width, per-input offsets, total_width)."""
        if self.shared_hash_space:
            hash_width = self.num_features
            offsets = [0] * n_inputs
        else:
            hash_width = self.num_features * n_inputs
            offsets = [self.num_features * i for i in range(n_inputs)]
        total = hash_width + (n_inputs if self.track_nulls else 0)
        return hash_width, offsets, total

    def transform_row(self, *values):
        hash_width, offsets, total = self._layout(len(values))
        row = np.zeros(total, dtype=np.float32)
        for i, v in enumerate(values):
            self._accumulate(v, row, offsets[i])
            if self.track_nulls and v is None:
                row[hash_width + i] = 1.0
        return row

    def _native_column(self, col: fr.HostColumn, out: np.ndarray,
                       col_offset: int) -> bool:
        """Hash one column via the C++ path. Returns False when the column
        needs the Python path (non-ASCII text or very long rows — the
        native tokenizer is exact only for ASCII; parity with the Python
        row path is a contract)."""
        lib = _native()
        if lib is None:
            return False
        encoded = encode_ascii_rows(col.values)
        if encoded is None:
            return False
        buf, offsets, _ = encoded
        lib.hash_tokens_batch(
            buf, offsets, np.int64(len(col)),
            np.int32(self.num_features), np.int32(self.lowercase),
            np.int32(self.binary_freq), out, np.int64(out.shape[1]),
            np.int64(col_offset))
        return True

    def host_apply(self, *cols: fr.HostColumn) -> fr.HostColumn:
        n = len(cols[0])
        hash_width, offsets, total = self._layout(len(cols))
        out = np.zeros((n, total), dtype=np.float32)
        for i, col in enumerate(cols):
            if not self._native_column(col, out, offsets[i]):
                for r in range(n):
                    self._accumulate(col.values[r], out[r], offsets[i])
            if self.track_nulls:
                for r in range(n):
                    if col.values[r] is None:
                        out[r, hash_width + i] = 1.0
        return fr.HostColumn(ft.OPVector, out, meta=self._meta(len(cols)))

    def _meta(self, n_inputs: int) -> VectorMetadata:
        feats = self.input_features
        hash_width, offsets, _ = self._layout(n_inputs)
        cols = []
        if self.shared_hash_space:
            all_names = tuple(f.name for f in feats)
            all_types = tuple(f.ftype.__name__ for f in feats)
            for j in range(self.num_features):
                cols.append(VectorColumnMetadata(
                    all_names, all_types, grouping=None,
                    descriptor_value=f"hash_{j}"))
        else:
            for f in feats:
                for j in range(self.num_features):
                    cols.append(VectorColumnMetadata(
                        *parent_of(f), grouping=f.name,
                        descriptor_value=f"hash_{j}"))
        if self.track_nulls:
            for f in feats:
                cols.append(VectorColumnMetadata(
                    *parent_of(f), grouping=f.name,
                    indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.get_output().name, tuple(cols)).reindexed(0)


class DeviceTextHashingVectorizer(DeviceTransformer):
    """Device-resident categorical feature hashing: N text inputs ->
    [n, N*bins (+N)] hashed one-hot counts, computed INSIDE the fused FE
    program (round 14).

    Semantics: each value hashes as ONE token (murmur3 x86_32 of its
    UTF-8 bytes — ``ops/hashing_pallas.murmur3_str``) — the categorical
    hashing-trick (Criteo-style high-cardinality id columns), not the
    token-bag hashing of :class:`TextHashingVectorizer` (which stays the
    right choice for free text). Layout matches the host vectorizer:
    per-input hash blocks first, then one null-indicator column per input.

    Execution split: hashing is per-UNIQUE — a trace-time murmur3 table
    over the column's dictionary vocab (aux data, exactly
    ``OneHotModel``'s category-table idiom, so the jit key moves only
    when the vocab does) — while the per-ROW work (the O(n x bins)
    one-hot accumulate the host vectorizer paid in Python) runs on
    device through ``ops/hashing_pallas.segment_onehot`` (Pallas kernel
    on TPU, XLA fallback elsewhere; bitwise-identical)."""

    variadic = True
    in_types = (ft.Text,)
    out_type = ft.OPVector

    def __init__(self, num_features: int = 512, track_nulls: bool = True,
                 seed: int = 0, uid: Optional[str] = None):
        self.num_features = num_features
        self.track_nulls = track_nulls
        self.seed = seed
        super().__init__(uid=uid)

    def _vocab_bins(self, vocab: Sequence[str]) -> np.ndarray:
        from transmogrifai_tpu.ops.hashing_pallas import murmur3_str
        if not vocab:
            return np.zeros(1, np.int32)
        return np.fromiter(
            (murmur3_str(v, self.seed) % self.num_features for v in vocab),
            np.int32, count=len(vocab))

    def device_apply(self, params, *cols: fr.CodesColumn) -> fr.VectorColumn:
        import jax.numpy as jnp

        from transmogrifai_tpu.ops.hashing_pallas import segment_onehot
        B = self.num_features
        blocks = []
        nulls = []
        for c in cols:
            table = jnp.asarray(self._vocab_bins(c.vocab))
            bins = jnp.where(c.codes >= 0, table[jnp.clip(c.codes, 0)],
                             jnp.int32(-1))
            blocks.append(segment_onehot(bins[:, None], B))
            if self.track_nulls:
                nulls.append((c.codes < 0).astype(jnp.float32)[:, None])
        parts = blocks + nulls
        out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        return fr.VectorColumn(out, self._meta(len(cols)))

    def transform_row(self, *values):
        from transmogrifai_tpu.ops.hashing_pallas import murmur3_str
        B = self.num_features
        n = len(values)
        width = n * B + (n if self.track_nulls else 0)
        row = np.zeros(width, np.float32)
        for i, v in enumerate(values):
            if v is None:
                if self.track_nulls:
                    row[n * B + i] = 1.0
            else:
                row[i * B + murmur3_str(v, self.seed) % B] += 1.0
        return row

    def _meta(self, n_inputs: int) -> VectorMetadata:
        feats = self.input_features
        cols = []
        for f in feats:
            for j in range(self.num_features):
                cols.append(VectorColumnMetadata(
                    *parent_of(f), grouping=f.name,
                    descriptor_value=f"hash_{j}"))
        if self.track_nulls:
            for f in feats:
                cols.append(VectorColumnMetadata(
                    *parent_of(f), grouping=f.name,
                    indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.get_output().name, tuple(cols)).reindexed(0)
