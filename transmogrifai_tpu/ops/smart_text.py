"""SmartTextVectorizer: cardinality-adaptive text vectorization.

Parity: reference ``core/.../stages/impl/feature/SmartTextVectorizer.scala:
62-200`` — per-column ``TextStats`` (a value-count monoid capped at
``max_cardinality``) decides the treatment:

- all empty            -> null-indicator only ("ignore")
- low cardinality      -> categorical pivot (topK + OTHER + null)
- high cardinality     -> hashing trick (+ length feature + null indicator)

Optional name/sensitive-data detection (reference NameDetectFun /
HumanNameDetector): columns whose values look like human names beyond a
threshold are dropped and reported, when enabled (off by default, as in the
reference's SensitiveFeatureMode.Off).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.ops.vectorizers.hashing import hash_token, tokenize
from transmogrifai_tpu.ops.vectorizers.onehot import _top_k
from transmogrifai_tpu.stages.base import Estimator, HostTransformer
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.vector_metadata import (
    NULL_INDICATOR, OTHER, VectorColumnMetadata, VectorMetadata, parent_of,
)

__all__ = ["TextStats", "SmartTextVectorizer", "SmartTextModel",
           "COMMON_FIRST_NAMES", "looks_like_name"]


from transmogrifai_tpu.utils.dict_encode import \
    scan_column as _scan_column  # shared object-column scanner

#: the per-value device fill (``_dense_from_entries``) carries a value's
#: occupied hash slots as columns of a per-row table; a column whose values
#: occupy more (text, not an id) fills from per-row entries instead
_DEVICE_FILL_MAX_SLOTS = 8

#: rows the repetition scan adds to its set of seen values at a time
_SCAN_BLOCK = 8192


@functools.partial(jax.jit, static_argnames="layout")
def _dense_from_entries(idx, val, layout):
    """The ``[n, sum of widths]`` vector from each row's few entries that
    are not zero. ``layout`` is ``(width, groups)`` a column; a column owns
    ``groups`` consecutive columns of ``idx`` (position inside its block,
    -1 for none) and ``val``, and its block is the sum of its groups'
    one-hot rows: compares and selects the device writes out once, where
    the host fill touches every page of a dense matrix it then uploads."""
    n = idx.shape[0]
    out = jnp.zeros((n, sum(w for w, _ in layout)), jnp.float32)
    g = off = 0
    for width, groups in layout:
        at = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1)
        block = jnp.zeros((n, width), jnp.float32)
        for j in range(g, g + groups):
            block = block + jnp.where(at == idx[:, j, None],
                                      val[:, j, None], 0.0)
        out = jax.lax.dynamic_update_slice(out, block, (0, off))
        g += groups
        off += width
    return out


def entry_piece(n_rows: int) -> int:
    """Entries a call of ``_fill_text_entries`` takes: a power of two set by
    the ROWS alone (eight a row, between 2**12 and 2**22). A column's
    entries go up in as many whole pieces as hold them, the last filled up,
    so that no compiled shape follows how many tokens a table drew."""
    return 1 << min(max((8 * max(n_rows, 1) - 1).bit_length(), 12), 22)


@functools.partial(jax.jit, static_argnames="width")
def _fill_text_entries_block(length, null, width):
    """A hashed column's zeroed ``[n, width]`` block with what follows the
    hash slots written: the text length and the null indicator, where
    given (a ``[n, 0]`` operand stands for one not tracked)."""
    with jax.named_scope("fe.text_fill"):
        n = length.shape[0]
        tail = jnp.concatenate([length.astype(jnp.float32),
                                null.astype(jnp.float32)], axis=1)
        return jnp.concatenate(
            [jnp.zeros((n, width - tail.shape[1]), jnp.float32), tail],
            axis=1)


def _fill_text_entries(block, row_start, slot, count, base):
    """One piece of a column's entries written into its block: entry ``e``
    of the piece is entry ``base + e`` of the column, whose row is the
    number of row boundaries ``row_start[1:n]`` at or before it; entries
    past the column's last fall outside the block and are dropped. The
    (row, slot) positions are distinct, so the scatter sets and need not
    add; that they also ascend is NOT promised to XLA: on the TPU
    ``indices_are_sorted`` misplaces two-dimensional positions (chip probe,
    PR 33). Counts are whole numbers: equal to the host fill to the bit."""
    with jax.named_scope("fe.text_fill"):
        n, piece = block.shape[0], slot.shape[0]
        at = row_start[1:-1] - base
        marks = jnp.zeros(piece, jnp.int32).at[
            jnp.where(at < 0, piece, at)].add(1, mode="drop")
        row = jnp.sum(at < 0) + jnp.cumsum(marks)
        e = base + jnp.arange(piece, dtype=jnp.int32)
        row = jnp.where(e < row_start[-1], row, n)
        return block.at[row, slot.astype(jnp.int32)].set(
            count.astype(jnp.float32), mode="drop", unique_indices=True)


@functools.lru_cache(maxsize=None)
def _fill_text_entries_program():
    """``_fill_text_entries`` jitted, the block donated where the backend
    aliases buffers (each piece is then written in place)."""
    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(_fill_text_entries, donate_argnums=donate)


def _fill_text_block(length, null, row_start, *pieces, width: int):
    """A hashed column's ``[n, width]`` block from its device operands
    (``SmartTextModel._text_block_operands``): the zeroed block with its
    length and null columns, then one call of the ONE compiled
    ``_fill_text_entries`` a piece (``slot``, ``count``, ``base``)."""
    block = _fill_text_entries_block(length, null, width=width)
    for i in range(0, len(pieces), 3):
        block = _fill_text_entries_program()(block, row_start,
                                             *pieces[i:i + 3])
    return block


def _dict_encode_span(vals: np.ndarray, column: str):
    """``dict_encode`` under a ``fe.dict_encode`` span that names the
    column, its rows and the distinct values found."""
    from transmogrifai_tpu.utils.dict_encode import dict_encode
    from transmogrifai_tpu.utils.tracing import recorder
    t0 = time.time()
    codes, vocab = dict_encode(vals)
    recorder.add("fe.dict_encode", t0, time.time(), column=column,
                 rows=len(vals), distinct=len(vocab))
    return codes, vocab


def pivot_slot_fill(out: np.ndarray, off: int, cats, codes: np.ndarray,
                    vocab, null_mask: np.ndarray,
                    track_nulls: bool) -> None:
    """Columnar categorical pivot: per-UNIQUE slot assignment gathered by
    dict-encode code (categories -> own slot, unknown -> OTHER at k,
    null -> k+1 when tracked). Shared by the scalar SmartText path and the
    keyed-map pivot fills so the encode-gate semantics can't drift."""
    k = len(cats)
    cat_idx = {c: j for j, c in enumerate(cats)}
    slots = np.array([cat_idx.get(v, k) for v in vocab], dtype=np.int64)
    rows = np.nonzero(~null_mask)[0]
    out[rows, off + slots[codes[rows]]] = 1.0
    if track_nulls:
        out[null_mask, off + k + 1] = 1.0


def hashed_unique_slots(vocab, num_hash_features: int,
                        max_slots: Optional[int] = None):
    """Per distinct value, the hash slots its tokens fall into and how many
    tokens fall into each: ``(starts [uniques + 1], slots, counts)``, value
    ``u`` owning ``slots[starts[u]:starts[u + 1]]`` (distinct within a
    value): an id-like value is one slot, not a row of
    ``num_hash_features`` floats. None at the first value that occupies
    more than ``max_slots`` slots, where that is given: the caller then has
    no use for the rest."""
    starts = np.zeros(len(vocab) + 1, np.int64)
    slots: list[int] = []
    counts: list[int] = []
    for u, v in enumerate(vocab):
        per: dict[int, int] = {}
        for tok in tokenize(v):
            s = hash_token(tok, num_hash_features)
            per[s] = per.get(s, 0) + 1
        if max_slots is not None and len(per) > max_slots:
            return None
        slots.extend(per)
        counts.extend(per.values())
        starts[u + 1] = len(slots)
    return (starts, np.asarray(slots, np.int64),
            np.asarray(counts, np.float32))


def hashed_slot_fill(out: np.ndarray, off: int, vocab, codes: np.ndarray,
                     null_mask: np.ndarray, num_hash_features: int,
                     track_text_len: bool, track_nulls: bool) -> None:
    """One hashed column into its zeroed block of ``out``: token counts in
    ``num_hash_features`` slots, then the text length and the null
    indicator where tracked. Written as ONE pass in row order over the
    entries that are not zero: a row of ``out`` is tens of kilobytes, so a
    pass a kind of entry would walk every page of the matrix three times,
    and a gathered per-value table copies ``num_hash_features`` floats a
    row where an id-like value occupies one slot."""
    starts, slots, counts = hashed_unique_slots(vocab, num_hash_features)
    rows = np.nonzero(~null_mask)[0]
    c = codes[rows]
    per_row = starts[c + 1] - starts[c]
    ends = np.cumsum(per_row)
    at = np.arange(int(ends[-1]) if ends.size else 0) \
        + np.repeat(starts[c] - (ends - per_row), per_row)
    r_idx, c_idx, vals = [np.repeat(rows, per_row)], [off + slots[at]], \
        [counts[at]]
    pos = off + num_hash_features
    if track_text_len:
        vlens = np.array([len(v) for v in vocab], np.float32)
        r_idx.append(rows)
        c_idx.append(np.full(len(rows), pos, np.int64))
        vals.append(vlens[c])
        pos += 1
    if track_nulls:
        nulls = np.nonzero(null_mask)[0]
        r_idx.append(nulls)
        c_idx.append(np.full(len(nulls), pos, np.int64))
        vals.append(np.ones(len(nulls), np.float32))
    r_idx = np.concatenate(r_idx)
    order = np.argsort(r_idx, kind="stable")  # a merge of sorted runs
    out[r_idx[order], np.concatenate(c_idx)[order]] = \
        np.concatenate(vals)[order]


def _distinct_exceed(vals: np.ndarray, null_mask: np.ndarray,
                     limit: int) -> tuple[bool, int]:
    """Whether a column of strings holds more than ``limit`` distinct
    values, and how many the pass had seen when it knew: it adds
    ``_SCAN_BLOCK`` rows at a time to a set and stops once the set passes
    ``limit``; no vocabulary of the column is built."""
    seen: set = set()
    for s in range(0, len(vals), _SCAN_BLOCK):
        block = vals[s:s + _SCAN_BLOCK]
        seen.update(block[~null_mask[s:s + _SCAN_BLOCK]].tolist())
        if len(seen) > limit:
            return True, len(seen)
    return False, len(seen)


def _values_repeat(vals: np.ndarray, null_mask: np.ndarray,
                   column: str = "") -> bool:
    """Whether a hashed column's values repeat: at most half of its values
    are distinct, so that work a distinct value (a dictionary code, slots
    hashed once a value) is at most half of work a row. Decided from the
    column itself, exactly: the pass stops as soon as the distinct values
    seen pass half of the column's values (a column of free text after half
    of its rows). Under a ``fe.scan`` span."""
    from transmogrifai_tpu.utils.tracing import recorder
    t0 = time.time()
    distinct, seen = _distinct_exceed(vals, null_mask,
                                      int((~null_mask).sum()) // 2)
    recorder.add("fe.scan", t0, time.time(), column=column, rows=len(vals),
                 seen=seen, repeats=not distinct)
    return not distinct


@dataclass
class TextStats:
    """Value-count monoid with cardinality cap (reference TextStats)."""

    counts: dict = field(default_factory=dict)
    n: int = 0
    nulls: int = 0
    overflowed: bool = False
    max_cardinality: int = 100

    def add(self, value: Optional[str]) -> None:
        self.n += 1
        if value is None:
            self.nulls += 1
            return
        if self.overflowed:
            return
        self.counts[value] = self.counts.get(value, 0) + 1
        if len(self.counts) > self.max_cardinality:
            self.overflowed = True
            self.counts.clear()

    @property
    def cardinality(self) -> int:
        return (self.max_cardinality + 1 if self.overflowed
                else len(self.counts))


COMMON_FIRST_NAMES = frozenset(
    "james john robert michael william david richard joseph thomas charles "
    "christopher daniel matthew anthony mark donald steven paul andrew "
    "joshua kenneth kevin brian george timothy ronald edward jason jeffrey "
    "ryan jacob gary nicholas eric jonathan stephen larry justin scott "
    "brandon benjamin samuel gregory frank alexander raymond patrick jack "
    "mary patricia jennifer linda elizabeth barbara susan jessica sarah "
    "karen lisa nancy betty margaret sandra ashley kimberly emily donna "
    "michelle carol amanda dorothy melissa deborah stephanie rebecca sharon "
    "laura cynthia kathleen amy angela shirley anna brenda pamela emma "
    "nicole helen samantha katherine christine debra rachel carolyn janet "
    "catherine maria heather diane ruth julie olivia joyce virginia".split())


def looks_like_name(value: str) -> bool:
    toks = tokenize(value)
    return bool(toks) and any(t in COMMON_FIRST_NAMES for t in toks)


class SmartTextVectorizer(Estimator):
    """Variadic estimator over Text inputs with per-column treatment."""

    variadic = True
    in_types = (ft.Text,)
    out_type = ft.OPVector

    def __init__(self, max_cardinality: int = 100, top_k: int = 20,
                 min_support: int = 10, num_hash_features: int = 512,
                 track_nulls: bool = True, track_text_len: bool = True,
                 detect_names: bool = False, name_threshold: float = 0.5,
                 uid: Optional[str] = None):
        self.max_cardinality = max_cardinality
        self.top_k = top_k
        self.min_support = min_support
        self.num_hash_features = num_hash_features
        self.track_nulls = track_nulls
        self.track_text_len = track_text_len
        self.detect_names = detect_names
        self.name_threshold = name_threshold
        super().__init__(uid=uid)

    def fit_model(self, data) -> "SmartTextModel":
        treatments: list[dict] = []
        for name in self.input_names:
            col = data.host_col(name)
            if not self.detect_names:
                # vectorized stats (the Criteo hot path: 26 columns x 10M+
                # rows), final-state equivalent to n per-row
                # TextStats.add() calls: overflow iff the distinct values
                # exceed the cap, found by a pass that stops there (no
                # vocabulary of a column of free text is built); counts
                # over all values otherwise, by one native dict-encode
                # pass + a bincount.
                vals = np.asarray(col.values, dtype=object)
                null_mask, all_str = _scan_column(vals)
                nulls = int(null_mask.sum())
                non_null = len(vals) - nulls
                stats = TextStats(max_cardinality=self.max_cardinality)
                stats.n = len(vals)
                stats.nulls = nulls
                if non_null and not all_str:
                    # non-string objects leaked into the column: the
                    # vectorized encoder would stringify them and the
                    # fitted categories would no longer match raw values
                    # at scoring time — count the slow exact way
                    stats = TextStats(max_cardinality=self.max_cardinality)
                    for v in col.values:
                        stats.add(v)
                elif non_null and _distinct_exceed(
                        vals, null_mask, self.max_cardinality)[0]:
                    stats.overflowed = True     # TextStats's overflow
                elif non_null:
                    codes, vocab = _dict_encode_span(vals, name)
                    counts = np.bincount(codes[codes >= 0],
                                         minlength=len(vocab))
                    stats.counts = {v: int(c) for v, c in zip(vocab, counts)}
                name_hits = 0
            else:
                stats = TextStats(max_cardinality=self.max_cardinality)
                name_hits = 0
                non_null = 0
                for v in col.values:
                    stats.add(v)
                    if v is not None:
                        non_null += 1
                        if looks_like_name(v):
                            name_hits += 1
            if self.detect_names and non_null > 0 \
                    and name_hits / non_null >= self.name_threshold:
                # record WHAT was detected, not just that the column vanished
                # (reference SensitiveFeatureInformation rides into
                # ModelInsights via vector metadata)
                treatments.append({"kind": "sensitive",
                                   "prob_name": name_hits / non_null})
            elif non_null == 0:
                treatments.append({"kind": "ignore"})
            elif not stats.overflowed:
                cats = _top_k(list(stats.counts), list(stats.counts.values()),
                              self.top_k, self.min_support)
                treatments.append({"kind": "pivot", "categories": cats})
            else:
                treatments.append({"kind": "hash"})
        return SmartTextModel(
            treatments=treatments, num_hash_features=self.num_hash_features,
            track_nulls=self.track_nulls, track_text_len=self.track_text_len)


class SmartTextModel(HostTransformer):
    variadic = True
    in_types = (ft.Text,)
    out_type = ft.OPVector

    def __init__(self, treatments: Sequence[dict] = (),
                 num_hash_features: int = 512, track_nulls: bool = True,
                 track_text_len: bool = True, uid: Optional[str] = None):
        self.treatments = [dict(t) for t in treatments]
        self.num_hash_features = num_hash_features
        self.track_nulls = track_nulls
        self.track_text_len = track_text_len
        super().__init__(uid=uid)

    # -- layout --------------------------------------------------------------
    def _width(self, t: dict) -> int:
        kind = t["kind"]
        if kind in ("sensitive",):
            return 0
        if kind == "ignore":
            return 1 if self.track_nulls else 0
        if kind == "pivot":
            return len(t["categories"]) + 1 + (1 if self.track_nulls else 0)
        w = self.num_hash_features
        if self.track_text_len:
            w += 1
        if self.track_nulls:
            w += 1
        return w

    def _fill_row(self, out: np.ndarray, offset: int, t: dict,
                  v: Optional[str]) -> None:
        kind = t["kind"]
        if kind == "sensitive":
            return
        if kind == "ignore":
            if self.track_nulls:
                out[offset] = 1.0 if v is None else 0.0
            return
        if kind == "pivot":
            cats = t["categories"]
            k = len(cats)
            if v is None:
                if self.track_nulls:
                    out[offset + k + 1] = 1.0
            elif v in cats:
                out[offset + cats.index(v)] = 1.0
            else:
                out[offset + k] = 1.0
            return
        # hash
        base = offset
        if v is not None:
            for tok in tokenize(v):
                out[base + hash_token(tok, self.num_hash_features)] += 1.0
        pos = base + self.num_hash_features
        if self.track_text_len:
            out[pos] = 0.0 if v is None else float(len(v))
            pos += 1
        if self.track_nulls:
            out[pos] = 1.0 if v is None else 0.0

    def transform_row(self, *values):
        total = sum(self._width(t) for t in self.treatments)
        out = np.zeros(total, dtype=np.float32)
        offset = 0
        for t, v in zip(self.treatments, values):
            self._fill_row(out, offset, t, v)
            offset += self._width(t)
        return out

    def host_apply(self, *cols: fr.HostColumn) -> fr.HostColumn:
        n = len(cols[0])
        total = sum(self._width(t) for t in self.treatments)
        out = np.zeros((n, total), dtype=np.float32)
        offset = 0
        for t, col, name in zip(self.treatments, cols, self.input_names):
            self._fill_column(out, offset, t, col.values, n, name)
            offset += self._width(t)
        return fr.HostColumn(ft.OPVector, out, meta=self._meta())

    def _fill_column(self, out: np.ndarray, offset: int, t: dict,
                     values, n: int, column: str = "") -> None:
        """Columnar treatment fill, exact per-row (``_fill_row``)
        semantics with no Python a row: a pivot and a hashed column whose
        values repeat take one native dict-encode pass and per-UNIQUE work
        (category slot / hashed token counts) gathered back by code; a
        hashed column whose values do not repeat takes one native
        tokenize-and-hash pass (``_tokenize_column``)."""
        kind = t["kind"]
        if kind == "sensitive":
            return
        vals = np.asarray(values, dtype=object)
        null_mask, all_str = _scan_column(vals)
        if kind == "ignore":
            if self.track_nulls:
                out[:, offset] = null_mask.astype(np.float32)
            return
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        if not all_str:
            # non-string objects: the encoder's vocab is stringified and
            # would mis-route category matching — exact per-row semantics
            if kind == "hash":
                sweep_counters.count_run(fe_hash_fallbacks=1)
            for r in range(n):
                self._fill_row(out[r], offset, t, values[r])
            return
        H = self.num_hash_features
        if kind == "hash" and not _values_repeat(vals, null_mask, column):
            e = self._tokenize_column(vals, null_mask, column)
            rows = np.repeat(np.arange(n), np.diff(e.row_start))
            out[rows, offset + e.slot] = e.count
            pos = offset + H
            if self.track_text_len:
                out[:, pos] = e.length
                pos += 1
            if self.track_nulls:
                out[:, pos] = null_mask
            return
        codes, vocab = _dict_encode_span(vals, column)
        sweep_counters.count_run(fe_distinct_values=len(vocab))
        if kind == "pivot":
            with span("fe.pivot", column=column, rows=n,
                      distinct=len(vocab)):
                pivot_slot_fill(out, offset, t["categories"], codes, vocab,
                                null_mask, self.track_nulls)
            return
        with span("fe.hash", column=column, rows=n, distinct=len(vocab),
                  perRowFallback=False):
            hashed_slot_fill(out, offset, vocab, codes, null_mask, H,
                             self.track_text_len, self.track_nulls)

    def _tokenize_column(self, vals: np.ndarray, null_mask: np.ndarray,
                         column: str):
        """A hashed column's :class:`ColumnEntries` in one native pass,
        under a ``fe.tokenize`` span; the train's totals of tokens,
        entries and rows that took the Python tokenizer are counted."""
        from transmogrifai_tpu.ops.vectorizers.hashing import (
            text_column_entries,
        )
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import recorder
        t0 = time.time()
        e = text_column_entries(vals, null_mask, self.num_hash_features)
        recorder.add("fe.tokenize", t0, time.time(), column=column,
                     rows=len(vals), tokens=e.tokens, entries=len(e.slot),
                     pythonRows=e.python_rows)
        sweep_counters.count_run(fe_text_tokens=e.tokens,
                                 fe_text_entries=len(e.slot),
                                 fe_text_python_rows=e.python_rows)
        return e

    def _value_table(self, t: dict, vals: np.ndarray, null_mask: np.ndarray,
                     column: str):
        """A pivoted column's, or a repeating hashed column's, entries per
        row as the per-value device fill reads them: ``(idx, val)``, each
        ``[n, groups]``, ``idx`` the position inside the column's block or
        -1; gathered by dictionary code from per-value slots. None for a
        hashed column one of whose values occupies more than
        ``_DEVICE_FILL_MAX_SLOTS`` slots."""
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        n = len(vals)
        one = np.ones(n, np.float32)
        codes, vocab = _dict_encode_span(vals, column)
        sweep_counters.count_run(fe_distinct_values=len(vocab))
        at = np.where(null_mask, 0, codes)  # a safe index; nulls are masked
        if t["kind"] == "pivot":
            with span("fe.pivot", column=column, rows=n,
                      distinct=len(vocab)):
                cats = t["categories"]
                k = len(cats)
                cat_idx = {c: j for j, c in enumerate(cats)}
                slots = np.array([cat_idx.get(v, k) for v in vocab] or [k],
                                 dtype=np.int32)
                null_at = k + 1 if self.track_nulls else -1
                idx = np.where(null_mask, null_at, slots[at])
            return idx.astype(np.int32)[:, None], one[:, None]
        H = self.num_hash_features
        with span("fe.hash", column=column, rows=n, distinct=len(vocab),
                  perRowFallback=False):
            hashed = hashed_unique_slots(vocab, H, _DEVICE_FILL_MAX_SLOTS)
            if hashed is None:
                return None
            starts, slots, counts = hashed
            per_value = np.diff(starts)
            width = int(per_value.max()) if per_value.size else 0
            # per-value tables [distinct, width], gathered by code
            tab_idx = np.full((max(len(vocab), 1), width), -1, np.int32)
            tab_val = np.zeros(tab_idx.shape, np.float32)
            u = np.repeat(np.arange(len(vocab)), per_value)
            j = np.arange(len(slots)) - np.repeat(starts[:-1], per_value)
            tab_idx[u, j] = slots
            tab_val[u, j] = counts
            idx = [np.where(null_mask[:, None], -1, tab_idx[at])]
            val = [tab_val[at]]
            pos = H
            if self.track_text_len:
                vlens = np.array([len(v) for v in vocab] or [0], np.float32)
                idx.append(np.where(null_mask, -1, pos)[:, None])
                val.append(vlens[at][:, None])
                pos += 1
            if self.track_nulls:
                idx.append(np.where(null_mask, pos, -1)[:, None])
                val.append(one[:, None])
        return (np.concatenate(idx, axis=1).astype(np.int32),
                np.concatenate(val, axis=1))

    def _column_entries(self, t: dict, values, n: int, column: str):
        """What the device fill reads of one column: ``(idx, val)`` tables
        for the per-value fill (``_value_table``; an ignored column's null
        flag; nothing for a sensitive one), a :class:`ColumnEntries` for a
        hashed column whose values do not repeat or occupy more slots than
        a table carries, None where only the host's per-row fill is exact
        (objects that are not strings)."""
        kind = t["kind"]
        none = (np.zeros((n, 0), np.int32), np.zeros((n, 0), np.float32))
        if kind == "sensitive":
            return none
        vals = np.asarray(values, dtype=object)
        null_mask, all_str = _scan_column(vals)
        if kind == "ignore":
            if not self.track_nulls:
                return none
            return (np.where(null_mask, 0, -1).astype(np.int32)[:, None],
                    np.ones((n, 1), np.float32))
        if not all_str:
            return None
        table = None
        if kind == "pivot" or _values_repeat(vals, null_mask, column):
            table = self._value_table(t, vals, null_mask, column)
        if table is None:
            return self._tokenize_column(vals, null_mask, column)
        return table

    def _text_block_operands(self, e, n: int) -> list:
        """Host operands of one column's text fill in the smallest encoding
        that is exact: ``[length [n, 0 or 1] int32, null [n, 0 or 1] bool,
        row_start [n + 1] int32]``, then ``slot`` (16 bits up to 65,536
        slots), ``count`` (8 bits up to 255) and ``base`` a piece of
        ``entry_piece(n)`` entries; the last piece is filled up with zeros,
        which the program drops."""
        small = e.count.max(initial=0) <= np.iinfo(np.uint8).max
        count = e.count.astype(np.uint8 if small else np.int32)
        piece = entry_piece(n)
        length = e.length.astype(np.int32)[:, None]
        null = e.null[:, None]
        ops = [length if self.track_text_len else length[:, :0],
               null if self.track_nulls else null[:, :0],
               e.row_start.astype(np.int32)]
        for base in range(0, len(count), piece):
            for a in (e.slot[base:base + piece], count[base:base + piece]):
                ops.append(np.concatenate(
                    [a, np.zeros(piece - len(a), a.dtype)]))
            ops.append(np.int32(base))
        return ops

    def device_output_column(self, data):
        """The output vector filled ON the device from each row's entries
        that are not zero: tens of numbers a row go up, not a dense row of
        kilobytes that the host first had to write. Pivots and hashed
        columns whose values repeat go up as per-row tables of a few
        (position, value) pairs (``_dense_from_entries``); a hashed column
        whose values do not repeat goes up as its entries in row order, a
        piece of ``entry_piece(n)`` at a time (``_fill_text_block``).
        Equal to ``host_apply`` to the bit (counts and indicators). None
        under a mesh and where a column needs the host fill."""
        from transmogrifai_tpu.parallel import mesh as pmesh
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        if pmesh.current_mesh() is not None:
            return None
        n = data.n_rows
        # one (program, host operands) a block of the vector: consecutive
        # per-value columns share a block, a column of row-ordered entries
        # is a block of its own
        blocks: list = []
        tables: list = []           # the per-value columns not yet in a block

        def close_tables():
            if tables:
                layout, idx, val = zip(*tables)
                blocks.append((functools.partial(_dense_from_entries,
                                                 layout=layout),
                               [np.concatenate(idx, axis=1),
                                np.concatenate(val, axis=1)]))
                tables.clear()

        for t, name in zip(self.treatments, self.runtime_input_names()):
            entries = self._column_entries(
                t, data.host_col(name).values, n, name)
            if entries is None:
                return None
            if isinstance(entries, tuple):
                tables.append(((self._width(t), entries[0].shape[1]),
                               *entries))
                continue
            if len(entries.slot) >= 2 ** 31:     # past int32 positions
                return None
            close_tables()
            blocks.append((functools.partial(_fill_text_block,
                                             width=self._width(t)),
                           self._text_block_operands(entries, n)))
        close_tables()
        host = [a for _, ops in blocks for a in ops]
        nbytes = int(sum(a.nbytes for a in host))
        with span("fe.upload", column=self.get_output().name, bytes=nbytes):
            up = iter(jax.device_put(host))
        sweep_counters.count_run(fe_upload_bytes=nbytes)
        out = [fill(*(next(up) for _ in ops)) for fill, ops in blocks] \
            or [jnp.zeros((n, 0), jnp.float32)]
        return fr.VectorColumn(
            out[0] if len(out) == 1 else jnp.concatenate(out, axis=1),
            self._meta())

    def _meta(self) -> VectorMetadata:
        cols: list[VectorColumnMetadata] = []
        for t, f in zip(self.treatments, self.input_features):
            parent = parent_of(f)
            kind = t["kind"]
            if kind == "sensitive":
                continue
            if kind == "ignore":
                if self.track_nulls:
                    cols.append(VectorColumnMetadata(
                        *parent, grouping=f.name,
                        indicator_value=NULL_INDICATOR))
                continue
            if kind == "pivot":
                for c in t["categories"]:
                    cols.append(VectorColumnMetadata(
                        *parent, grouping=f.name, indicator_value=c))
                cols.append(VectorColumnMetadata(
                    *parent, grouping=f.name, indicator_value=OTHER))
                if self.track_nulls:
                    cols.append(VectorColumnMetadata(
                        *parent, grouping=f.name,
                        indicator_value=NULL_INDICATOR))
                continue
            for j in range(self.num_hash_features):
                cols.append(VectorColumnMetadata(
                    *parent, grouping=f.name, descriptor_value=f"hash_{j}"))
            if self.track_text_len:
                cols.append(VectorColumnMetadata(
                    *parent, grouping=f.name, descriptor_value="textLen"))
            if self.track_nulls:
                cols.append(VectorColumnMetadata(
                    *parent, grouping=f.name, indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.get_output().name, tuple(cols)).reindexed(0)

    def sensitive_features(self) -> list[str]:
        return [f.name for t, f in zip(self.treatments, self.input_features)
                if t["kind"] == "sensitive"]

    def sensitive_info(self) -> dict[str, dict]:
        """SensitiveFeatureInformation analog: name -> detection record for
        every input column the fit dropped as sensitive."""
        return {f.name: {"detected": True,
                         "probName": t.get("prob_name"),
                         "action": "removedFromVector"}
                for t, f in zip(self.treatments, self.input_features)
                if t["kind"] == "sensitive"}

    def fitted_state(self):
        return {"treatments": self.treatments}

    def set_fitted_state(self, state):
        self.treatments = [dict(t) for t in state["treatments"]]
