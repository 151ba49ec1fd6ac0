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

#: hash treatments fall back to the per-row loop when the per-unique
#: table (uniques x num_hash_features) would exceed this many floats
#: (true free text — no repetition to exploit)
_UNIQUE_TABLE_CAP = 64_000_000


def _over_table_cap(n_unique: int, num_hash_features: int) -> bool:
    """Whether a per-unique token-count table would pass the memory cap."""
    return n_unique * num_hash_features > _UNIQUE_TABLE_CAP


#: the device fill (``SmartTextModel.device_output_column``) carries a
#: value's occupied hash slots as columns of a per-row table; a value past
#: this many (free text, not an id) sends the stage to the host fill
_DEVICE_FILL_MAX_SLOTS = 8


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


def hashed_unique_table(vocab, num_hash_features: int):
    """[uniques, H] token-count table for a vocab, or None when the table
    would blow the memory cap (caller falls back to the per-row loop)."""
    if _over_table_cap(len(vocab), num_hash_features):
        return None
    uvecs = np.zeros((len(vocab), num_hash_features), np.float32)
    for u, v in enumerate(vocab):
        for tok in tokenize(v):
            uvecs[u, hash_token(tok, num_hash_features)] += 1.0
    return uvecs


def hashed_unique_slots(vocab, num_hash_features: int):
    """Per distinct value, the hash slots its tokens fall into and how many
    tokens fall into each: ``(starts [uniques + 1], slots, counts)``, value
    ``u`` owning ``slots[starts[u]:starts[u + 1]]`` (distinct within a
    value). The sparse form of :func:`hashed_unique_table`: an id-like
    value is one slot, not a row of ``num_hash_features`` floats."""
    starts = np.zeros(len(vocab) + 1, np.int64)
    slots: list[int] = []
    counts: list[int] = []
    for u, v in enumerate(vocab):
        per: dict[int, int] = {}
        for tok in tokenize(v):
            s = hash_token(tok, num_hash_features)
            per[s] = per.get(s, 0) + 1
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
                # rows): one native dict-encode pass + a bincount replaces
                # n per-row TextStats.add() calls. Final-state equivalent:
                # overflow iff total uniques exceed the cap, counts over
                # all values otherwise.
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
                elif non_null:
                    codes, vocab = _dict_encode_span(vals, name)
                    if len(vocab) > self.max_cardinality:
                        stats.overflowed = True
                    else:
                        counts = np.bincount(codes[codes >= 0],
                                             minlength=len(vocab))
                        stats.counts = {v: int(c)
                                        for v, c in zip(vocab, counts)}
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
        """Columnar treatment fill — exact per-row (_fill_row) semantics,
        vectorized for the Criteo-scale categorical path: one native
        dict-encode pass per column, then per-UNIQUE work (category slot /
        hashed token counts) gathered back by code. Python cost is
        O(uniques), not O(rows)."""
        kind = t["kind"]
        if kind == "sensitive":
            return
        vals = np.asarray(values, dtype=object)
        null_mask, all_str = _scan_column(vals)
        if kind == "ignore":
            if self.track_nulls:
                out[:, offset] = null_mask.astype(np.float32)
            return
        if not all_str:
            # non-string objects: the encoder's vocab is stringified and
            # would mis-route category matching — exact per-row semantics
            for r in range(n):
                self._fill_row(out[r], offset, t, values[r])
            return
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        codes, vocab = _dict_encode_span(vals, column)
        sweep_counters.count_run(fe_distinct_values=len(vocab))
        if kind == "pivot":
            with span("fe.pivot", column=column, rows=n,
                      distinct=len(vocab)):
                pivot_slot_fill(out, offset, t["categories"], codes, vocab,
                                null_mask, self.track_nulls)
            return
        # hash
        H = self.num_hash_features
        over_cap = _over_table_cap(len(vocab), H)
        with span("fe.hash", column=column, rows=n, distinct=len(vocab),
                  perRowFallback=over_cap):
            if over_cap:  # exact per-row
                sweep_counters.count_run(fe_hash_fallbacks=1)
                for r in range(n):
                    self._fill_row(out[r], offset, t, values[r])
                return
            hashed_slot_fill(out, offset, vocab, codes, null_mask, H,
                             self.track_text_len, self.track_nulls)

    def _column_entries(self, t: dict, values, n: int, column: str):
        """A column's entries that are not zero, per row: ``(idx, val)``,
        each ``[n, groups]``, ``idx`` the position inside the column's
        block or -1. ``_fill_column``'s semantics in the form the device
        fill reads; None where only the host fill is exact or cheap
        (objects that are not strings, a vocabulary past the table cap,
        values of more than ``_DEVICE_FILL_MAX_SLOTS`` slots)."""
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        kind = t["kind"]
        none = (np.zeros((n, 0), np.int32), np.zeros((n, 0), np.float32))
        if kind == "sensitive":
            return none
        vals = np.asarray(values, dtype=object)
        null_mask, all_str = _scan_column(vals)
        one = np.ones(n, np.float32)
        if kind == "ignore":
            if not self.track_nulls:
                return none
            return (np.where(null_mask, 0, -1).astype(np.int32)[:, None],
                    one[:, None])
        if not all_str:
            return None
        codes, vocab = _dict_encode_span(vals, column)
        at = np.where(null_mask, 0, codes)  # a safe index; nulls are masked
        if kind == "pivot":
            with span("fe.pivot", column=column, rows=n,
                      distinct=len(vocab)):
                cats = t["categories"]
                k = len(cats)
                cat_idx = {c: j for j, c in enumerate(cats)}
                slots = np.array([cat_idx.get(v, k) for v in vocab] or [k],
                                 dtype=np.int32)
                null_at = k + 1 if self.track_nulls else -1
                idx = np.where(null_mask, null_at, slots[at])
            sweep_counters.count_run(fe_distinct_values=len(vocab))
            return idx.astype(np.int32)[:, None], one[:, None]
        H = self.num_hash_features
        if _over_table_cap(len(vocab), H):
            return None  # the host fill's per-row loop, counted there
        with span("fe.hash", column=column, rows=n, distinct=len(vocab),
                  perRowFallback=False):
            starts, slots, counts = hashed_unique_slots(vocab, H)
            per_value = np.diff(starts)
            width = int(per_value.max()) if per_value.size else 0
            if width > _DEVICE_FILL_MAX_SLOTS:
                return None
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
        sweep_counters.count_run(fe_distinct_values=len(vocab))
        return (np.concatenate(idx, axis=1).astype(np.int32),
                np.concatenate(val, axis=1))

    def device_output_column(self, data):
        """The output vector filled ON the device from each row's entries
        that are not zero (a slot a hashed id, a slot a pivoted value, the
        text length, the null indicator): tens of numbers a row go up, not
        a dense row of tens of kilobytes that the host first had to write.
        Equal to ``host_apply`` to the bit (counts and indicators). None
        under a mesh and where a column needs the host fill."""
        from transmogrifai_tpu.parallel import mesh as pmesh
        from transmogrifai_tpu.utils.profiling import sweep_counters
        from transmogrifai_tpu.utils.tracing import span
        if pmesh.current_mesh() is not None:
            return None
        n = data.n_rows
        layout, idx, val = [], [], []
        for t, name in zip(self.treatments, self.runtime_input_names()):
            entries = self._column_entries(
                t, data.host_col(name).values, n, name)
            if entries is None:
                return None
            layout.append((self._width(t), entries[0].shape[1]))
            idx.append(entries[0])
            val.append(entries[1])
        idx = np.concatenate(idx, axis=1) if idx else np.zeros((n, 0),
                                                               np.int32)
        val = np.concatenate(val, axis=1) if val else np.zeros((n, 0),
                                                               np.float32)
        nbytes = int(idx.nbytes + val.nbytes)
        with span("fe.upload", column=self.get_output().name, bytes=nbytes):
            didx, dval = jax.device_put(idx), jax.device_put(val)
        sweep_counters.count_run(fe_upload_bytes=nbytes)
        return fr.VectorColumn(
            _dense_from_entries(didx, dval, layout=tuple(layout)),
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
