"""Map vectorizers: key-expanded vectorization of map features.

Parity: reference ``core/.../stages/impl/feature/{OPMapVectorizer,
TextMapPivotVectorizer, MultiPickListMapVectorizer, DateMapToUnitCircleVectorizer,
GeolocationMapVectorizer}.scala`` and ``SmartTextMapVectorizer.scala`` — maps
expand to one column block per key seen at fit time (sorted key order),
then each key's block follows its scalar vectorizer's semantics (mean-fill
numeric, topK pivot, multi-hot, sin/cos, midpoint-fill geo), with
``grouping = key`` provenance metadata throughout (whitelist/blacklist key
filtering like the reference's map params).
"""

from __future__ import annotations

import numpy as np

from typing import Optional, Sequence

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.ops.smart_text import TextStats
from transmogrifai_tpu.ops.vectorizers.dates import TIME_PERIODS
from transmogrifai_tpu.ops.vectorizers.hashing import hash_token, tokenize
from transmogrifai_tpu.ops.vectorizers.onehot import _top_k
from transmogrifai_tpu.stages.base import Estimator, HostTransformer
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.vector_metadata import (
    NULL_INDICATOR, OTHER, VectorColumnMetadata, VectorMetadata, parent_of,
)

__all__ = [
    "FilterMapKeys", "Base64MapMimeDetector",
    "RealMapVectorizer", "IntegralMapVectorizer", "BinaryMapVectorizer",
    "TextMapPivotVectorizer", "MultiPickListMapVectorizer",
    "DateMapToUnitCircleVectorizer", "GeolocationMapVectorizer",
    "SmartTextMapVectorizer", "TextMapLenEstimator", "TextMapNullEstimator",
]


class _MapVectorizerBase(Estimator):
    """Shared fit plumbing: collect keys (+ per-key state) per input."""

    variadic = True
    out_type = ft.OPVector

    def __init__(self, allow_keys: Sequence[str] = (),
                 block_keys: Sequence[str] = (),
                 block_keys_by_feature: Optional[dict] = None,
                 track_nulls: bool = True, uid: Optional[str] = None,
                 **extra):
        self.allow_keys = tuple(allow_keys)
        self.block_keys = tuple(block_keys)
        #: per-feature key exclusions (reference RawFeatureFilter's per-key
        #: map blocklist, applied by OpWorkflow.setBlocklist — here the
        #: workflow rewires fitted map vectorizers with this dict)
        self.block_keys_by_feature = {
            str(n): tuple(ks)
            for n, ks in (block_keys_by_feature or {}).items()}
        #: WORKFLOW-applied per-key exclusions (RawFeatureFilter results,
        #: set by Workflow._apply_map_key_blocklist) — kept separate from
        #: the user-owned ``block_keys_by_feature`` so each train() can
        #: replace its own exclusions without ever touching user config
        self.wf_block_keys_by_feature: dict = {}
        self.track_nulls = track_nulls
        for k, v in extra.items():
            setattr(self, k, v)
        super().__init__(uid=uid)

    def _keep_key(self, k: str, feature: Optional[str] = None) -> bool:
        if self.allow_keys and k not in self.allow_keys:
            return False
        if feature is not None \
                and (k in self.block_keys_by_feature.get(feature, ())
                     or k in self.wf_block_keys_by_feature.get(feature, ())):
            return False
        return k not in self.block_keys

    def _collect(self, col: fr.HostColumn, feature: Optional[str] = None):
        """-> {key: [values...]} (missing key -> absent)."""
        per_key: dict[str, list] = {}
        for m in col.values:
            for k, v in (m or {}).items():
                if self._keep_key(k, feature):
                    per_key.setdefault(k, []).append(v)
        return per_key


class _KeyedModelBase(HostTransformer):
    """Shared transform plumbing: iterate (input, key) blocks."""

    variadic = True
    out_type = ft.OPVector

    def __init__(self, keys: Sequence[Sequence[str]] = (),
                 track_nulls: bool = True, uid: Optional[str] = None,
                 **extra):
        self.keys = [list(k) for k in keys]
        self.track_nulls = track_nulls
        for k, v in extra.items():
            setattr(self, k, v)
        super().__init__(uid=uid)

    # subclass: width per key block, fill one key block, metadata per key
    def key_width(self, i: int, key: str) -> int:
        raise NotImplementedError

    def fill_key(self, out: np.ndarray, off: int, i: int, key: str, value):
        raise NotImplementedError

    def key_meta(self, i: int, key: str, parent) -> list:
        raise NotImplementedError

    def _total_width(self) -> int:
        return sum(self.key_width(i, k)
                   for i, ks in enumerate(self.keys) for k in ks)

    def transform_row(self, *values):
        out = np.zeros(self._total_width(), dtype=np.float32)
        off = 0
        for i, ks in enumerate(self.keys):
            m = values[i] or {}
            for k in ks:
                self.fill_key(out, off, i, k, m.get(k))
                off += self.key_width(i, k)
        return out

    def fill_key_column(self, out: np.ndarray, off: int, i: int, key: str,
                        values: list) -> None:
        """Columnar fill for one (feature, key) block over ALL rows.

        Default: the per-row ``fill_key`` loop. Hot subclasses (numeric,
        pivot) override with vectorized fills — wide keyed maps are the
        reference's OPMapVectorizer scale problem, and per-(row, key)
        Python method dispatch dominates otherwise."""
        for r, v in enumerate(values):
            self.fill_key(out[r], off, i, key, v)

    def host_apply(self, *cols: fr.HostColumn) -> fr.HostColumn:
        n = len(cols[0])
        out = np.zeros((n, self._total_width()), dtype=np.float32)
        off = 0
        for i, ks in enumerate(self.keys):
            vals = cols[i].values
            for k in ks:
                vk = [m.get(k) if m else None for m in vals]
                self.fill_key_column(out, off, i, k, vk)
                off += self.key_width(i, k)
        return fr.HostColumn(ft.OPVector, out, meta=self._meta())

    def _meta(self) -> VectorMetadata:
        cols = []
        for i, ks in enumerate(self.keys):
            f = self.input_features[i]
            parent = parent_of(f)
            for k in ks:
                cols.extend(self.key_meta(i, k, parent))
        return VectorMetadata(self.get_output().name, tuple(cols)).reindexed(0)

    def fitted_state(self):
        return {"keys": self.keys, **self._extra_state()}

    def _extra_state(self):
        return {}

    def set_fitted_state(self, state):
        self.keys = [list(k) for k in state["keys"]]
        for k, v in state.items():
            if k != "keys":
                setattr(self, k, v)


# ---------------------------------------------------------------------------
# numeric maps (Real/Currency/Percent/Integral/Binary)
# ---------------------------------------------------------------------------

class _NumericMapModel(_KeyedModelBase):
    in_types = (ft.OPMap,)

    def key_width(self, i, key):
        return 2 if self.track_nulls else 1

    def fill_key(self, out, off, i, key, value):
        fill = self.fills[i].get(key, 0.0)
        missing = value is None
        out[off] = fill if missing else float(value)
        if self.track_nulls:
            out[off + 1] = 1.0 if missing else 0.0

    def fill_key_column(self, out, off, i, key, values):
        fill = float(self.fills[i].get(key, 0.0))
        n = len(values)
        out[:, off] = np.fromiter(
            (fill if v is None else float(v) for v in values),
            np.float32, count=n)
        if self.track_nulls:
            out[:, off + 1] = np.fromiter(
                (1.0 if v is None else 0.0 for v in values),
                np.float32, count=n)

    def key_meta(self, i, key, parent):
        cols = [VectorColumnMetadata(*parent, grouping=key)]
        if self.track_nulls:
            cols.append(VectorColumnMetadata(
                *parent, grouping=key, indicator_value=NULL_INDICATOR))
        return cols

    def _extra_state(self):
        return {"fills": self.fills}


class RealMapVectorizer(_MapVectorizerBase):
    """RealMap/CurrencyMap/PercentMap: per-key mean fill + null tracking."""

    in_types = (ft.RealMap,)

    def fit_model(self, data):
        keys, fills = [], []
        for name in self.input_names:
            per_key = self._collect(data.host_col(name), name)
            ks = sorted(per_key)
            keys.append(ks)
            fills.append({k: float(np.mean([float(v) for v in per_key[k]]))
                          for k in ks})
        return _NumericMapModel(keys=keys, track_nulls=self.track_nulls,
                                fills=fills)


class IntegralMapVectorizer(_MapVectorizerBase):
    """IntegralMap: per-key mode fill."""

    in_types = (ft.IntegralMap,)

    def fit_model(self, data):
        keys, fills = [], []
        for name in self.input_names:
            per_key = self._collect(data.host_col(name), name)
            ks = sorted(per_key)
            keys.append(ks)
            f = {}
            for k in ks:
                vals, cnts = np.unique([int(v) for v in per_key[k]],
                                       return_counts=True)
                f[k] = float(vals[np.argmax(cnts)])
            fills.append(f)
        return _NumericMapModel(keys=keys, track_nulls=self.track_nulls,
                                fills=fills)


class BinaryMapVectorizer(_MapVectorizerBase):
    """BinaryMap: false-fill + null tracking."""

    in_types = (ft.BinaryMap,)

    def fit_model(self, data):
        keys = [sorted(self._collect(data.host_col(n), n))
                for n in self.input_names]
        fills = [{k: 0.0 for k in ks} for ks in keys]
        return _NumericMapModel(keys=keys, track_nulls=self.track_nulls,
                                fills=fills)


# ---------------------------------------------------------------------------
# categorical maps
# ---------------------------------------------------------------------------

class _PivotMapModel(_KeyedModelBase):
    in_types = (ft.TextMap,)

    def key_width(self, i, key):
        k = len(self.categories[i][key])
        return k + 1 + (1 if self.track_nulls else 0)

    def fill_key(self, out, off, i, key, value):
        cats = self.categories[i][key]
        k = len(cats)
        if value is None:
            if self.track_nulls:
                out[off + k + 1] = 1.0
        elif value in cats:
            out[off + cats.index(value)] = 1.0
        else:
            out[off + k] = 1.0

    def fill_key_column(self, out, off, i, key, values):
        from transmogrifai_tpu.ops.smart_text import pivot_slot_fill
        from transmogrifai_tpu.utils.dict_encode import (
            dict_encode, scan_column,
        )
        vals = np.asarray(values, dtype=object)
        null_mask, all_str = scan_column(vals)
        if not all_str:  # non-string values: exact per-row matching
            for r, v in enumerate(values):
                self.fill_key(out[r], off, i, key, v)
            return
        codes, vocab = dict_encode(vals)
        pivot_slot_fill(out, off, self.categories[i][key], codes, vocab,
                        null_mask, self.track_nulls)

    def key_meta(self, i, key, parent):
        cols = [VectorColumnMetadata(*parent, grouping=key, indicator_value=c)
                for c in self.categories[i][key]]
        cols.append(VectorColumnMetadata(*parent, grouping=key,
                                         indicator_value=OTHER))
        if self.track_nulls:
            cols.append(VectorColumnMetadata(
                *parent, grouping=key, indicator_value=NULL_INDICATOR))
        return cols

    def _extra_state(self):
        return {"categories": self.categories}


class TextMapPivotVectorizer(_MapVectorizerBase):
    """TextMap-family: topK pivot per key."""

    in_types = (ft.TextMap,)

    def __init__(self, top_k: int = 20, min_support: int = 10, **kw):
        super().__init__(top_k=top_k, min_support=min_support, **kw)

    def fit_model(self, data):
        keys, categories = [], []
        for name in self.input_names:
            per_key = self._collect(data.host_col(name), name)
            ks = sorted(per_key)
            keys.append(ks)
            cat = {}
            for k in ks:
                counts: dict[str, int] = {}
                for v in per_key[k]:
                    counts[v] = counts.get(v, 0) + 1
                cat[k] = _top_k(list(counts), list(counts.values()),
                                self.top_k, self.min_support)
            categories.append(cat)
        return _PivotMapModel(keys=keys, track_nulls=self.track_nulls,
                              categories=categories)


class _MultiPickMapModel(_PivotMapModel):
    in_types = (ft.MultiPickListMap,)

    def fill_key_column(self, out, off, i, key, values):
        # values are SETS/LISTS of picks, not scalars: the inherited pivot
        # fast path would treat a string value as one category (and ''
        # as a category instead of empty) — keep the exact per-row fill
        for r, v in enumerate(values):
            self.fill_key(out[r], off, i, key, v)

    def fill_key(self, out, off, i, key, value):
        cats = self.categories[i][key]
        k = len(cats)
        if not value:
            if self.track_nulls:
                out[off + k + 1] = 1.0
            return
        for v in value:
            if v in cats:
                out[off + cats.index(v)] = 1.0
            else:
                out[off + k] = 1.0


class MultiPickListMapVectorizer(_MapVectorizerBase):
    in_types = (ft.MultiPickListMap,)

    def __init__(self, top_k: int = 20, min_support: int = 10, **kw):
        super().__init__(top_k=top_k, min_support=min_support, **kw)

    def fit_model(self, data):
        keys, categories = [], []
        for name in self.input_names:
            per_key = self._collect(data.host_col(name), name)
            ks = sorted(per_key)
            keys.append(ks)
            cat = {}
            for k in ks:
                counts: dict[str, int] = {}
                for s in per_key[k]:
                    for v in (s or ()):
                        counts[v] = counts.get(v, 0) + 1
                cat[k] = _top_k(list(counts), list(counts.values()),
                                self.top_k, self.min_support)
            categories.append(cat)
        return _MultiPickMapModel(keys=keys, track_nulls=self.track_nulls,
                                  categories=categories)


# ---------------------------------------------------------------------------
# date / geolocation maps
# ---------------------------------------------------------------------------

class _DateMapModel(_KeyedModelBase):
    in_types = (ft.DateMap,)

    def key_width(self, i, key):
        return 2 + (1 if self.track_nulls else 0)

    def fill_key(self, out, off, i, key, value):
        if value is None:
            if self.track_nulls:
                out[off + 2] = 1.0
            return
        modulus, offset = TIME_PERIODS[self.time_period]
        theta = ((float(value) + offset) % modulus) / modulus * 2 * np.pi
        out[off] = np.sin(theta)
        out[off + 1] = np.cos(theta)

    def key_meta(self, i, key, parent):
        cols = [VectorColumnMetadata(*parent, grouping=key,
                                     descriptor_value=f"sin_{self.time_period}"),
                VectorColumnMetadata(*parent, grouping=key,
                                     descriptor_value=f"cos_{self.time_period}")]
        if self.track_nulls:
            cols.append(VectorColumnMetadata(
                *parent, grouping=key, indicator_value=NULL_INDICATOR))
        return cols

    def _extra_state(self):
        return {"time_period": self.time_period}


class DateMapToUnitCircleVectorizer(_MapVectorizerBase):
    in_types = (ft.DateMap,)

    def __init__(self, time_period: str = "HourOfDay", **kw):
        if time_period not in TIME_PERIODS:
            raise ValueError(f"Unknown time period {time_period!r}")
        super().__init__(time_period=time_period, **kw)

    def fit_model(self, data):
        keys = [sorted(self._collect(data.host_col(n), n))
                for n in self.input_names]
        return _DateMapModel(keys=keys, track_nulls=self.track_nulls,
                             time_period=self.time_period)


class _GeoMapModel(_KeyedModelBase):
    in_types = (ft.GeolocationMap,)

    def key_width(self, i, key):
        return 3 + (1 if self.track_nulls else 0)

    def fill_key(self, out, off, i, key, value):
        if not value:
            out[off:off + 3] = self.fills[i].get(key, [0.0, 0.0, 0.0])
            if self.track_nulls:
                out[off + 3] = 1.0
        else:
            out[off:off + 3] = [float(x) for x in value]

    def key_meta(self, i, key, parent):
        cols = [VectorColumnMetadata(*parent, grouping=key, descriptor_value=p)
                for p in ("lat", "lon", "accuracy")]
        if self.track_nulls:
            cols.append(VectorColumnMetadata(
                *parent, grouping=key, indicator_value=NULL_INDICATOR))
        return cols

    def _extra_state(self):
        return {"fills": self.fills}


class GeolocationMapVectorizer(_MapVectorizerBase):
    in_types = (ft.GeolocationMap,)

    def fit_model(self, data):
        keys, fills = [], []
        for name in self.input_names:
            per_key = self._collect(data.host_col(name), name)
            ks = sorted(per_key)
            keys.append(ks)
            f = {}
            for k in ks:
                pts = np.asarray([p for p in per_key[k] if p], np.float64)
                f[k] = (pts.mean(axis=0).tolist() if pts.size
                        else [0.0, 0.0, 0.0])
            fills.append(f)
        return _GeoMapModel(keys=keys, track_nulls=self.track_nulls,
                            fills=fills)


# ---------------------------------------------------------------------------
# smart text maps
# ---------------------------------------------------------------------------

#: a key's hashed values fall back to the per-row fill when the per-unique
#: table (uniques x num_hash_features) would exceed this many floats
_UNIQUE_TABLE_CAP = 64_000_000


def _hashed_unique_table(vocab, num_hash_features: int):
    """``[uniques, H]`` token-count table for a vocab, or None when the
    table would pass the memory cap (the caller fills row by row)."""
    if len(vocab) * num_hash_features > _UNIQUE_TABLE_CAP:
        return None
    uvecs = np.zeros((len(vocab), num_hash_features), np.float32)
    for u, v in enumerate(vocab):
        for tok in tokenize(v):
            uvecs[u, hash_token(tok, num_hash_features)] += 1.0
    return uvecs


class _SmartTextMapModel(_KeyedModelBase):
    in_types = (ft.TextMap,)

    def __init__(self, keys: Sequence[Sequence[str]] = (),
                 track_nulls: bool = True, uid: Optional[str] = None,
                 **extra):
        # signature mirrors _KeyedModelBase so ctor-reflecting config()
        # keeps carrying keys/track_nulls through save/load
        #: "feature.key" -> detection record for keys dropped as sensitive
        #: (SensitiveFeatureInformation analog; merged into ModelInsights)
        self.sensitive: dict = {}
        super().__init__(keys=keys, track_nulls=track_nulls, uid=uid,
                         **extra)

    def sensitive_info(self) -> dict:
        return dict(self.sensitive)

    def key_width(self, i, key):
        t = self.treatments[i][key]
        if t["kind"] == "pivot":
            return len(t["categories"]) + 1 + (1 if self.track_nulls else 0)
        return self.num_hash_features + (1 if self.track_nulls else 0)

    def fill_key(self, out, off, i, key, value):
        t = self.treatments[i][key]
        if t["kind"] == "pivot":
            cats = t["categories"]
            k = len(cats)
            if value is None:
                if self.track_nulls:
                    out[off + k + 1] = 1.0
            elif value in cats:
                out[off + cats.index(value)] = 1.0
            else:
                out[off + k] = 1.0
            return
        if value is not None:
            for tok in tokenize(value):
                out[off + hash_token(tok, self.num_hash_features)] += 1.0
        if self.track_nulls:
            out[off + self.num_hash_features] = 1.0 if value is None else 0.0

    def fill_key_column(self, out, off, i, key, values):
        """Columnar per-key fill via the SHARED SmartText helpers (pivot
        slot gather / per-unique hashed table — one implementation for the
        scalar and map paths); non-string values and over-cap hash vocabs
        fall back to the exact per-row fill."""
        from transmogrifai_tpu.ops.smart_text import pivot_slot_fill
        from transmogrifai_tpu.utils.dict_encode import (
            dict_encode, scan_column,
        )
        vals = np.asarray(values, dtype=object)
        null_mask, all_str = scan_column(vals)
        t = self.treatments[i][key]
        uvecs = None
        if all_str:
            codes, vocab = dict_encode(vals)
            if t["kind"] != "pivot":
                uvecs = _hashed_unique_table(vocab, self.num_hash_features)
        if not all_str or (t["kind"] != "pivot" and uvecs is None):
            # non-strings (stringified encoding would skew matching) or an
            # over-cap hash vocab (table would not fit): exact per-row
            for r, v in enumerate(values):
                self.fill_key(out[r], off, i, key, v)
            return
        if t["kind"] == "pivot":
            pivot_slot_fill(out, off, t["categories"], codes, vocab,
                            null_mask, self.track_nulls)
            return
        rows = np.nonzero(~null_mask)[0]
        out[rows, off:off + self.num_hash_features] = uvecs[codes[rows]]
        if self.track_nulls:
            out[:, off + self.num_hash_features] = \
                null_mask.astype(np.float32)

    def key_meta(self, i, key, parent):
        t = self.treatments[i][key]
        cols = []
        if t["kind"] == "pivot":
            for c in t["categories"]:
                cols.append(VectorColumnMetadata(*parent, grouping=key,
                                                 indicator_value=c))
            cols.append(VectorColumnMetadata(*parent, grouping=key,
                                             indicator_value=OTHER))
        else:
            for j in range(self.num_hash_features):
                cols.append(VectorColumnMetadata(
                    *parent, grouping=key, descriptor_value=f"hash_{j}"))
        if self.track_nulls:
            cols.append(VectorColumnMetadata(
                *parent, grouping=key, indicator_value=NULL_INDICATOR))
        return cols

    def _extra_state(self):
        return {"treatments": self.treatments,
                "num_hash_features": self.num_hash_features,
                "sensitive": self.sensitive}


class SmartTextMapVectorizer(_MapVectorizerBase):
    """Per-key cardinality-adaptive pivot/hash (reference
    SmartTextMapVectorizer), with optional per-key name/sensitive detection
    (the map variant of the scalar SmartTextVectorizer's NameDetectFun):
    keys whose values look like human names beyond ``name_threshold`` are
    dropped from the expansion and RECORDED (``sensitive_info()`` reaches
    ModelInsights like the scalar path)."""

    in_types = (ft.TextMap,)

    def __init__(self, max_cardinality: int = 100, top_k: int = 20,
                 min_support: int = 10, num_hash_features: int = 128,
                 detect_names: bool = False, name_threshold: float = 0.5,
                 **kw):
        super().__init__(max_cardinality=max_cardinality, top_k=top_k,
                         min_support=min_support,
                         num_hash_features=num_hash_features,
                         detect_names=detect_names,
                         name_threshold=name_threshold, **kw)

    def fit_model(self, data):
        from transmogrifai_tpu.ops.smart_text import looks_like_name
        keys, treatments = [], []
        sensitive: dict[str, dict] = {}
        for name in self.input_names:
            per_key = self._collect(data.host_col(name), name)
            ks = []
            tr = {}
            for k in sorted(per_key):
                vals = per_key[k]
                if self.detect_names and vals:
                    hits = sum(1 for v in vals if looks_like_name(str(v)))
                    if hits / len(vals) >= self.name_threshold:
                        sensitive[f"{name}.{k}"] = {
                            "detected": True,
                            "probName": hits / len(vals),
                            "action": "removedFromVector"}
                        continue  # sensitive key: never expands
                ks.append(k)
                stats = TextStats(max_cardinality=self.max_cardinality)
                for v in vals:
                    stats.add(v)
                if not stats.overflowed:
                    cats = _top_k(list(stats.counts),
                                  list(stats.counts.values()),
                                  self.top_k, self.min_support)
                    tr[k] = {"kind": "pivot", "categories": cats}
                else:
                    tr[k] = {"kind": "hash"}
            keys.append(ks)
            treatments.append(tr)
        model = _SmartTextMapModel(keys=keys, track_nulls=self.track_nulls,
                                   treatments=treatments,
                                   num_hash_features=self.num_hash_features)
        model.sensitive = sensitive
        return model


# ---------------------------------------------------------------------------
# text-map length / null estimators
# ---------------------------------------------------------------------------

class _TextMapLenModel(_KeyedModelBase):
    in_types = (ft.TextMap,)

    def key_width(self, i, key):
        return 1

    def fill_key(self, out, off, i, key, value):
        out[off] = 0.0 if value is None else float(len(str(value)))

    def key_meta(self, i, key, parent):
        return [VectorColumnMetadata(*parent, grouping=key,
                                     descriptor_value="TextLen")]


class TextMapLenEstimator(_MapVectorizerBase):
    """Per-key text lengths of a TextMap -> OPVector (reference
    ``TextMapLenEstimator.scala`` — missing keys contribute length 0)."""

    in_types = (ft.TextMap,)

    def fit_model(self, data):
        keys = [sorted(self._collect(data.host_col(n), n))
                for n in self.input_names]
        return _TextMapLenModel(keys=keys, track_nulls=False)


class _TextMapNullModel(_KeyedModelBase):
    in_types = (ft.TextMap,)

    def key_width(self, i, key):
        return 1

    def fill_key(self, out, off, i, key, value):
        out[off] = 1.0 if value is None else 0.0

    def key_meta(self, i, key, parent):
        return [VectorColumnMetadata(*parent, grouping=key,
                                     indicator_value=NULL_INDICATOR)]


class TextMapNullEstimator(_MapVectorizerBase):
    """Per-key null indicators of a TextMap -> OPVector (reference
    ``TextMapNullEstimator.scala``)."""

    in_types = (ft.TextMap,)

    def fit_model(self, data):
        keys = [sorted(self._collect(data.host_col(n), n))
                for n in self.input_names]
        return _TextMapNullModel(keys=keys, track_nulls=False)


class FilterMapKeys(HostTransformer):
    """Key allow/block filtering on any map feature, type-preserving
    (reference RichMapFeature.filter, RichMapFeature.scala:58-88)."""

    in_types = (ft.OPMap,)
    out_type = ft.OPMap

    def __init__(self, allow_list: Sequence[str] = (),
                 block_list: Sequence[str] = (),
                 uid: Optional[str] = None):
        self.allow_list = list(allow_list)
        self.block_list = list(block_list)
        self._allow = frozenset(self.allow_list)
        self._block = frozenset(self.block_list)
        super().__init__(uid=uid)

    def set_input(self, *features):
        super().set_input(*features)
        self.out_type = features[0].ftype  # type-preserving
        return self

    def transform_row(self, value):
        if not value:
            return {}
        allow, block = self._allow, self._block
        return {k: v for k, v in value.items()
                if (not allow or k in allow) and k not in block}

    def config(self):
        return {"allow_list": self.allow_list,
                "block_list": self.block_list}


class Base64MapMimeDetector(HostTransformer):
    """Base64Map -> PickListMap of detected MIME types per key (reference
    RichMapFeature.detectMimeTypes)."""

    in_types = (ft.Base64Map,)
    out_type = ft.PickListMap

    def __init__(self, uid: Optional[str] = None):
        super().__init__(uid=uid)

    def transform_row(self, value):
        if not value:
            return {}
        import base64

        from transmogrifai_tpu.ops.parsers import detect_mime
        out = {}
        for k, v in value.items():
            if v is None:
                continue
            try:
                data = base64.b64decode(v, validate=False)
            except Exception:  # failure-ok: invalid base64 entry is skipped
                continue
            if data:
                out[k] = detect_mime(data)
        return out
