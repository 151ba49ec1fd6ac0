"""Columnar data: host frame (numpy) and device columns (JAX pytrees).

This replaces the reference's Spark DataFrame/RDD data abstraction
(`features/.../utils/spark/RichDataset.scala`, `readers/DataReader.scala`)
with a TPU-first design:

- **HostFrame**: immutable dict of named ``HostColumn``s (numpy-backed).
  Strings and maps live here; categorical columns can be dictionary-encoded.
  This is the analog of the raw DataFrame produced by the readers.
- **Device columns**: fixed-shape arrays + validity masks registered as JAX
  pytrees (``NumericColumn``, ``CodesColumn``, ``VectorColumn``). Nullability
  is a mask, not an Option. These flow through jitted, mesh-sharded stage
  programs; the row (batch) axis shards over the ``"data"`` mesh axis.

There is no shuffle: grouped aggregation is host-side sort + device segment
ops (see readers.aggregate).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Iterable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu.types import feature_types as ft

__all__ = [
    "HostColumn", "HostFrame", "NumericColumn", "CodesColumn", "VectorColumn",
    "DeviceFrame", "NUMERIC_KINDS", "TEXT_KINDS", "MAP_KINDS", "LIST_KINDS",
    "frame_fingerprint", "device_col_nbytes",
]

# device_kind families
NUMERIC_KINDS = frozenset({"real", "integral", "binary", "date", "datetime"})
TEXT_KINDS = frozenset({
    "text", "textarea", "email", "url", "phone", "id", "picklist", "combobox",
    "base64", "country", "state", "city", "postalcode", "street",
})
LIST_KINDS = frozenset({"textlist", "datelist", "datetimelist"})
MAP_KINDS = frozenset({k for k in (
    "map_text map_textarea map_email map_url map_phone map_id map_picklist "
    "map_combobox map_base64 map_country map_state map_city map_postalcode "
    "map_street map_real map_currency map_percent map_integral map_date "
    "map_datetime map_binary map_multipicklist map_geolocation map_namestats "
    "prediction").split()})


def _kind_of(ftype: type[ft.FeatureType]) -> str:
    return ftype.device_kind


# ---------------------------------------------------------------------------
# Host columns
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HostColumn:
    """One feature column on host.

    Representation by kind family:
      numerics      -> float64 ``values`` + bool ``mask`` (True = present)
      text          -> object ndarray of ``str | None`` in ``values``
      lists/sets    -> object ndarray of list/set in ``values``
      geolocation   -> float64 (n, 3) ``values`` + bool ``mask``
      vector        -> float32 (n, d) ``values``
      maps          -> object ndarray of dict in ``values``
    """

    ftype: type[ft.FeatureType]
    values: np.ndarray
    mask: Optional[np.ndarray] = None  # bool[n]; None for kinds w/o mask
    meta: Optional[Any] = None         # VectorMetadata for vector kinds

    @property
    def kind(self) -> str:
        return _kind_of(self.ftype)

    def __len__(self) -> int:
        return int(self.values.shape[0])

    # -- construction -------------------------------------------------------
    @staticmethod
    def builder(ftype: type[ft.FeatureType]):
        """Resolve the kind dispatch ONCE and return a chunk builder
        ``(raw values) -> HostColumn``. Chunked/streaming ingest calls
        this per reader, not per micro-batch: the per-column schema
        resolution (kind family, representation choice) used to re-run
        on every chunk concat (``readers/base.generate_frame``), which a
        high-frequency micro-batch stream paid per batch."""
        kind = _kind_of(ftype)
        if kind in NUMERIC_KINDS:
            return lambda raw: HostColumn._build_numeric(ftype, raw)
        if kind in TEXT_KINDS:
            return lambda raw: HostColumn._build_text(ftype, raw)
        if kind == "geolocation":
            return lambda raw: HostColumn._build_geolocation(ftype, raw)
        if kind == "vector":
            return lambda raw: HostColumn._build_vector(ftype, raw)
        return lambda raw: HostColumn._build_object(ftype, raw)

    @staticmethod
    def from_values(ftype: type[ft.FeatureType], raw: Sequence[Any]) -> "HostColumn":
        """Build a column from python values (None = missing), validating via
        the feature type (the columnar analog of wrapping each value)."""
        return HostColumn.builder(ftype)(raw)

    @staticmethod
    def _build_numeric(ftype: type[ft.FeatureType], raw: Sequence[Any]) -> "HostColumn":
        n = len(raw)
        vals = np.zeros(n, dtype=np.float64)
        mask = np.zeros(n, dtype=bool)
        for i, v in enumerate(raw):
            pv = ftype._validate(v)
            if pv is not None:
                vals[i] = float(pv)
                mask[i] = True
        if not ftype.is_nullable and not mask.all():
            raise ft.FeatureTypeValueError(
                f"{ftype.__name__} column contains empty values")
        return HostColumn(ftype, vals, mask)

    @staticmethod
    def _build_text(ftype: type[ft.FeatureType], raw: Sequence[Any]) -> "HostColumn":
        vals = np.empty(len(raw), dtype=object)
        for i, v in enumerate(raw):
            vals[i] = ftype._validate(v)
        return HostColumn(ftype, vals, None)

    @staticmethod
    def _build_geolocation(ftype: type[ft.FeatureType], raw: Sequence[Any]) -> "HostColumn":
        n = len(raw)
        vals = np.zeros((n, 3), dtype=np.float64)
        mask = np.zeros(n, dtype=bool)
        for i, v in enumerate(raw):
            pv = ftype._validate(v)
            if pv:
                vals[i] = pv
                mask[i] = True
        return HostColumn(ftype, vals, mask)

    @staticmethod
    def _build_vector(ftype: type[ft.FeatureType], raw: Sequence[Any]) -> "HostColumn":
        n = len(raw)
        arrs = [np.asarray(ftype._validate(v), dtype=np.float32) for v in raw]
        d = max((a.shape[0] for a in arrs), default=0)
        vals = np.zeros((n, d), dtype=np.float32)
        for i, a in enumerate(arrs):
            if a.shape[0] not in (0, d):
                raise ft.FeatureTypeValueError(
                    f"ragged vector column: {a.shape[0]} vs {d}")
            if a.shape[0] == d:
                vals[i] = a
        return HostColumn(ftype, vals, None)

    @staticmethod
    def _build_object(ftype: type[ft.FeatureType], raw: Sequence[Any]) -> "HostColumn":
        # lists, sets, maps, prediction -> object array of validated values
        vals = np.empty(len(raw), dtype=object)
        for i, v in enumerate(raw):
            vals[i] = ftype._validate(v)
        return HostColumn(ftype, vals, None)

    # -- access -------------------------------------------------------------
    def python_value(self, i: int) -> Any:
        """Row value as the feature type's python value (None when missing)."""
        kind = self.kind
        if kind in NUMERIC_KINDS:
            if not self.mask[i]:
                return None
            v = self.values[i]
            if kind in ("integral", "date", "datetime"):
                return int(v)
            if kind == "binary":
                return bool(v)
            return float(v)
        if kind == "geolocation":
            return list(self.values[i]) if self.mask[i] else []
        if kind == "vector":
            return np.asarray(self.values[i])
        return self.values[i]

    def take(self, idx: np.ndarray) -> "HostColumn":
        return HostColumn(
            self.ftype,
            self.values[idx],
            None if self.mask is None else self.mask[idx],
            self.meta,
        )

    @staticmethod
    def concat(chunks: Sequence["HostColumn"]) -> "HostColumn":
        """Row-concatenate same-typed column chunks (the chunked-ingest
        combiner). Vector chunks may differ in width (per-chunk max): the
        result pads to the overall max."""
        if not chunks:
            raise ValueError("concat of zero chunks")
        first = chunks[0]
        if len(chunks) == 1:
            return first
        if first.kind == "vector":
            widths = {int(c.values.shape[1]) for c in chunks}
            d = max(widths)
            # chunks may legitimately be NARROWER only when entirely empty
            # (width 0: every row was an empty vector); two different
            # non-zero widths are the same ragged-column error from_values
            # raises on unchunked data
            if len(widths - {0, d}) > 0:
                raise ft.FeatureTypeValueError(
                    f"ragged vector column across chunks: widths {sorted(widths)}")
            n = sum(len(c) for c in chunks)
            vals = np.zeros((n, d), np.float32)
            at = 0
            for c in chunks:
                vals[at:at + len(c), :c.values.shape[1]] = c.values
                at += len(c)
            meta = next((c.meta for c in chunks if c.meta is not None), None)
            return HostColumn(first.ftype, vals, None, meta)
        values = np.concatenate([c.values for c in chunks])
        mask = (np.concatenate([c.mask for c in chunks])
                if first.mask is not None else None)
        return HostColumn(first.ftype, values, mask, first.meta)


# ---------------------------------------------------------------------------
# Device columns (JAX pytrees)
# ---------------------------------------------------------------------------

#: milliseconds in a day: a date column's ``day_parts`` split
MS_PER_DAY = 86_400_000


def day_parts(ms: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(whole days since the epoch, milliseconds into the day)`` of epoch
    milliseconds, each as float32 holds it: the days exactly (to year
    47,900), the milliseconds to 4 ms. Epoch milliseconds themselves are
    2^17 ms = 131 s apart in float32 at 2016."""
    ms = np.asarray(ms, np.float64)
    days = np.floor(ms / MS_PER_DAY)
    return (days.astype(np.float32),
            (ms - days * MS_PER_DAY).astype(np.float32))


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class NumericColumn:
    """float32 values + float32 {0,1} mask. Missing slots hold 0 in values.

    A Date/DateTime column (epoch milliseconds) also carries ``day_parts``,
    ``(days f32[n], ms_of_day f32[n])`` from :func:`day_parts`, for what
    needs the time of day (``DateToUnitCircleVectorizer``); every other
    column has none, and flattens as it always did."""

    values: jax.Array  # f32[n]
    mask: jax.Array    # f32[n]
    day_parts: Optional[tuple] = None

    def tree_flatten(self):
        if self.day_parts is None:
            return (self.values, self.mask), None
        return (self.values, self.mask, *self.day_parts), "day_parts"

    @classmethod
    def tree_unflatten(cls, aux, children):
        if aux is None:
            return cls(*children)
        return cls(children[0], children[1], tuple(children[2:]))

    @staticmethod
    def from_host(col: HostColumn) -> "NumericColumn":
        vals = np.where(col.mask, col.values, 0.0)
        parts = None
        if col.kind in ("date", "datetime"):
            parts = tuple(jnp.asarray(p) for p in day_parts(vals))
        return NumericColumn(
            jnp.asarray(vals, dtype=jnp.float32),
            jnp.asarray(col.mask, dtype=jnp.float32), parts)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class CodesColumn:
    """Dictionary-encoded categorical: int32 codes into ``vocab``; -1 = null.

    The vocab is static aux data (affects compiled shapes only via downstream
    one-hot sizes, which are fixed at fit time).
    """

    codes: jax.Array            # i32[n]
    vocab: tuple[str, ...]      # aux (host-side)

    def tree_flatten(self):
        return (self.codes,), self.vocab

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class VectorColumn:
    """Dense f32[n, d] feature-vector block with provenance metadata.

    The metadata (see ``transmogrifai_tpu.vector_metadata``) is aux data: it
    names every one of the d columns with its parent feature, grouping,
    pivot/indicator value and null-indicator flag — the backbone of
    SanityChecker, ModelInsights and LOCO, mirroring the reference's
    ``OpVectorMetadata`` riding on DataFrame schema.
    """

    values: jax.Array  # f32[n, d]
    metadata: Any = None  # VectorMetadata | None (aux, static)

    def tree_flatten(self):
        return (self.values,), self.metadata

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    @property
    def width(self) -> int:
        return int(self.values.shape[-1])


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PredictionColumn:
    """Model output: prediction f32[n], raw scores f32[n,C], probabilities
    f32[n,C] — the columnar analog of the reference's ``Prediction`` map
    type (prediction/rawPrediction/probability keys)."""

    prediction: jax.Array
    raw_prediction: jax.Array
    probability: jax.Array

    def tree_flatten(self):
        return (self.prediction, self.raw_prediction, self.probability), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def pos_score(self) -> jax.Array:
        """Positive-class score: P(class=1) when a real probability matrix is
        present, else the raw prediction. The single guard for the (n,0)
        empty-probability convention used by margin-only/regression models."""
        prob = self.probability
        if prob is not None and getattr(prob, "ndim", 1) == 2 and prob.shape[1] >= 2:
            return jnp.asarray(prob[:, 1], jnp.float32)
        return jnp.asarray(self.prediction, jnp.float32)


DeviceColumn = Any  # NumericColumn | CodesColumn | VectorColumn | PredictionColumn
DeviceFrame = dict  # dict[str, DeviceColumn]


# ---------------------------------------------------------------------------
# Host frame
# ---------------------------------------------------------------------------

class HostFrame:
    """Immutable named collection of equal-length HostColumns.

    The analog of the raw/intermediate Spark DataFrame. Cheap structural
    sharing: with_columns/select return new frames referencing the same
    column objects.
    """

    def __init__(self, columns: Mapping[str, HostColumn], key: Optional[np.ndarray] = None):
        lens = {len(c) for c in columns.values()}
        if len(lens) > 1:
            raise ValueError(f"ragged frame: column lengths {lens}")
        self._cols = dict(columns)
        self._n = lens.pop() if lens else 0
        self.key = key  # optional entity-key column (object ndarray of str)

    # -- construction -------------------------------------------------------
    @staticmethod
    def from_dict(data: Mapping[str, tuple[type[ft.FeatureType], Sequence[Any]]],
                  key: Optional[Sequence[str]] = None) -> "HostFrame":
        cols = {name: HostColumn.from_values(t, vals) for name, (t, vals) in data.items()}
        k = None if key is None else np.asarray(list(key), dtype=object)
        return HostFrame(cols, k)

    # -- structure ----------------------------------------------------------
    @property
    def n_rows(self) -> int:
        return self._n

    @property
    def columns(self) -> dict[str, HostColumn]:
        return dict(self._cols)

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def __getitem__(self, name: str) -> HostColumn:
        return self._cols[name]

    def names(self) -> list[str]:
        return list(self._cols)

    def with_columns(self, new: Mapping[str, HostColumn]) -> "HostFrame":
        cols = dict(self._cols)
        cols.update(new)
        return HostFrame(cols, self.key)

    def select(self, names: Iterable[str]) -> "HostFrame":
        return HostFrame({n: self._cols[n] for n in names}, self.key)

    def drop(self, names: Iterable[str]) -> "HostFrame":
        names = set(names)
        return HostFrame({n: c for n, c in self._cols.items() if n not in names},
                         self.key)

    def take(self, idx: np.ndarray) -> "HostFrame":
        return HostFrame({n: c.take(idx) for n, c in self._cols.items()},
                         None if self.key is None else self.key[idx])

    def row(self, i: int) -> dict[str, Any]:
        return {n: c.python_value(i) for n, c in self._cols.items()}

    def iter_rows(self):
        for i in range(self._n):
            yield self.row(i)

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}: {c.ftype.__name__}" for n, c in self._cols.items())
        return f"HostFrame(n={self._n}, [{cols}])"


# ---------------------------------------------------------------------------
# Identity + accounting helpers (round 14: device-frame cache)
# ---------------------------------------------------------------------------

def _hash_strings(h, v: np.ndarray) -> bool:
    """Feed an object column of strings and ``None`` to ``h`` with no
    Python a row: the values end to end (a block of rows at a time), their
    lengths and the null mask. False, with ``h`` untouched, where the
    column holds anything else."""
    null = np.equal(v, None)
    if null.all():
        return False
    text = np.where(null, "", v) if null.any() else v
    try:
        lengths = np.frompyfunc(str.__len__, 1, 1)(text).astype(np.int64)
    except TypeError:
        return False
    h.update(b"str")
    for lo in range(0, len(text), 4096):
        h.update("".join(text[lo:lo + 4096].tolist()).encode(
            "utf-8", "surrogatepass"))
    h.update(lengths.tobytes())
    h.update(null.tobytes())
    return True


class _CountedHash:
    """A hash that counts the bytes fed to it."""
    __slots__ = ("h", "nbytes")

    def __init__(self, h):
        self.h, self.nbytes = h, 0

    def update(self, b) -> None:
        self.nbytes += len(b)
        self.h.update(b)


def frame_fingerprint(frame: "HostFrame") -> str:
    """Content fingerprint of a host frame: column names, feature types,
    and the FULL value/mask bytes (blake2b). This keys the device-frame
    cache, so it must be collision-safe in practice — numeric columns hash
    at memory bandwidth; a column of strings and nulls hashes its text end
    to end with each value's length and null flag beside it (which together
    determine every value), in bulk; other object columns (maps, lists)
    hash per-row reprs. Two frames with equal fingerprints produce
    identical device columns. Under a ``frame.fingerprint`` span with the
    frame's rows and columns and the bytes hashed."""
    import hashlib

    from transmogrifai_tpu.utils.tracing import now, recorder
    t0 = now()
    h = _CountedHash(hashlib.blake2b(digest_size=16))
    for name in sorted(frame.names()):
        col = frame[name]
        h.update(name.encode())
        h.update(col.ftype.__name__.encode())
        v = col.values
        h.update(str(v.shape).encode())
        if v.dtype == object and not _hash_strings(h, v):
            for x in v:
                h.update(repr(x).encode())
                h.update(b"\x1f")
        elif v.dtype != object:
            h.update(np.ascontiguousarray(v).tobytes())
        if col.mask is not None:
            h.update(np.ascontiguousarray(col.mask).tobytes())
        if col.meta is not None:
            # vector provenance metadata distinguishes otherwise
            # value-equal frames (it rides the cached device column)
            h.update(repr(col.meta).encode())
    if frame.key is not None:
        for k in frame.key:
            h.update(str(k).encode())
            h.update(b"\x1f")
    recorder.add("frame.fingerprint", t0, now(), rows=frame.n_rows,
                 columns=len(frame.names()), bytes=h.nbytes)
    return h.h.hexdigest()


def device_col_nbytes(col: Any) -> int:
    """Approximate HBM bytes a device column holds (leaf array nbytes);
    the device-frame cache's budget accounting."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(col):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total
