"""PipelineData: the mixed host/device view stages execute against.

The analog of the raw + intermediate Spark DataFrame flowing through
``FitStagesUtil``: a HostFrame of ingested columns plus device-resident
columns produced by fused stage programs. Columns convert lazily between
residencies:

- numeric host columns  -> ``NumericColumn`` (f32 values + f32 mask)
- text-ish host columns -> ``CodesColumn`` (dictionary-encoded on first use)
- vector host columns   -> ``VectorColumn``
- device outputs pull back to host only at the edges (save/inspect/local).

When a mesh is active, device placement shards the row axis over the "data"
axis, padding non-divisible row counts up to the mesh multiple (padded slots
carry mask=0 / code=-1 so masked statistics ignore them; ``row_mask`` exposes
the validity vector and host pulls slice the padding back off).
"""

from __future__ import annotations

import functools
from typing import Any, Iterable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.parallel import mesh as pmesh
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.utils.dict_encode import dict_encode

__all__ = ["PipelineData"]


def _shard(arr, pad_value=0.0):
    return pmesh.pad_and_shard_rows(arr, pad_value=pad_value)


@functools.partial(jax.jit, donate_argnums=(0,))
def _fill_rows(buf, chunk, start):
    return jax.lax.dynamic_update_slice(
        buf, chunk, (start,) + (0,) * (buf.ndim - 1))


def _upload_rows(arr):
    """Host->device transfer in bounded row chunks.

    Explicit device_put of <=TRANSMOGRIFAI_UPLOAD_CHUNK_MB row slices
    keeps each transfer (and its pinned host staging buffer) small. Chunks are written into one preallocated (donated) device
    buffer so peak device memory stays ~1x the array, not 2x. No-op for
    small arrays and for already-device arrays."""
    import os
    if not isinstance(arr, np.ndarray):
        return arr
    chunk_bytes = int(os.environ.get(
        "TRANSMOGRIFAI_UPLOAD_CHUNK_MB", 96)) << 20
    if arr.nbytes <= chunk_bytes or arr.ndim == 0 or arr.shape[0] == 0:
        return jax.device_put(arr)
    per_row = max(arr.nbytes // arr.shape[0], 1)
    rows_per = max(int(chunk_bytes // per_row), 1)
    out = jnp.zeros(arr.shape, arr.dtype)
    for i in range(0, arr.shape[0], rows_per):
        out = _fill_rows(out, jax.device_put(arr[i:i + rows_per]),
                         jnp.int32(i))
    return out


@jax.jit
def _split_columns(dvals, dmasks):
    dmasks = dmasks.astype(jnp.float32)
    return (tuple(dvals[:, i] for i in range(dvals.shape[1])),
            tuple(dmasks[:, i] for i in range(dmasks.shape[1])))


class PipelineData:
    def __init__(self, host: fr.HostFrame,
                 device: Optional[Mapping[str, Any]] = None,
                 n_rows_logical: Optional[int] = None):
        self.host = host
        self.device: dict[str, Any] = dict(device or {})
        self._codes_cache: dict[str, fr.CodesColumn] = {}
        #: true (unpadded) row count; device columns may carry mesh padding
        self._n_logical = n_rows_logical if n_rows_logical is not None \
            else (host.n_rows or None)
        self._row_mask = None

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_host(host: fr.HostFrame) -> "PipelineData":
        return PipelineData(host)

    @property
    def n_rows(self) -> int:
        if self._n_logical is not None:
            return self._n_logical
        if self.host.n_rows:
            return self.host.n_rows
        for c in self.device.values():
            v = getattr(c, "values", getattr(c, "codes", None))
            if v is not None:
                return int(v.shape[0])
        return 0

    def row_mask(self) -> jnp.ndarray:
        """Device validity vector over the (possibly padded) row axis:
        1.0 for real rows, 0.0 for mesh-padding slots. Statistics stages
        weight by this so padded rows contribute monoid identity."""
        if self._row_mask is None:
            n = self.n_rows
            ctx = pmesh.current_mesh()
            n_pad = pmesh.pad_rows(n) if ctx is not None else n
            mask = np.zeros(n_pad, np.float32)
            mask[:n] = 1.0
            self._row_mask = _shard(jnp.asarray(mask))
        return self._row_mask

    def has(self, name: str) -> bool:
        return name in self.device or name in self.host

    # -- column access -------------------------------------------------------
    def host_col(self, name: str) -> fr.HostColumn:
        if name in self.host:
            return self.host[name]
        if name in self.device:
            return self._device_to_host(self.device[name])
        raise KeyError(f"No column {name!r}")

    def device_col(self, name: str) -> Any:
        if name in self.device:
            return self.device[name]
        if name in self._codes_cache:
            return self._codes_cache[name]
        if name not in self.host:
            raise KeyError(f"No column {name!r}")
        col = self.host[name]
        kind = col.kind
        if kind in fr.NUMERIC_KINDS:
            # bulk path: move EVERY numeric host column in two transfers
            # (one [n,k] values matrix + one mask matrix) instead of 2k
            # small ones — per-transfer latency, not bandwidth, dominates
            # small uploads
            self._bulk_upload_numeric()
            return self.device[name]
        if kind == "vector":
            # same chunked-transfer discipline as the numeric bulk path
            # (wide pre-vectorized matrices are the other >GB upload);
            # the mesh path still places in one transfer — chunked
            # SHARDED placement is future work
            from transmogrifai_tpu.utils.tracing import span
            vals = np.asarray(col.values, np.float32)
            with span("fe.upload", column=name, bytes=int(vals.nbytes)):
                dval = _shard(vals) if pmesh.current_mesh() is not None \
                    else _upload_rows(vals)
            dev = fr.VectorColumn(dval, col.meta)
            self.device[name] = dev
            return dev
        if kind in fr.TEXT_KINDS:
            dev = self._encode_text(col)
            self._codes_cache[name] = dev
            return dev
        raise TypeError(
            f"Column {name!r} of kind {kind!r} has no generic device "
            "representation; the consuming stage must handle it on host")

    def _bulk_upload_numeric(self) -> None:
        pending = [(n, c) for n, c in self.host.columns.items()
                   if c.kind in fr.NUMERIC_KINDS and n not in self.device]
        if not pending:
            return
        from transmogrifai_tpu.utils.profiling import OpStep, profiler
        from transmogrifai_tpu.utils.tracing import span
        n = len(pending[0][1].values)
        # a date column's exact day parts ride as two more value columns
        dates = [i for i, (_, c) in enumerate(pending)
                 if c.kind in ("date", "datetime")]
        # float32 values and uint8 masks go up
        with profiler.phase(OpStep.DATA_READING_AND_FILTERING), \
                span("ingest.numeric", columns=len(pending), rows=n,
                     bytes=5 * n * len(pending) + 8 * n * len(dates)):
            filled = [np.where(c.mask, c.values, 0.0) for _, c in pending]
            vals = np.stack(
                [v.astype(np.float32) for v in filled]
                + [p for i in dates for p in fr.day_parts(filled[i])],
                axis=1)
            # masks travel as uint8 (4x fewer bytes over the link) and
            # widen to f32 on device inside _split_columns
            masks = np.stack([c.mask.astype(np.uint8) for _, c in pending],
                             axis=1)
            if pmesh.current_mesh() is not None:
                dvals = _shard(vals)
                dmasks = _shard(masks)
            else:
                dvals = _upload_rows(vals)
                dmasks = _upload_rows(masks)
            # split into per-column arrays inside ONE jitted program — k
            # eager `dvals[:, i]` slices would pay k dispatches
            cols_v, cols_m = _split_columns(dvals, dmasks)
            parts = {i: (cols_v[len(pending) + 2 * j],
                         cols_v[len(pending) + 2 * j + 1])
                     for j, i in enumerate(dates)}
            for i, (name, _) in enumerate(pending):
                self.device[name] = fr.NumericColumn(cols_v[i], cols_m[i],
                                                     parts.get(i))

    @staticmethod
    def _encode_text(col: fr.HostColumn) -> fr.CodesColumn:
        codes, vocab = dict_encode(col.values)
        return fr.CodesColumn(_shard(codes, pad_value=-1), tuple(vocab))

    def _device_to_host(self, col: Any) -> fr.HostColumn:
        n = self.n_rows  # slice mesh padding back off on host pull
        if isinstance(col, fr.NumericColumn):
            vals = np.asarray(col.values, dtype=np.float64)[:n]
            mask = (np.asarray(col.mask) > 0.5)[:n]
            return fr.HostColumn(ft.Real, vals, mask)
        if isinstance(col, fr.VectorColumn):
            return fr.HostColumn(ft.OPVector,
                                 np.asarray(col.values, np.float32)[:n],
                                 meta=col.metadata)
        if isinstance(col, fr.CodesColumn):
            codes = np.asarray(col.codes)[:n]
            vals = np.empty(codes.shape[0], dtype=object)
            for i, c in enumerate(codes):
                vals[i] = col.vocab[c] if c >= 0 else None
            return fr.HostColumn(ft.Text, vals)
        if isinstance(col, fr.PredictionColumn):
            pred = np.asarray(col.prediction, np.float64)[:n]
            raw = np.asarray(col.raw_prediction, np.float64)[:n]
            prob = np.asarray(col.probability, np.float64)[:n]
            vals = np.empty(pred.shape[0], dtype=object)
            for i in range(pred.shape[0]):
                vals[i] = ft.Prediction.make(pred[i], raw[i], prob[i]).value
            return fr.HostColumn(ft.Prediction, vals)
        raise TypeError(f"Cannot pull {type(col).__name__} to host")

    # -- updates -------------------------------------------------------------
    def with_host_cols(self, new: Mapping[str, fr.HostColumn]) -> "PipelineData":
        out = PipelineData(self.host.with_columns(new), self.device,
                           n_rows_logical=self._n_logical)
        # a text column's codes hold while its host values do: a stage
        # that adds host columns does not make the next one encode again
        stale = set(new) & set(self._codes_cache)
        out._codes_cache = ({n: c for n, c in self._codes_cache.items()
                             if n not in stale} if stale
                            else self._codes_cache)
        out._row_mask = self._row_mask
        return out

    def with_device_cols(self, new: Mapping[str, Any]) -> "PipelineData":
        dev = dict(self.device)
        dev.update(new)
        out = PipelineData(self.host, dev, n_rows_logical=self._n_logical)
        out._codes_cache = self._codes_cache
        out._row_mask = self._row_mask
        return out

    def without(self, names: Iterable[str]) -> "PipelineData":
        """This data less the columns ``names``, on host and device: the
        training pass lets go of a derived column once its last consumer has
        run, so a wide vector and the blocks it was combined from are never
        resident together for longer than the stage that reads both. Waits
        first for the programs that still read them: a buffer let go while
        its consumer runs stays allocated until the consumer ends, and
        whatever is enqueued meanwhile is allocated beside it."""
        dead = set(names)
        jax.block_until_ready([c for n, c in self.device.items()
                               if n not in dead])
        host = fr.HostFrame({n: c for n, c in self.host.columns.items()
                             if n not in dead}, self.host.key)
        out = PipelineData(host, {n: c for n, c in self.device.items()
                                  if n not in dead},
                           n_rows_logical=self.n_rows)
        out._codes_cache = {n: c for n, c in self._codes_cache.items()
                            if n not in dead}
        out._row_mask = self._row_mask
        return out

    def select_result(self, names: Iterable[str]) -> "PipelineData":
        names = list(names)
        host_cols = {n: self.host[n] for n in names if n in self.host}
        dev_cols = {n: self.device[n] for n in names if n in self.device}
        return PipelineData(fr.HostFrame(host_cols, self.host.key), dev_cols,
                            n_rows_logical=self._n_logical)

    # -- row-axis ops (splits) ----------------------------------------------
    def take(self, idx: np.ndarray) -> "PipelineData":
        host = self.host.take(idx) if self.host.names() else self.host
        jidx = jnp.asarray(np.asarray(idx))
        # re-pad + re-shard the gathered rows so fold subsets keep the mesh
        # invariant (device length == pad_rows(logical), mask 0 on padding) —
        # row_mask() of the subset must match its device columns' length
        dev = {}
        for n, c in self.device.items():
            if isinstance(c, fr.NumericColumn):
                dev[n] = fr.NumericColumn(
                    _shard(c.values[jidx]), _shard(c.mask[jidx]),
                    None if c.day_parts is None
                    else tuple(_shard(p[jidx]) for p in c.day_parts))
            elif isinstance(c, fr.VectorColumn):
                dev[n] = fr.VectorColumn(_shard(c.values[jidx]), c.metadata)
            elif isinstance(c, fr.CodesColumn):
                dev[n] = fr.CodesColumn(_shard(c.codes[jidx], pad_value=-1),
                                        c.vocab)
            elif isinstance(c, fr.PredictionColumn):
                dev[n] = fr.PredictionColumn(
                    _shard(c.prediction[jidx]), _shard(c.raw_prediction[jidx]),
                    _shard(c.probability[jidx]))
            else:
                raise TypeError(f"take: unsupported device column {type(c)}")
        if self.host.names():
            return PipelineData(host, dev, n_rows_logical=len(idx))
        return PipelineData(fr.HostFrame({}, None), dev,
                            n_rows_logical=len(idx))

    def vector_meta(self, name: str):
        col = self.device.get(name)
        return getattr(col, "metadata", None)
