"""Murmur-style feature hashing: host reference hash + Pallas TPU kernel
for the segment one-hot accumulate.

The hashing-trick vectorizers split into two halves:

- **hashing** a categorical value to a bin — murmur3 x86_32 over the
  value's UTF-8 bytes (:func:`murmur3_str`, the host reference used by the
  row path and by trace-time vocab tables) or the integer finalizer
  (:func:`murmur_mix32`) for already-integer keys. Per-UNIQUE work: the
  device vectorizer hashes each dictionary vocab entry once at trace time
  (O(V), like ``OneHotModel``'s category table), never per row.
- **accumulating** the per-row bins into a dense ``[n, n_bins]`` count
  block — O(n x bins) of pure VPU work, the expensive half the host
  vectorizer used to pay in Python. :func:`segment_onehot` runs it as a
  Pallas kernel (one grid step = one row block; the ``[R, T]`` bin ids and
  the ``[R, n_bins]`` output tile live in VMEM; tokens accumulate by a
  static unroll of iota-compares — "segment accumulate" with the segment
  axis materialized as the row block) with a pure-XLA fallback
  (:func:`segment_onehot_xla`) that computes the identical compare-and-sum,
  so CPU CI asserts BITWISE parity in interpret mode.

Engine selection: ``TRANSMOGRIFAI_HASH_ENGINE`` = ``auto`` (pallas on TPU
backends) | ``pallas`` | ``xla``. The kernel is stateless per grid step —
``vmap`` batching stays legal.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["murmur3_str", "murmur3_bytes", "murmur_mix32",
           "segment_onehot", "segment_onehot_xla", "hash_engine"]

_M32 = 0xFFFFFFFF

#: rows per kernel grid step
_BLOCK_ROWS = 512


def hash_engine() -> str:
    eng = os.environ.get("TRANSMOGRIFAI_HASH_ENGINE", "auto")
    if eng not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"TRANSMOGRIFAI_HASH_ENGINE={eng!r}; one of auto|pallas|xla")
    if eng == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return eng


def murmur3_bytes(data: bytes, seed: int = 0) -> int:
    """Murmur3 x86_32 over raw bytes (reference implementation; matches
    Spark's ``Murmur3_x86_32`` family the reference HashingTF rides)."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed & _M32
    n = len(data)
    n4 = n - (n % 4)
    for i in range(0, n4, 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (k * c1) & _M32
        k = ((k << 15) | (k >> 17)) & _M32
        k = (k * c2) & _M32
        h ^= k
        h = ((h << 13) | (h >> 19)) & _M32
        h = (h * 5 + 0xE6546B64) & _M32
    k = 0
    tail = data[n4:]
    if len(tail) >= 3:
        k ^= tail[2] << 16
    if len(tail) >= 2:
        k ^= tail[1] << 8
    if len(tail) >= 1:
        k ^= tail[0]
        k = (k * c1) & _M32
        k = ((k << 15) | (k >> 17)) & _M32
        k = (k * c2) & _M32
        h ^= k
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _M32
    h ^= h >> 16
    return h


def murmur3_str(value: str, seed: int = 0) -> int:
    """Murmur3 x86_32 of a string's UTF-8 bytes — THE hash shared by the
    device vectorizer's trace-time vocab table and the row-path parity
    contract."""
    return murmur3_bytes(value.encode("utf-8"), seed)


@jax.jit
def murmur_mix32(x):
    """Murmur3 fmix32 finalizer as a jittable uint32 map — device-side
    hashing for integer-keyed features (avalanches sequential ids across
    bins)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def segment_onehot_xla(bin_ids, n_bins: int):
    """Pure-XLA fallback: ``out[r, b] = #{t : bin_ids[r, t] == b}`` with
    negative ids (missing/padding tokens) contributing nothing. The
    compare-and-sum runs in the same static token order as the kernel, so
    the two are bitwise-identical (0/1 float sums are exact)."""
    n, T = bin_ids.shape
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n, n_bins), 1)
    out = jnp.zeros((n, n_bins), jnp.float32)
    for t in range(T):  # static unroll — T is the (small) token capacity
        col = bin_ids[:, t]
        out = out + ((lanes == col[:, None]) & (col >= 0)[:, None]
                     ).astype(jnp.float32)
    return out


def _kernel(ids_ref, out_ref, *, T: int, n_bins: int):
    """One grid step = one row block: [R, T] bin ids -> [R, n_bins]
    counts, all VMEM-resident, tokens accumulated by static unroll."""
    ids = ids_ref[0]  # [R, T] int32
    R = ids.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (R, n_bins), 1)
    acc = jnp.zeros((R, n_bins), jnp.float32)
    for t in range(T):
        col = ids[:, t]
        acc = acc + ((lanes == col[:, None]) & (col >= 0)[:, None]
                     ).astype(jnp.float32)
    out_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("n_bins", "interpret"))
def _segment_onehot_pallas(bin_ids, *, n_bins: int, interpret: bool):
    n, T = bin_ids.shape
    R = min(_BLOCK_ROWS, max(int(n), 1))
    n_pad = int(np.ceil(max(n, 1) / R) * R)
    ids = jnp.pad(bin_ids.astype(jnp.int32), ((0, n_pad - n), (0, 0)),
                  constant_values=-1)  # padding rows count nothing
    nb = n_pad // R
    out = pl.pallas_call(
        functools.partial(_kernel, T=T, n_bins=n_bins),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, R, T), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, R, n_bins), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nb, R, n_bins), jnp.float32),
        interpret=interpret,
    )(ids.reshape(nb, R, T))
    return out.reshape(n_pad, n_bins)[:n]


def segment_onehot(bin_ids, n_bins: int, engine: str | None = None,
                   interpret: bool | None = None):
    """Engine-dispatched segment one-hot accumulate (see module
    docstring). ``bin_ids``: int32 [n, T], -1 = no token."""
    eng = engine or hash_engine()
    if eng != "pallas":
        return segment_onehot_xla(bin_ids, n_bins)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _segment_onehot_pallas(bin_ids, n_bins=int(n_bins),
                                  interpret=bool(interpret))
