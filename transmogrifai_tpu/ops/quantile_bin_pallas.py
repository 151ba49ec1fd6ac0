"""Pallas TPU kernel for quantile/fixed-split bucketization.

The bucketizer transform (``ops/vectorizers/bucketizers._bucketize_block``)
is a bin-edge search over the fitted splits followed by a one-hot expand:

    idx[r]  = #{j : inner_split[j] <= v[r]}            (searchsorted right)
    slot[r] = idx | invalid | null                      (range + mask rules)
    out     = one_hot(slot, width)                      [n, width] f32

The XLA path materializes the searchsorted gather + one-hot as separate
HLOs; at Criteo widths (13 numeric columns x ~34-bucket tree splits inside
one fused FE program) the one-hot scatter is pure VPU work that this kernel
keeps entirely in VMEM: one grid step = one row block laid out with rows on
the lane axis, the split vector (tiny, <= a few hundred f32) in SMEM, bin
index by comparison count and the one-hot written as a single iota-compare
— no intermediate index array ever reaches HBM.

Engine selection: ``TRANSMOGRIFAI_BUCKET_ENGINE`` picks
``pallas`` / ``xla`` / ``auto`` (auto = pallas on TPU backends, xla
elsewhere); CPU CI runs the kernel in interpret mode and asserts BITWISE
parity with the XLA path (`tests/test_ingest_fusion.py`). The kernel is
stateless per grid step, so ``vmap`` batching (a future stacked use) stays
legal.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bucketize_block", "bucketize_block_xla", "bucket_engine"]

#: rows per kernel grid step (one VMEM-resident block)
_BLOCK_ROWS = 1024


def bucket_engine() -> str:
    """Resolved engine: ``pallas`` | ``xla``. ``auto`` (default) picks
    pallas only on TPU backends — the XLA path is the portable
    fallback every CPU run takes."""
    eng = os.environ.get("TRANSMOGRIFAI_BUCKET_ENGINE", "auto")
    if eng not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"TRANSMOGRIFAI_BUCKET_ENGINE={eng!r}; one of auto|pallas|xla")
    if eng == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return eng


def bucketize_block_xla(values, mask, splits: np.ndarray,
                        track_invalid: bool, track_nulls: bool):
    """Pure-XLA reference path (the pre-round-14 ``_bucketize_block``
    math, verbatim): jittable one-hot bucket block for one numeric
    column. Layout: [bucket_0..bucket_{k-1}, invalid?, null?]."""
    k = len(splits) - 1
    inner = jnp.asarray(splits[1:-1], jnp.float32)
    idx = jnp.searchsorted(inner, values, side="right") if k > 1 else (
        jnp.zeros(values.shape, jnp.int32))
    in_range = (values >= splits[0]) & (values <= splits[-1])
    width = k + int(track_invalid) + int(track_nulls)
    # slot: bucket for valid, k for invalid, k+trackInvalid for null,
    # `width` (one-hot of width drops it) for untracked cases
    invalid_slot = k if track_invalid else width
    null_slot = k + int(track_invalid) if track_nulls else width
    slot = jnp.where(in_range, idx, invalid_slot)
    slot = jnp.where(mask > 0, slot, null_slot)
    return jax.nn.one_hot(slot, width, dtype=jnp.float32)


def _kernel(sp_ref, v_ref, m_ref, out_ref, *, k: int, width: int,
            invalid_slot: int, null_slot: int):
    """One grid step = one row block, rows on the LANE axis.

    The bin-edge search is a comparison COUNT against the inner splits
    (sum over j of v >= inner[j] == searchsorted side="right"), the
    range/null slot rules match the XLA path exactly, and the one-hot is
    a single [width, R] sublane-iota compare — all VPU element-wise work
    on lane-dense tiles. The splits are scalars read from SMEM."""
    v = v_ref[...]                    # [1, R] f32
    m = m_ref[...]                    # [1, R] f32
    idx = jnp.zeros(v.shape, jnp.int32)
    for j in range(1, k):             # static unroll over the inner splits
        idx = idx + (v >= sp_ref[j]).astype(jnp.int32)
    in_range = (v >= sp_ref[0]) & (v <= sp_ref[k])
    slot = jnp.where(in_range, idx, invalid_slot)
    slot = jnp.where(m > 0, slot, null_slot)
    rows = jax.lax.broadcasted_iota(jnp.int32, (width, v.shape[1]), 0)
    out_ref[...] = (rows == slot).astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("k", "track_invalid", "track_nulls",
                              "interpret"))
def _bucketize_pallas(values, mask, splits, *, k: int, track_invalid: bool,
                      track_nulls: bool, interpret: bool):
    n = values.shape[0]
    width = k + int(track_invalid) + int(track_nulls)
    invalid_slot = k if track_invalid else width
    null_slot = k + int(track_invalid) if track_nulls else width
    R = _BLOCK_ROWS
    nb = max(-(-n // R), 1)
    n_pad = nb * R
    # padded rows carry mask 0 -> null_slot (or all-zeros): harmless, and
    # sliced back off below. The kernel computes the TRANSPOSED block
    # [width, n_pad] so every tile is lane-dense (Mosaic's (8, 128) block
    # rule: a (1, R) block's sublane dim equals the array's, R % 128 == 0)
    v = jnp.pad(values.astype(jnp.float32), (0, n_pad - n))
    m = jnp.pad(mask.astype(jnp.float32), (0, n_pad - n))
    out_t = pl.pallas_call(
        functools.partial(_kernel, k=k, width=width,
                          invalid_slot=invalid_slot, null_slot=null_slot),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, R), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((width, R), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((width, n_pad), jnp.float32),
        interpret=interpret,
    )(splits, v.reshape(1, n_pad), m.reshape(1, n_pad))
    return out_t[:, :n].T


def bucketize_block(values, mask, splits: np.ndarray, track_invalid: bool,
                    track_nulls: bool, engine: str | None = None,
                    interpret: bool | None = None):
    """Engine-dispatched bucket block (see module docstring). ``engine``
    overrides the env-resolved default; ``interpret`` forces the pallas
    interpreter (CPU parity tests). Degenerate shapes (no splits, k < 1)
    keep the XLA path — there is nothing for a kernel to win there."""
    eng = engine or bucket_engine()
    k = len(splits) - 1
    if eng != "pallas" or k < 1:
        return bucketize_block_xla(values, mask, splits,
                                   track_invalid, track_nulls)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _bucketize_pallas(
        values, mask, jnp.asarray(splits, jnp.float32), k=k,
        track_invalid=bool(track_invalid), track_nulls=bool(track_nulls),
        interpret=bool(interpret))
