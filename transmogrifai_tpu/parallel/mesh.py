"""Device mesh management: the framework's distributed substrate.

Replaces the reference's Spark driver/executor + shuffle/broadcast comm layer
(SURVEY §2.7): all distribution here is a single-program `jax.sharding.Mesh`
with XLA collectives over ICI/DCN. Two named axes:

- ``"data"``  — rows (batch) shard here; the workhorse axis (reference P1).
- ``"model"`` — model-selection candidates / feature-width shard here
  (reference P3/P5 thread pools and the O(d^2) stats decomposition).

Multi-host pods join the same mesh via ``jax.distributed.initialize`` (DCN);
see ``transmogrifai_tpu.parallel.distributed``.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MeshContext", "make_mesh", "use_mesh", "current_mesh", "row_sharding",
    "replicated", "pad_rows", "shard_rows", "num_data_shards",
    "pad_and_shard_rows", "shard_training_rows", "fold_axis_on_model",
    "shard_stacked_training_rows",
]

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclass(frozen=True)
class MeshContext:
    """A mesh plus the framework's axis conventions."""

    mesh: Mesh

    @property
    def n_data(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def n_model(self) -> int:
        return self.mesh.shape.get(MODEL_AXIS, 1)

    def row_sharding(self, *trailing_axes: Optional[str]) -> NamedSharding:
        """Rows sharded over 'data'; trailing dims per ``trailing_axes``."""
        return NamedSharding(self.mesh, P(DATA_AXIS, *trailing_axes))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def model_sharding(self, *trailing_axes: Optional[str]) -> NamedSharding:
        """Leading candidate axis sharded over 'model'."""
        return NamedSharding(self.mesh, P(MODEL_AXIS, *trailing_axes))


_current: contextvars.ContextVar[Optional[MeshContext]] = contextvars.ContextVar(
    "transmogrifai_mesh", default=None)


def make_mesh(n_data: Optional[int] = None, n_model: int = 1,
              devices=None) -> MeshContext:
    """Build a (data, model) mesh over available devices.

    Defaults to all devices on the data axis — the right choice for the
    row-parallel workhorse path. ``n_model > 1`` carves off a candidate-
    parallel axis for the ModelSelector sweep.
    """
    devices = list(devices if devices is not None else jax.devices())
    total = len(devices)
    if n_data is None:
        n_data = total // n_model
    if n_data * n_model != total:
        raise ValueError(
            f"mesh shape {n_data}x{n_model} != device count {total}")
    arr = np.asarray(devices).reshape(n_data, n_model)
    return MeshContext(Mesh(arr, (DATA_AXIS, MODEL_AXIS)))


@contextlib.contextmanager
def use_mesh(ctx: MeshContext):
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def current_mesh() -> Optional[MeshContext]:
    """The active mesh, or None (single-device eager fallback)."""
    return _current.get()


def row_sharding() -> Optional[NamedSharding]:
    ctx = current_mesh()
    return None if ctx is None else ctx.row_sharding()


def replicated() -> Optional[NamedSharding]:
    ctx = current_mesh()
    return None if ctx is None else ctx.replicated()


def num_data_shards() -> int:
    ctx = current_mesh()
    return 1 if ctx is None else ctx.n_data


def pad_rows(n: int, multiple: Optional[int] = None) -> int:
    """Rows padded up so the batch axis divides the data-axis size. Padded
    slots carry mask=0 so every masked statistic ignores them."""
    if multiple is None:
        multiple = num_data_shards()
    return int(math.ceil(n / multiple) * multiple) if multiple > 1 else n


def _already_placed(arr, sharding) -> bool:
    """True when ``arr`` is a jax array ALREADY carrying a sharding
    equivalent to the target — the round-14 "pre-partitioned operands"
    contract: a device frame placed rows-on-"data" at first touch flows
    into the sweep with no resharding device_put (and therefore no
    resharding collectives on a real mesh)."""
    s = getattr(arr, "sharding", None)
    if s is None:
        return False
    try:
        same = s.is_equivalent_to(sharding, getattr(arr, "ndim", 1))
    except Exception:  # failure-ok: version-dependent API; fall back to ==
        same = s == sharding
    if same:
        from transmogrifai_tpu.utils.profiling import ingest_counters
        ingest_counters.presharded_skips += 1
    return bool(same)


def shard_rows(arr: jax.Array) -> jax.Array:
    """Place an array with its leading (row) axis sharded over the mesh.
    No-op without an active mesh, and a counted no-op when the array
    already carries the target sharding (``_already_placed``)."""
    ctx = current_mesh()
    if ctx is None:
        return arr
    spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
    sharding = NamedSharding(ctx.mesh, spec)
    if _already_placed(arr, sharding):
        return arr
    return jax.device_put(arr, sharding)


def pad_and_shard_rows(arr, pad_value=0.0):
    """Pad the row axis up to a multiple of the data-axis size, then shard.

    The device_put row-sharding path requires the leading dim to divide the
    mesh; padded slots are poisoned with ``pad_value`` (callers pair this
    with a zeroed mask/weight so every masked statistic ignores them).
    Accepts numpy or jax arrays; pads on host before transfer. No-op
    without an active mesh.
    """
    ctx = current_mesh()
    if ctx is None:
        return arr
    n = int(arr.shape[0])
    n_pad = pad_rows(n, ctx.n_data)
    if n_pad != n:
        width = [(0, n_pad - n)] + [(0, 0)] * (arr.ndim - 1)
        if isinstance(arr, np.ndarray):
            arr = np.pad(arr, width, constant_values=pad_value)
        else:
            import jax.numpy as jnp
            arr = jnp.pad(arr, width, constant_values=pad_value)
    return shard_rows(arr)


def fold_axis_on_model(k: int) -> bool:
    """True when a stacked fold axis of size ``k`` rides the mesh "model"
    axis (it must divide it evenly). The ModelSelector's fold-stacked sweep
    uses this to pick which of its two candidate-parallel axes (fold vs
    grid) the "model" axis shards: folds win when they divide; otherwise the
    grid scalars take the axis (``_shard_candidates``) and folds replicate."""
    ctx = current_mesh()
    return ctx is not None and ctx.n_model > 1 and k % ctx.n_model == 0


def shard_stacked_training_rows(X, y, w):
    """Fold-stacked ([k, n, ...]) analog of ``shard_training_rows``: the
    ROW axis (axis 1) pads to the data-axis multiple with weight 0 and
    shards over "data"; the leading FOLD axis shards over "model" when it
    divides that axis (``fold_axis_on_model``), else replicates. This is
    the 2-D placement of the ModelSelector's (fold x grid) work units:
    rows over "data", fold/grid candidates over "model" (SURVEY §2.7
    P1 + P3 combined). ``X`` may be float features (the linear families'
    stacked batch) or integer bin codes (the fold x grid-stacked tree
    sweep's int8 code gather) — padding is dtype-preserving and padded
    slots carry weight 0, so every weighted statistic ignores them.
    No-op without an active mesh."""
    ctx = current_mesh()
    if ctx is None:
        return X, y, w
    import jax.numpy as jnp
    k = int(X.shape[0])
    n = int(X.shape[1])
    n_pad = pad_rows(n, ctx.n_data)

    def pad1(a, val):
        if n_pad == n:
            return a
        width = [(0, 0), (0, n_pad - n)] + [(0, 0)] * (a.ndim - 2)
        if isinstance(a, np.ndarray):
            return np.pad(a, width,
                          constant_values=np.asarray(val, a.dtype))
        return jnp.pad(a, width,
                       constant_values=jnp.asarray(val, a.dtype))

    fold_ax = MODEL_AXIS if fold_axis_on_model(k) else None

    def put(a):
        spec = P(fold_ax, DATA_AXIS, *([None] * (a.ndim - 2)))
        sharding = NamedSharding(ctx.mesh, spec)
        if _already_placed(a, sharding):
            return a
        return jax.device_put(a, sharding)

    return (put(pad1(X, 0.0)), put(pad1(y, 0.0)), put(pad1(w, 0.0)))


def shard_training_rows(X, y, w):
    """Distribute one (features, label, weight) training set over the mesh:
    rows padded to the data-axis multiple with weight 0, so every weighted
    trainer (`fit_arrays(X, y, w, ...)`) computes identical results sharded
    or not. No-op without an active mesh. This is the seam that makes the
    ModelSelector sweep row-parallel (reference P1 pervasiveness:
    FitStagesUtil.scala:96-119 — every fit is distributed)."""
    ctx = current_mesh()
    if ctx is None:
        return X, y, w
    return (pad_and_shard_rows(X), pad_and_shard_rows(y),
            pad_and_shard_rows(w, pad_value=0.0))
