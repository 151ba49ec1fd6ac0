"""Monoid-pytree reductions over the mesh — the statistics comm backend.

The reference computes every distributed statistic as an algebird monoid
reduced via Spark ``reduce``/``reduceByKey``/``treeAggregate`` (SURVEY §2.7
P2: RawFeatureFilter summaries, SmartTextVectorizer TextStats, SanityChecker
contingency). Here the same algebra runs as:

- inside ``shard_map``: ``tree_psum(stats, axis="data")`` — XLA all-reduce
  over ICI, one collective per fused stats program;
- at host level (multi-process): ``jax.experimental.multihost_utils`` style
  all-gather is unnecessary because stats arrays are device-resident and
  jit output shardings already materialize the reduced value replicated.

A "monoid" here is any pytree of arrays whose combine is elementwise ``+``
(sums, counts, histograms, contingency tables) — min/max/moment variants
provide their own combine.
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from transmogrifai_tpu.parallel.mesh import DATA_AXIS, MeshContext

__all__ = ["tree_psum", "tree_pmax", "tree_pmin", "mesh_reduce_stats",
           "reduce_host_metrics", "CollectiveTimeoutError",
           "run_with_deadline", "collective_timeout_s"]


class CollectiveTimeoutError(RuntimeError):
    """A multihost collective/barrier exceeded its deadline. One dead or
    partitioned host makes every OTHER host block inside the collective
    forever — this error converts the silent pod-wide hang into a fast,
    per-host-attributed failure an orchestrator can act on (restart the
    pod, resume from checkpoints). Carries ``DEADLINE_EXCEEDED`` in the
    message so retry classification treats it as transient infrastructure.
    """


def collective_timeout_s(timeout_s: Optional[float] = None) -> float:
    """Effective collective deadline: the explicit argument, else
    ``TRANSMOGRIFAI_COLLECTIVE_TIMEOUT_S`` (default 600). ``0`` disables
    the guard (legacy block-forever behavior)."""
    if timeout_s is not None:
        return float(timeout_s)
    from transmogrifai_tpu.utils.retry import _env_float
    return _env_float("TRANSMOGRIFAI_COLLECTIVE_TIMEOUT_S", 600.0)


def _host_diagnostics() -> str:
    try:
        return (f"host {jax.process_index()}/{jax.process_count()}, "
                f"{len(jax.local_devices())} local device(s), "
                f"backend={jax.default_backend()}")
    except Exception:  # failure-ok: diagnostics must never mask the timeout
        return "host ?/? (jax backend unavailable)"


def run_with_deadline(fn: Callable[[], Any], *, name: str,
                      timeout_s: Optional[float] = None) -> Any:
    """Run a blocking collective with a deadline: ``fn()`` executes on a
    worker thread; if it has not returned within the timeout, raise
    :class:`CollectiveTimeoutError` naming the collective and this host
    instead of hanging the pod. The abandoned thread is daemonic — the
    expected reaction to a timeout is tearing the process down and
    resuming from checkpoints, exactly what resumable training enables.

    Deliberately guarded even single-process: barrier/shard_global_rows
    are rare, per-phase calls whose bounded-wait contract must hold (and
    be chaos-testable) everywhere; only the per-stats-call hot path
    (``mesh_reduce_stats``) skips the guard when no peer can be dead."""
    timeout = collective_timeout_s(timeout_s)
    if timeout <= 0:
        return fn()
    from transmogrifai_tpu.utils import devicewatch
    box: dict[str, Any] = {}
    done = threading.Event()

    def work() -> None:
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — reraised on the caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=work, daemon=True,
                         name=f"collective[{name}]")
    t0 = time.time()
    eid = devicewatch.dispatch_ledger.register("collective", name=name,
                                               timeoutSeconds=timeout)
    try:
        t.start()
        if not done.wait(timeout):
            # freeze the device-execution autopsy BEFORE raising: the
            # abandoned worker thread's stack (blocked inside the
            # collective), the in-flight dispatch inventory, and the HBM
            # census are exactly the evidence a pod-hang postmortem
            # needs. Gated like every observatory seam — a disabled
            # watchdog (TRANSMOGRIFAI_DEVICEWATCH=0) restores the
            # pre-observatory timeout byte for byte
            if devicewatch.watchdog.enabled:
                try:
                    devicewatch.stall_autopsy(
                        f"collective.timeout:{name}", site="collective",
                        wait={"name": name, "site": "collective",
                              "timeoutS": timeout, "t0": t0,
                              "thread": t.name})
                except Exception as e:  # noqa: BLE001 — diagnostics must never mask the timeout
                    warnings.warn(
                        f"collective-timeout autopsy failed "
                        f"({type(e).__name__}: {e})", RuntimeWarning)
            raise CollectiveTimeoutError(
                f"DEADLINE_EXCEEDED: collective {name!r} timed out after "
                f"{timeout:g}s on {_host_diagnostics()} — a peer host is "
                "likely dead or partitioned; restart the job and resume "
                "from checkpoints (docs/ROBUSTNESS.md)")
    finally:
        devicewatch.dispatch_ledger.complete(eid)
    if "error" in box:
        raise box["error"]
    return box["value"]


def tree_psum(tree: Any, axis: str = DATA_AXIS) -> Any:
    """All-reduce-sum every leaf across a mesh axis (use under shard_map)."""
    return jax.tree_util.tree_map(lambda x: jax.lax.psum(x, axis), tree)


def tree_pmax(tree: Any, axis: str = DATA_AXIS) -> Any:
    return jax.tree_util.tree_map(lambda x: jax.lax.pmax(x, axis), tree)


def tree_pmin(tree: Any, axis: str = DATA_AXIS) -> Any:
    return jax.tree_util.tree_map(lambda x: jax.lax.pmin(x, axis), tree)


def mesh_reduce_stats(ctx: MeshContext,
                      local_stats_fn: Callable[..., Any],
                      *row_sharded_args: jax.Array,
                      reduce: Callable[[Any], Any] | None = None,
                      timeout_s: Optional[float] = None) -> Any:
    """Run a per-shard statistics function over row-sharded inputs and
    all-reduce the resulting monoid pytree across the data axis.

    ``local_stats_fn(*shard_args) -> stats pytree`` sees only its shard of the
    rows (masked rows contribute identity). The result is replicated.
    This is the direct analog of the reference's
    ``rdd.map(prepare).reduce(monoid.plus)``.

    ``reduce`` combines the per-shard pytrees (default ``tree_psum``); pass a
    custom combiner for non-additive monoids, e.g. one that psums sums but
    pmins/pmaxes extrema — it runs inside shard_map with the data axis bound.

    Multihost, the all-reduce rides DCN and a dead peer host blocks it
    forever: the dispatch + materialization runs under a deadline
    (``timeout_s``, default env ``TRANSMOGRIFAI_COLLECTIVE_TIMEOUT_S``)
    and raises :class:`CollectiveTimeoutError` with per-host diagnostics
    instead of hanging the pod. Single-process meshes skip the guard — no
    peer can be dead, and stats calls stay thread-free on the hot path.
    """
    combine = reduce if reduce is not None else tree_psum
    in_specs = tuple(
        P(DATA_AXIS, *([None] * (a.ndim - 1))) for a in row_sharded_args)

    def shard_fn(*args):
        return combine(local_stats_fn(*args))

    fn = jax.shard_map(shard_fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=P())
    if jax.process_count() <= 1:
        return fn(*row_sharded_args)
    # block inside the deadline: jit dispatch is async, so only a
    # block_until_ready surfaces a cross-host hang at this seam
    return run_with_deadline(
        lambda: jax.block_until_ready(fn(*row_sharded_args)),
        name="mesh_reduce_stats", timeout_s=timeout_s)


def reduce_host_metrics(ctx: MeshContext, values: dict[str, float],
                        timeout_s: Optional[float] = None
                        ) -> dict[str, float]:
    """Sum a host-local ``{name: value}`` metrics mapping across every
    host of the mesh — the observability reduction behind one-run-summary
    multihost metrics (``utils.profiling.aggregate_across_hosts``).

    Every host MUST call this with the same sorted key set (phase/stage
    names come from the same program on every host, so they do) — the
    values pack into one vector, each host spreads its vector over its
    local rows of the data axis, and the same deadline-guarded
    ``mesh_reduce_stats`` all-reduce that serves training statistics sums
    them. Single-process meshes reduce locally (identity sum) with no
    deadline thread, like every other collective here.
    """
    import numpy as np

    names = sorted(values)
    if not names:
        return {}
    n_proc = jax.process_count()
    axis = ctx.mesh.shape[DATA_AXIS]
    rows_local = max(axis // max(n_proc, 1), 1)
    v = jnp.asarray([float(values[n]) for n in names], jnp.float32)
    # spread this host's vector over its local rows so the data-axis psum
    # equals the straight sum over hosts
    block = jnp.tile(v / rows_local, (rows_local, 1))
    if n_proc > 1:
        from jax.sharding import NamedSharding
        arr = jax.make_array_from_process_local_data(
            NamedSharding(ctx.mesh, P(DATA_AXIS)), np.asarray(block))
    else:
        arr = block
    out = mesh_reduce_stats(ctx, lambda rows: jnp.sum(rows, axis=0), arr,
                            timeout_s=timeout_s)
    out = np.asarray(out, np.float64)
    return {n: float(out[i]) for i, n in enumerate(names)}
