"""Scatter-add (node, feature, bin) gradient/hessian histograms.

The tree learner's per-level op in its GSPMD-safe form: one flat-index
scatter-add over the binned matrix. Under a mesh the scatter runs per
shard and XLA inserts the psum (the analog of XGBoost's Rabit
all-reduce / Spark MLlib's executor histogram aggregation, SURVEY §2.7
P5). On a single chip at large row counts the sorted MXU engine in
``models/trees._grow_tree_sorted`` replaces it — host-fenced chip
measurements put this scatter at ~24 ms per stat per 100k x 28 x 64
(~0.9 GB/s, serialized) versus ~80 ms per LEVEL for the sorted block
contraction at 1M rows.

Batched shape (round 8, the fold x grid-stacked tree sweep): the public
function carries a ``jax.custom_batching.custom_vmap`` rule that FOLDS
every vmapped axis into the node axis — a [B]-batched call lowers to ONE
flat-index scatter over ``B * n_nodes`` logical nodes instead of a
B-times-serialized batched scatter. The fold/lane/class vmaps of the
stacked tree trainer compose: each level folds again, so the whole
(k folds x L lanes x n_out classes) batch is still a single scatter per
level. (The sorted engine needs no such rule: its one-hot contraction is
a batched einsum whose extra axes feed the MXU batch dims directly.)
The rule changes only the lowering, not the math — per batch slice the
update order is row order either way, so results are bit-identical to
the unbatched call.

History: an earlier Pallas compare+matmul kernel lived beside this
(``ops/histogram_pallas.py``, rounds 1-4) for levels with <= 8 nodes.
Its justifying on-chip numbers turned out to be enqueue-time artifacts
(unfenced walls); re-measured with fences its niche
(sub-ms shallow levels of the small-fit path) was irrelevant. Deleted in
round 5: benchmark-or-delete, resolved by deletion with data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["node_bin_histogram_xla"]


@functools.lru_cache(maxsize=None)
def _hist_fn(n_nodes: int, n_bins: int):
    """The (n_nodes, n_bins)-specialized scatter histogram with its
    batch-folding vmap rule. Cached so the custom_vmap wrapper (and its
    jit traces) are built once per static shape."""
    from jax.custom_batching import custom_vmap

    @custom_vmap
    def hist(Xb, node, grad, hess):
        n, d = Xb.shape
        flat = ((node[:, None] * d + jnp.arange(d)[None, :]) * n_bins
                + Xb).reshape(-1)
        seg = n_nodes * d * n_bins
        hg = jnp.zeros(seg, jnp.float32).at[flat].add(
            jnp.broadcast_to(grad[:, None], (n, d)).reshape(-1))
        hh = jnp.zeros(seg, jnp.float32).at[flat].add(
            jnp.broadcast_to(hess[:, None], (n, d)).reshape(-1))
        return (hg.reshape(n_nodes, d, n_bins),
                hh.reshape(n_nodes, d, n_bins))

    @hist.def_vmap
    def _batched(axis_size, in_batched, Xb, node, grad, hess):
        # fold the vmapped axis into the node axis: one flat scatter over
        # axis_size * n_nodes logical nodes. Unbatched operands (e.g. the
        # shared bin codes under the stacked sweep's lane vmap) broadcast
        # — XLA fuses the broadcast into the scatter's index computation.
        bsz = axis_size

        def bc(a, was_batched):
            return a if was_batched else jnp.broadcast_to(
                a, (bsz,) + a.shape)

        Xb2 = bc(Xb, in_batched[0])
        node2 = bc(node, in_batched[1])
        g2 = bc(grad, in_batched[2])
        h2 = bc(hess, in_batched[3])
        n, d = Xb2.shape[1], Xb2.shape[2]
        off = (jnp.arange(bsz, dtype=node2.dtype) * n_nodes)[:, None]
        hg, hh = _hist_fn(bsz * n_nodes, n_bins)(
            Xb2.reshape(bsz * n, d), (node2 + off).reshape(-1),
            g2.reshape(-1), h2.reshape(-1))
        return (hg.reshape(bsz, n_nodes, d, n_bins),
                hh.reshape(bsz, n_nodes, d, n_bins)), (True, True)

    return hist


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def node_bin_histogram_xla(Xb, node, grad, hess, *, n_nodes: int,
                           n_bins: int):
    """[n_nodes, d, B] grad and hess histograms via flat-index scatter.

    Xb: [n, d] integer bin codes in [0, B) (int8 codes promote in the
    flat-index arithmetic); node: [n] int32 in [0, n_nodes); grad/hess:
    [n] f32 (row weights already applied). Safe under ``vmap`` at any
    nesting depth: the batch axes fold into the node axis (module
    docstring) so the lowering stays one scatter.
    """
    return _hist_fn(int(n_nodes), int(n_bins))(Xb, node, grad, hess)
