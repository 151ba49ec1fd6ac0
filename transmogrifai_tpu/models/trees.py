"""Histogram-based tree ensembles: GBT / XGBoost-parity boosting + random
forests + single decision trees — pure JAX, TPU-native.

Parity targets: reference ``OpXGBoostClassifier/Regressor`` (xgboost4j JNI ->
native libxgboost histogram boosting), ``OpGBTClassifier/Regressor``,
``OpRandomForestClassifier/Regressor``, ``OpDecisionTreeClassifier/Regressor``
(Spark MLlib executor-distributed histogram trees). This module replaces both
native engines with one device-resident histogram learner (SURVEY §2.7 P5):

- features quantile-bin once into int32 codes (``max_bins``, default 64)
- each tree level builds ALL (node, feature, bin) gradient/hessian
  histograms with one of two engines (``hist=``): the GSPMD-safe
  scatter-add over the row-sharded binned matrix (the analog of XGBoost's
  Rabit all-reduced per-worker histograms; under a mesh the scatter runs
  per shard and the histogram psum rides ICI) or — the single-chip hot
  path — the SORTED engine: rows kept grouped by node across levels,
  node segments padded to block multiples, and the whole level computed
  as blocked one-hot MXU contractions whose cost is independent of the
  node count (host-fenced on chip in round 5: 5-7x faster per tree at
  1M rows; not re-measured)
- split choice is the XGBoost gain formula (lambda/gamma/min_child_weight)
  via cumulative sums along the bin axis; the whole ensemble trains inside
  one ``lax.scan`` jitted program (boosting) or a scanned loop of
  independent bootstrapped trees (forest)
- the CV sweep stacks further (round 8): ``train_score_stacked`` vmaps
  the grower over a leading (fold x grid-lane) batch — one compiled
  program trains and scores a whole depth-group of the ModelSelector's
  k-fold x hyperparameter sweep, per-lane scalars riding as batched
  operands and the scatter histograms folding every batch axis into the
  node axis (``ops/histograms.py``'s custom_vmap rule)
- trees are fixed-shape: a non-splitting node stores feature -1 and routes
  rows left, so depth-d trees are dense arrays and prediction is d table
  lookups a row; on a TPU the scoring path (``bin_data``, ``predict_tree``)
  makes a lookup in a small table by comparing against the whole table,
  never by a per-element gather (``_compares_all``).

Random forests grow CART-style regression trees on bootstrap (Poisson)
weights with per-tree feature subsampling; for classification the leaf holds
the class-probability estimate (variance-reduction splits ~ gini for binary).
On the sorted engine a forest round carries only the rows its bootstrap drew
(``drawn_rows``, ``forest_rows_carried``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.models.base import PredictionModel, Predictor
from transmogrifai_tpu.utils.tracing import device_scope

__all__ = [
    "OpGBTClassifier", "OpGBTRegressor",
    "OpXGBoostClassifier", "OpXGBoostRegressor",
    "OpRandomForestClassifier", "OpRandomForestRegressor",
    "OpDecisionTreeClassifier", "OpDecisionTreeRegressor",
    "TreeEnsembleModel",
]


# ---------------------------------------------------------------------------
# binning
# ---------------------------------------------------------------------------

#: rows used for quantile-edge estimation; above this the percentiles run on
#: a deterministic subsample (XGBoost's approx-sketch analog — edge jitter of
#: O(1/sqrt(sample)) is far below bin width at 64 bins)
_EDGE_SAMPLE_CAP = 2_000_000


def quantile_bin_edges(X: np.ndarray, max_bins: int,
                       seed: int = 0) -> np.ndarray:
    """[d, max_bins-1] quantile edges per feature (host, once per fit)."""
    if X.shape[0] > _EDGE_SAMPLE_CAP:
        idx = np.random.default_rng(seed).choice(
            X.shape[0], size=_EDGE_SAMPLE_CAP, replace=False)
        X = X[np.sort(idx)]
    qs = np.linspace(0, 100, max_bins + 1)[1:-1]
    edges = np.percentile(X, qs, axis=0).T  # [d, B-1]
    return np.ascontiguousarray(edges, dtype=np.float32)


@functools.partial(jax.jit, static_argnames=("max_bins",))
def quantile_bin_edges_device(X, *, max_bins: int):
    """[d, max_bins-1] quantile edges computed ON DEVICE (one jitted sort
    per fit). The host path pulls the full X matrix over the host<->device
    link first — at 1M x 28 that is ~100MB over PCIe per grid
    point; this keeps the whole binning pass device-resident."""
    qs = jnp.linspace(0.0, 1.0, max_bins + 1)[1:-1]
    return jnp.quantile(X, qs, axis=0).T.astype(jnp.float32)


#: the shapes up to which a lookup in a small table is made by comparing
#: against the WHOLE table (``_compares_all``): edges a feature (past it,
#: imported boosters with thousands of thresholds a feature, the binary
#: search's ``log2`` serial lookups are cheaper), the width of the frame
#: whose rows pick their split feature's code out of the ``d`` codes they
#: hold (``O(d)`` a row and level), and the length of a per-level table of
#: split features, bins or leaves (``O(N)`` a row: depth 12)
_COUNT_MAX_EDGES = 4096
_SELECT_MAX_WIDTH = 256
_SELECT_MAX_NODES = 4096
#: the TPU's lane width: the row tile a walk that compares against whole
#: tables scores (``predict_tree``), and the narrowest block
#: ``_level_block`` lays out where a node's mean rows allow one of 32
#: slots or more
_LANES = 128


def _compares_all(size: int, cap: int) -> bool:
    """THE choice of how the scoring path looks a value up in a table of
    ``size`` entries: True = by comparison against all of them (dense VPU
    code that fuses into its reduction), False = by a per-element gather.
    XLA:TPU runs such a gather nearly serially, about 10 ns an element
    whatever the table holds, so on a TPU everything up to ``cap`` is
    compared; XLA:CPU gathers fast and MATERIALISES the ``[size, rows]``
    comparison (cpu, PR 30: 304 MB and 215 ms against 3 ms for 18 depth-12
    trees over 1,000 rows), so off the TPU nothing is. The compared form
    wants its rows in whole tiles of ``_LANES``: over a row count that is
    not, XLA:TPU lays every table of 128 entries or more with the TABLE
    axis on the lanes and each row's sum becomes a reduction across them,
    so ``predict_tree`` pads its rows (v5e: 4,467 ms against 253 ms for
    ``covtype_multi``'s depth-12 validation walk at 174,303 and 174,336
    rows)."""
    return size <= cap and jax.default_backend() == "tpu"


@jax.jit
def bin_data(X, edges):
    """Bin values: [n, d] int32 in [0, B-1], ``searchsorted(edges[f], x,
    side="left")`` for every (row, feature).

    On a TPU a row's code is the COUNT of its feature's edges below its
    value (``method="compare_all"``: jax's total-order comparison, so ties,
    values equal to an edge, ``-0.0``, infinities and NaN get the binary
    search's answers): ``B - 1`` compares fused into their sum, where the
    binary search is ``log2 B`` serial per-element gathers from the edge
    table (58 ns an element at 63 edges; PERF.md section 6, PR 30)."""
    method = ("compare_all" if _compares_all(edges.shape[1], _COUNT_MAX_EDGES)
              else "scan")

    def per_feature(x_col, e_col):
        return jnp.searchsorted(e_col, x_col, side="left", method=method)

    with device_scope("tree.bin"):
        return jax.vmap(per_feature, in_axes=(1, 1), out_axes=1)(
            X, edges.T.astype(X.dtype)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# single-tree growth (one jitted program per (n, d, depth, B) shape)
# ---------------------------------------------------------------------------

def _hist_engine(n_rows: int, n_devices: int = 1, *,
                 stacked: bool = False) -> str:
    """THE histogram-engine choice, for a fit of ``n_rows`` rows held on
    ``n_devices`` devices (``stacked``: the unit is the selector's vmapped
    fold x lane batch): ``"scatter"``, ``"sorted"`` or ``"sorted_sharded"``.

    The sorted MXU engine is for large fits on a TPU (round-5 on-chip
    shootout: ~7x/level at 1M rows; it trades ~B-times more, MXU-friendly,
    FLOPs for the serialized scatter, a trade only measured there) — a
    single-device input directly, a row-sharded one through the explicit
    shard_map wrapper (``train_ensemble_sharded``), which needs an active
    mesh and a row count its data axis divides (what
    ``shard_training_rows`` produces). Everything else keeps the scatter
    engine: small fits, CPU/GPU, sharded inputs the wrapper cannot take
    (GSPMD all-reduces the per-shard scatters; the sorted engine's
    global-index bookkeeping would generate heavy cross-shard collectives
    under plain GSPMD), and the stacked batch under a mesh, which cannot
    ride the per-family ``shard_map`` at all."""
    if n_rows < _SORT_MIN_ROWS or jax.default_backend() != "tpu":
        return "scatter"
    from transmogrifai_tpu.parallel.mesh import current_mesh
    ctx = current_mesh()
    if stacked:
        return "scatter" if ctx is not None else "sorted"
    if n_devices == 1:
        return "sorted"
    if ctx is None or n_rows % ctx.n_data:
        return "scatter"
    return "sorted_sharded"


#: histogram node budget per materialized array: [nodes, d, B] f32 x2 (g, h).
#: At the default (1024, d=28, B=64) that is ~14 MB; levels with more nodes
#: compute best-splits chunk-by-chunk so HBM stays bounded at any depth.
_MAX_HIST_NODES = 1024

#: sorted-histogram path: rows per MXU contraction block. Host-fenced chip
#: measurements (round 5, 1M x 28 x 64, not re-measured): the scatter-add
#: histogram costs ~540 ms/level (serialized, ~0.9 GB/s) while the sorted
#: block one-hot contraction runs the same level in ~80 ms and its cost is
#: INDEPENDENT of the node count, so deep levels stop needing chunking.
_SORT_BLOCK = 256
#: byte budget for the materialized one-hot chunk ([blocks, C, d, B] bf16)
_SORT_OH_BUDGET = 192 * 1024 * 1024
#: row threshold above which single-device fits switch to the sorted path
#: (below it the scatter path's lower fixed cost wins and stays the
#: well-trodden mesh/GSPMD route)
_SORT_MIN_ROWS = 150_000


def _pow2_at_most(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


#: block width of ``_long_cumsum``'s two-level form
_CUMSUM_BLOCK = 1024


def _long_cumsum(x):
    """Inclusive prefix sum of a long 1-D array in a two-level blocked
    form (within-block cumsum + a cumsum of the block totals). XLA:TPU
    takes 10+ s to COMPILE one flat reduce-window over ~10^5-10^6
    elements (AOT-compiled for v5e: 13 s at 600k rows vs 0.3 s blocked)
    and the sorted grower emits one per tree level and two per tree for
    the leaves — minutes of compile per depth-group. Exact for the int32
    position sums; for f32 the summation order differs from the flat
    form by rounding only."""
    n = x.shape[0]
    if n <= 8 * _CUMSUM_BLOCK:
        return jnp.cumsum(x)
    nb = -(-n // _CUMSUM_BLOCK)
    inner = jnp.cumsum(jnp.pad(x, (0, nb * _CUMSUM_BLOCK - n)).reshape(
        nb, _CUMSUM_BLOCK), axis=1)
    totals = inner[:, -1]
    offsets = jnp.cumsum(totals) - totals
    return (inner + offsets[:, None]).reshape(-1)[:n]


class _SortedLayout(NamedTuple):
    """One level's padded block layout (``_sorted_layout``). ``N`` nodes,
    ``nb`` blocks of ``C`` slots, ``n_pad = nb * C`` slots."""
    bnode: jax.Array      # [nb] the node each block belongs to
    within: jax.Array     # [nb, C] a slot's position in its node's segment
    valid: jax.Array      # [nb, C] the slot holds a row (False: padding)
    src_start: jax.Array  # [nb] sorted-row position of a block's first slot
    pstarts: jax.Array    # [N] first padded slot of each node
    pends: jax.Array      # [N] one past the last padded slot of each node
    pcounts: jax.Array    # [N] padded slots per node (a multiple of C)


def _sorted_layout(counts, n: int, C: int) -> _SortedLayout:
    """Padded block layout for rows grouped by node.

    ``counts``: [N] rows per node (sorted-order segments). Every node's
    segment is padded to a multiple of the block size ``C`` so each
    C-slot block belongs to exactly one node; total padded length is the
    static ``ceil(n/C)*C + N*C``.

    Everything a slot shares with the other slots of its block is looked
    up PER BLOCK (``nb = n_pad / C`` gathers from the [N] node tables:
    ``bnode``, and through it the node's start, count and padded start)
    and reaches the slots as a ``[nb, 1]`` operand of ``[nb, C]``
    arithmetic. Per slot there is only that arithmetic: ``within`` and
    ``valid``. A block's slots map to CONSECUTIVE sorted rows, so the
    layout gives one source position per block (``src_start``), not one
    per slot. Within a node's segment the valid slots come first, and
    ``within >= 0`` always (a block starts inside its own node's
    segment); the trailing blocks past the last node read as invalid.
    """
    N = counts.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    pcounts = ((counts + C - 1) // C) * C
    pends = jnp.cumsum(pcounts)
    pstarts = pends - pcounts
    n_pad = (-(-n // C)) * C + N * C
    nb = n_pad // C
    block_first = jnp.arange(nb, dtype=jnp.int32) * C
    bnode = jnp.clip(jnp.searchsorted(pends, block_first, side="right"),
                     0, N - 1).astype(jnp.int32)
    boff = block_first - pstarts[bnode]
    within = boff[:, None] + jnp.arange(C, dtype=jnp.int32)
    valid = within < counts[bnode][:, None]
    src_start = jnp.clip(starts[bnode] + boff, 0, n)
    return _SortedLayout(bnode, within, valid, src_start, pstarts, pends,
                         pcounts)


def _block_rows(order, src_start, C: int):
    """[nb * C] row ids of the padded slots: per block ONE contiguous
    C-long run of ``order`` starting at ``src_start``. Read per block, not
    per slot: the run lies in two consecutive rows of ``order`` viewed as
    ``[len / C, C]`` (two gathers of ``nb`` rows), and a barrel shifter
    (``log2 C`` static rolls, each selected per block by one bit of
    ``src_start % C``) rotates it to the front. XLA:TPU runs a gather of
    ``nb`` unaligned C-long slices as a loop of ``nb`` dynamic slices
    (1.25 us a block, and as many trace events). ``len(order)`` is a
    multiple of ``C`` with at least ``2 * C`` entries past
    ``src_start``'s largest value."""
    rows = order.reshape(-1, C)
    r, off = src_start // C, src_start % C
    two = jnp.concatenate([rows[r], rows[r + 1]], axis=1)
    for k in range((C - 1).bit_length()):
        two = jnp.where((off >> k & 1)[:, None] == 1,
                        jnp.roll(two, -(1 << k), axis=1), two)
    return two[:, :C].reshape(-1)


def _pack_rows(Xb, grad, hess, n_bins: int):
    """[n, W] int32: all a row carries through the level loop, so that a
    level moves a row in ONE gather. Bin codes below 128 go four int8 to
    a word (``d`` padded to a multiple of 4), wider codes one int32 each;
    ``grad`` and ``hess`` follow as their float32 BITS (bitcast, nothing
    rounds); ``W`` is padded to a multiple of 8, which is what the TPU's
    (8, 128) tiling stores for a narrow second-minor dimension anyway
    (and a [n, 9] row gather compiled 5-9x slower than [n, 8] or
    [n, 16], v5e). ``_unpack_rows`` is the exact inverse."""
    n, d = Xb.shape
    if n_bins <= 127:
        codes = jnp.pad(Xb.astype(jnp.int8), ((0, 0), (0, -d % 4)))
        words = jax.lax.bitcast_convert_type(
            codes.reshape(n, -1, 4), jnp.int32)
    else:
        words = Xb.astype(jnp.int32)
    gh = jax.lax.bitcast_convert_type(
        jnp.stack([grad, hess], axis=1).astype(jnp.float32), jnp.int32)
    packed = jnp.concatenate([words, gh], axis=1)
    return jnp.pad(packed, ((0, 0), (0, -packed.shape[1] % 8)))


def _unpack_rows(rows, d: int, n_bins: int):
    """(codes [m, d] int8 or int32, grad [m], hess [m]) out of ``m``
    gathered rows of ``_pack_rows``'s matrix."""
    if n_bins <= 127:
        n_words = -(-d // 4)
        codes = jax.lax.bitcast_convert_type(
            rows[:, :n_words], jnp.int8).reshape(rows.shape[0], -1)[:, :d]
    else:
        n_words = d
        codes = rows[:, :d]
    gh = jax.lax.bitcast_convert_type(rows[:, n_words:n_words + 2],
                                      jnp.float32)
    return codes, gh[:, 0], gh[:, 1]


#: blocks a group of ``_sorted_hist``'s two-level sum over the block axis: a
#: node's histogram is read off an inclusive prefix WITHIN groups of this
#: many blocks (one ``[G, G]`` triangular product) and the totals of the
#: whole groups between. A power of two. Chosen on the chip (v5e, PR 32; the
#: function alone under the sweep's 3-fold ``vmap``, 480,000 x 28 and 348,600
#: x 54 rows, 64 bins, at the deepest level, 9,548 and 7,495 blocks): 64, 128
#: and 256 stand within 4% of one another (57.8 / 55.7 / 56.3 ms and 81.4 /
#: 83.0 / 84.2 ms a level), so it is the width of the TPU's lanes; a
#: log-step shifted add in place of the product was 1.4 times slower.
_HIST_GROUP = 128


def _sorted_hist(Xp, gp, hp, layout, *, n_bins: int, C: int, acc_dtype):
    """[N, d, B] grad/hess histograms from the padded block layout.

    Per block: a [C, d*B] bin one-hot contracted with the [C, 2] (g, h)
    rows on the MXU (XLA's einsum over ``acc_dtype`` operands with float32
    accumulation; the one-hot is a chunk at a time, and the chunks' partial
    histograms are written straight into the ONE ``[blocks, 2, d, B]``
    array the next step reads). Per node: the sum of its blocks' partials,
    taken in two levels with NO prefix sum over the block axis (on a TPU a
    ``reduce-window`` over thousands of blocks, with copies beside it: 5 ms
    a tree-fold-level, a third of a tree sweep). The block axis, padded
    with empty blocks to whole chunks and whole groups, is viewed as groups
    of ``_HIST_GROUP``; WITHIN a group the inclusive prefix is one product
    with a lower-triangular matrix of ones, ACROSS groups the exclusive
    prefix of the group totals is a second, small one, both at
    ``Precision.HIGHEST`` (the partials are float32 sums and must not round
    to one bfloat16 pass; the ones are exact). A node's histogram is then
    ``prefix[last block] - prefix[block before its first]`` within the
    groups plus the totals of the groups between: 2N rows are gathered, the
    full-length prefix is never formed, and a node inside one group, or
    one that starts a group, subtracts nothing it did not add. A block axis
    no longer than a group is one group. No scatter anywhere, and the work
    is proportional to padded rows, not nodes.
    """
    pstarts, pends, pcounts = layout.pstarts, layout.pends, layout.pcounts
    nb = layout.valid.shape[0]
    d, B = Xp.shape[1], n_bins
    esize = jnp.dtype(acc_dtype).itemsize  # bf16 on TPU, f32 off it
    # blocks a chunk: a power of two whose one-hot fits the budget; the
    # block axis is padded to whole chunks and, past one group, whole groups
    cb = min(_pow2_at_most(_SORT_OH_BUDGET // (esize * d * B) // C),
             1 << (nb - 1).bit_length())
    unit = max(cb, _HIST_GROUP) if nb > _HIST_GROUP else cb
    nbp = -(-nb // unit) * unit
    g = min(_HIST_GROUP, nbp)
    ng = nbp // g
    grow = ((0, nbp - nb), (0, 0), (0, 0))
    Xpb = jnp.pad(Xp.reshape(nb, C, d), grow)
    ghb = jnp.pad(jnp.stack([gp, hp], axis=-1).reshape(nb, C, 2),
                  grow).astype(acc_dtype)
    iota_b = jnp.arange(B, dtype=jnp.int32).astype(Xpb.dtype)

    def add_chunk(i, part):
        xc = jax.lax.dynamic_slice_in_dim(Xpb, i * cb, cb)
        gc = jax.lax.dynamic_slice_in_dim(ghb, i * cb, cb)
        oh = (xc[..., None] == iota_b).astype(acc_dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            part, jnp.einsum("bcs,bcdk->bsdk", gc, oh,
                             preferred_element_type=jnp.float32),
            i * cb, axis=0)

    part = jax.lax.fori_loop(0, nbp // cb, add_chunk,
                             jnp.zeros((nbp, 2, d, B), jnp.float32))
    highest = dict(precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    inner = jnp.einsum("ij,gjsdk->gisdk",
                       jnp.tril(jnp.ones((g, g), jnp.float32)),
                       part.reshape(ng, g, 2, d, B), **highest)
    goff = jnp.einsum("ij,jsdk->isdk",
                      jnp.tril(jnp.ones((ng, ng), jnp.float32), -1),
                      inner[:, -1], **highest)
    inner = inner.reshape(nbp, 2, d, B)
    firstb = (pstarts // C).astype(jnp.int32)
    lastb = jnp.clip(pends // C - 1, 0, nbp - 1)
    # within the groups: a node that starts a group has nothing before it
    lower = jnp.where((firstb % g > 0)[:, None, None, None],
                      inner[jnp.clip(firstb - 1, 0, nbp - 1)], 0.0)
    across = goff[lastb // g] - goff[jnp.clip(firstb // g, 0, ng - 1)]
    hist = jnp.where((pcounts > 0)[:, None, None, None],
                     (inner[lastb] - lower) + across, 0.0)
    return hist[:, 0], hist[:, 1]


def _sorted_partition(counts, layout: _SortedLayout, go_left, src_row,
                      n_order: int):
    """Stable in-segment partition: the next level's ``order`` groups rows
    by ``2*node + go_right`` using ONE long cumsum and one unique-index
    scatter — the incremental analog of re-sorting by node each level.

    ``go_left``: [nb, C]; ``src_row``: [nb * C] row ids of the slots;
    ``n_order``: length of the returned ``order`` (the rows plus the
    spare entries ``_block_rows`` wants). Per slot: the cumsum of the
    left-goers, the destination arithmetic and the scatter. Per block
    (``[nb]`` lookups through ``layout.bnode``): the node's left-goer
    base and its two children's start positions. Per node: the bases and
    the new counts. The right-goers need no cumsum of their own: a
    node's valid slots are the first of its segment, so ``within`` slots
    precede a valid slot in its node, and those that do not go left go
    right.
    """
    bnode, within, valid = layout.bnode, layout.within, layout.valid
    pstarts, pends, pcounts = layout.pstarts, layout.pends, layout.pcounts
    nb, C = valid.shape
    n_pad = nb * C
    N = counts.shape[0]
    glv = (go_left & valid).astype(jnp.int32)
    cl = _long_cumsum(glv.reshape(-1))
    pfirst = jnp.clip(pstarts - 1, 0, n_pad - 1)
    plast = jnp.clip(pends - 1, 0, n_pad - 1)
    base_l = jnp.where(pstarts > 0, cl[pfirst], 0)
    nl = jnp.where(pcounts > 0, cl[plast] - base_l, 0)
    new_counts = jnp.stack([nl, counts - nl], axis=1).reshape(2 * N)
    new_ends = jnp.cumsum(new_counts)
    new_starts = (new_ends - new_counts).reshape(N, 2)[bnode]
    pl = cl.reshape(nb, C) - glv - base_l[bnode][:, None]
    dest = jnp.where(go_left, new_starts[:, :1] + pl,
                     new_starts[:, 1:] + (within - pl))
    # invalid slots get DISTINCT out-of-range sentinels so the
    # unique_indices promise stays true even for dropped updates
    dest = jnp.where(valid, dest, n_order + jnp.arange(
        n_pad, dtype=jnp.int32).reshape(nb, C))
    new_order = jnp.zeros(n_order, jnp.int32).at[dest.reshape(-1)].set(
        src_row, mode="drop", unique_indices=True)
    return new_order, new_counts


def _segment_sums(vals_sorted, counts):
    """[N] per-segment sums of an [n] array laid out in segment order,
    via one cumsum + boundary diffs (no scatter)."""
    n = vals_sorted.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    c = _long_cumsum(vals_sorted)
    upper = c[jnp.clip(ends - 1, 0, max(n - 1, 0))]
    lower = jnp.where(starts > 0, c[jnp.clip(starts - 1, 0, max(n - 1, 0))],
                      0.0)
    return jnp.where(counts > 0, upper - lower, 0.0)


def _level_block(n: int, N: int, block: int) -> int:
    """Slots a block of a level with ``N`` nodes over ``n`` rows: a power
    of two no wider than ``block`` and about half a node's mean rows (a
    node's segment is padded to whole blocks, ``N * C`` slots a level),
    but never 32 or 64: a block narrower than the TPU's 128 lanes costs
    ``_sorted_hist`` four to five times a slot what a full one does, more
    than its padding saves. ``n`` is the rows the grower is given (a
    forest's drawn rows), not the table's. Chosen on the chip (v5e, PR 34;
    one depth-12 forest tree over ``covtype_multi``'s 229,888 drawn rows of
    348,606 x 54 under the sweep's 3-fold ``vmap``, its validation scoring
    included; ms, ``hist`` alone in brackets). The rule before, half the
    mean rows down to 8: by the drawn rows 550.7 a tree, level 10 in
    blocks of 64 56.44 (45.17), level 11 in blocks of 32 72.95 (56.72); by
    the table's rows 533.1, level 10 in blocks of 128 27.99 (13.87), level
    11 in blocks of 64 74.72 (58.47), level 9 in blocks of 256 21.55
    against 18.64 in blocks of 128. This rule: 491.4 a tree, level 11 in
    blocks of 128 42.82 (22.14). ``hist`` costs 24 to 45 ns a slot at 128
    and 256, 153 at 64, 192 at 32; all rows under the old rule 663.4."""
    C = min(block, _pow2_at_most(max(n // (2 * N), 8)))
    return _LANES if _LANES // 4 <= C < _LANES else C


def _grow_tree_sorted(Xb, grad, hess, feat_mask, *, max_depth: int,
                      n_bins: int, reg_lambda, gamma, min_child_weight,
                      block: int = _SORT_BLOCK, data_axis=None):
    """Sort-based level-wise histogram tree (single-shard hot path).

    Same contract as the scatter-path ``grow_tree`` body: returns
    (feats, bins, leaf_values, feat_gain, row_pred). Maintains ``order``
    (row ids grouped by node) and per-node ``counts`` across levels. A
    level does PER SLOT only what differs per slot: one gather of the
    packed rows (codes, grad and hess in one int32 matrix, built once a
    tree) into the padded block layout, the MXU one-hot contraction for
    ALL (node, feature, bin) histograms, one long cumsum and one
    unique-index scatter for the stable partition. PER BLOCK there is the
    block's partial histogram, and a node's is summed from its blocks' by
    two triangular products over groups of blocks (``_sorted_hist``): no
    prefix sum runs over the block axis. Whatever is constant
    over a block of ``C`` slots — the node's layout entries, its split
    feature and bin, its children's positions, the run of ``order`` the
    block reads — is looked up PER BLOCK (``nb = n_pad / C`` entries)
    and broadcast. No scatter-adds and no node-count-dependent chunking.
    """
    n, d = Xb.shape
    B = n_bins
    # the contraction's operand dtype, decided here and nowhere else:
    # bfloat16 one-hot and stats on a TPU (the measured choice; the
    # accumulation is float32 either way), float32 elsewhere (XLA:CPU has
    # no bf16 x bf16 -> f32 dot)
    acc_dtype = (jnp.bfloat16 if jax.default_backend() == "tpu"
                 else jnp.float32)
    split_kw = dict(n_bins=B, reg_lambda=reg_lambda, gamma=gamma,
                    min_child_weight=min_child_weight)
    packed = _pack_rows(Xb, grad, hess, B)
    # every level's C is a power of two that divides ``block``; ``order``
    # is the n row ids and spare entries up to what ``_block_rows`` wants
    block = _pow2_at_most(block)
    n_order = (n // block + 2) * block
    order = jnp.pad(jnp.arange(n, dtype=jnp.int32), (0, n_order - n))
    counts = jnp.full((1,), n, jnp.int32)
    iota_d = jnp.arange(d, dtype=jnp.int32)
    feats_out, bins_out = [], []
    feat_gain = jnp.zeros(d, jnp.float32)
    for level in range(max_depth):
        # the level loop is a Python loop, so every level's phases carry
        # their own scope in the ops' metadata: a device trace splits the
        # grower's time by level and phase (``tree.L<level>/<phase>``)
        N = 2 ** level
        C = _level_block(n, N, block)
        with device_scope(f"tree.L{level}"):
            with device_scope("gather"):
                layout = _sorted_layout(counts, n, C)
                src_row = _block_rows(order, layout.src_start, C)
                Xp, gp, hp = _unpack_rows(packed[src_row], d, B)
                vf = layout.valid.reshape(-1).astype(gp.dtype)
                gp = gp * vf
                hp = hp * vf
            with device_scope("hist"):
                hist_g, hist_h = _sorted_hist(Xp, gp, hp, layout, n_bins=B,
                                              C=C, acc_dtype=acc_dtype)
                if data_axis is not None:
                    # distributed fit (explicit shard_map): per-shard local
                    # histograms all-reduce once per level — the
                    # Rabit/MLlib executor-aggregation analog on ICI —
                    # after which every shard takes identical split
                    # decisions and routes its own rows (order/counts stay
                    # shard-local)
                    hist_g = jax.lax.psum(hist_g, data_axis)
                    hist_h = jax.lax.psum(hist_h, data_axis)
            with device_scope("split"):
                feat, bin_, gain = _best_splits(hist_g, hist_h, feat_mask,
                                                **split_kw)
                feats_out.append(feat)
                bins_out.append(bin_)
                feat_gain = feat_gain.at[jnp.clip(feat, 0)].add(gain)
            with device_scope("partition"):
                # split feature and bin per block; a slot's code of that
                # feature is picked out of the d codes it already holds
                fb = feat[layout.bnode][:, None]
                bb = bin_[layout.bnode][:, None]
                xp = jnp.sum(jnp.where(
                    iota_d == fb[:, :, None],
                    Xp.reshape(-1, C, d).astype(jnp.int32), 0), axis=2)
                go_left = (fb < 0) | (xp <= bb)
                order, counts = _sorted_partition(counts, layout, go_left,
                                                  src_row, n_order)
    order = order[:n]
    with device_scope("tree.leaf"):
        leaf_g = _segment_sums(grad[order], counts)
        leaf_h = _segment_sums(hess[order], counts)
        if data_axis is not None:
            leaf_g = jax.lax.psum(leaf_g, data_axis)
            leaf_h = jax.lax.psum(leaf_h, data_axis)
        leaf_values = -leaf_g / (leaf_h + reg_lambda)
        # per-row predictions from the maintained segment order: leaf value
        # of each sorted row, scattered back to original row ids (unique
        # indices)
        ends = jnp.cumsum(counts)
        snode_final = jnp.searchsorted(ends, jnp.arange(n), side="right"
                                       ).astype(jnp.int32)
        row_pred = jnp.zeros(n, leaf_values.dtype).at[order].set(
            leaf_values[snode_final], unique_indices=True)
    return tuple(feats_out), tuple(bins_out), leaf_values, feat_gain, \
        row_pred


def _best_splits(hist_g, hist_h, feat_mask, *, n_bins, reg_lambda, gamma,
                 min_child_weight):
    """XGBoost gain formula over [nodes, d, B] histograms via bin-axis
    cumsums. Returns per-node (feat, bin): feat -1 / bin B on no-split
    (Xb <= B is always true -> such nodes route every row left)."""
    n_nodes, d, B = hist_g.shape
    GL = jnp.cumsum(hist_g, axis=2)
    HL = jnp.cumsum(hist_h, axis=2)
    G = GL[:, :, -1:]
    H = HL[:, :, -1:]
    GR = G - GL
    HR = H - HL
    gain = 0.5 * (GL ** 2 / (HL + reg_lambda)
                  + GR ** 2 / (HR + reg_lambda)
                  - G ** 2 / (H + reg_lambda)) - gamma
    bad = (HL < min_child_weight) | (HR < min_child_weight)
    gain = jnp.where(bad, -jnp.inf, gain)
    gain = jnp.where(feat_mask[None, :, None] > 0, gain, -jnp.inf)
    # last bin can't split (right side empty by construction)
    gain = gain.at[:, :, B - 1].set(-jnp.inf)
    flat_gain = gain.reshape(n_nodes, d * B)
    best = jnp.argmax(flat_gain, axis=1)
    best_gain = jnp.take_along_axis(flat_gain, best[:, None], axis=1)[:, 0]
    feat = (best // B).astype(jnp.int32)
    bin_ = (best % B).astype(jnp.int32)
    no_split = ~(best_gain > 0.0)
    feat = jnp.where(no_split, -1, feat)
    bin_ = jnp.where(no_split, B, bin_)
    gain_out = jnp.where(no_split, 0.0, best_gain)
    return feat, bin_, gain_out


@functools.partial(jax.jit, static_argnames=("max_depth", "n_bins",
                                             "max_hist_nodes", "hist",
                                             "data_axis"))
def grow_tree(Xb, grad, hess, feat_mask, *, max_depth: int, n_bins: int,
              reg_lambda, gamma, min_child_weight,
              max_hist_nodes: int = _MAX_HIST_NODES, hist: str = "scatter",
              data_axis=None):
    """Level-wise histogram tree. Returns (feats, bins, leaf_values,
    feat_gain, row_pred): feats/bins are tuples of per-level [2^level]
    arrays, leaf_values is [2^max_depth], feat_gain is the [d] per-feature
    split-gain total, and row_pred is each training row's leaf value (so
    boosting loops skip the re-descent). grad/hess already carry row
    weights.

    ``hist`` selects the histogram engine:

    - ``"scatter"`` (default): flat-index scatter-adds — the GSPMD-safe
      path (per-shard scatters + XLA-inserted psum under a mesh) and the
      cheapest at small n.
    - ``"sorted"``: the sort-based MXU path (``_grow_tree_sorted``) —
      ~7x faster per level on the real chip at 1M rows and node-count
      independent; meant for large single-shard fits (the bench path).

    Memory discipline for deep trees (reference RF default depth=12,
    README.md:60-80) on the scatter path: while a level's [nodes, d, B]
    histograms fit ``max_hist_nodes`` they are materialized once and the
    level uses the classic sibling-subtraction trick — only LEFT children
    are scattered, right = parent - left, halving scatter work; deeper
    levels switch to a ``lax.map`` over node chunks that keeps only
    per-node split decisions, so peak HBM stays O(max_hist_nodes * d * B)
    at any depth. The sorted path needs neither trick.
    """
    if hist == "sorted":
        return _grow_tree_sorted(
            Xb, grad, hess, feat_mask, max_depth=max_depth, n_bins=n_bins,
            reg_lambda=reg_lambda, gamma=gamma,
            min_child_weight=min_child_weight, data_axis=data_axis)
    if hist != "scatter":
        raise ValueError(f"hist={hist!r}: expected 'scatter' or 'sorted'")
    if data_axis is not None:
        # the scatter path has no in-body all-reduce: running it under a
        # shard_map with data_axis would silently grow divergent
        # per-shard trees (use GSPMD sharding for scatter instead)
        raise ValueError("data_axis requires hist='sorted'")
    from transmogrifai_tpu.ops.histograms import node_bin_histogram_xla
    n, d = Xb.shape
    B = n_bins
    # node counts are powers of two; round the budget down to one so the
    # chunked levels tile exactly (a non-power-of-two budget would otherwise
    # fail deep inside lax.map with a reshape error)
    max_hist_nodes = 1 << (max(int(max_hist_nodes), 1).bit_length() - 1)
    split_kw = dict(n_bins=B, reg_lambda=reg_lambda, gamma=gamma,
                    min_child_weight=min_child_weight)

    def hist_of(node_ids, g, h, n_nodes):
        return node_bin_histogram_xla(Xb, node_ids, g, h,
                                      n_nodes=n_nodes, n_bins=B)

    node = jnp.zeros(n, dtype=jnp.int32)
    rows = jnp.arange(n)
    feats_out, bins_out = [], []
    feat_gain = jnp.zeros(d, jnp.float32)  # per-feature split-gain totals
    prev_hist = None  # previous level's full (g, h) histograms, if kept
    for level in range(max_depth):
        n_nodes = 2 ** level
        scope = f"tree.L{level}"  # same scopes as the sorted engine
        if n_nodes <= max_hist_nodes:
            with device_scope(f"{scope}/hist"):
                if prev_hist is None:
                    hist_g, hist_h = hist_of(node, grad, hess, n_nodes)
                else:
                    # sibling subtraction: scatter left children (even node
                    # ids) under their PARENT index; right = parent - left
                    is_left = (node % 2 == 0).astype(grad.dtype)
                    half = n_nodes // 2
                    lg, lh = hist_of(node // 2, grad * is_left,
                                     hess * is_left, half)
                    pg, ph = prev_hist
                    hist_g = jnp.stack([lg, pg - lg], axis=1).reshape(
                        n_nodes, d, B)
                    hist_h = jnp.stack([lh, ph - lh], axis=1).reshape(
                        n_nodes, d, B)
            prev_hist = (hist_g, hist_h)
            with device_scope(f"{scope}/split"):
                feat, bin_, gain = _best_splits(hist_g, hist_h, feat_mask,
                                                **split_kw)
        else:
            # node-chunked: histogram + split per chunk, O(chunk*d*B) memory
            prev_hist = None
            n_chunks = n_nodes // max_hist_nodes

            def chunk_splits(c):
                with device_scope(f"{scope}/hist"):
                    base = c * max_hist_nodes
                    in_chunk = ((node >= base)
                                & (node < base + max_hist_nodes))
                    mask = in_chunk.astype(grad.dtype)
                    local = jnp.where(in_chunk, node - base, 0
                                      ).astype(jnp.int32)
                    hg, hh = hist_of(local, grad * mask, hess * mask,
                                     max_hist_nodes)
                with device_scope(f"{scope}/split"):
                    return _best_splits(hg, hh, feat_mask, **split_kw)

            feat_c, bin_c, gain_c = jax.lax.map(chunk_splits,
                                                jnp.arange(n_chunks))
            feat = feat_c.reshape(n_nodes)
            bin_ = bin_c.reshape(n_nodes)
            gain = gain_c.reshape(n_nodes)
        feats_out.append(feat)
        bins_out.append(bin_)
        # gain-based importances (reference ModelInsights extracts real
        # gain importances from the boosters): accumulate each realized
        # split's gain under its feature; clip(-1 -> 0) is safe because
        # no-split nodes carry gain 0
        feat_gain = feat_gain.at[jnp.clip(feat, 0)].add(gain)
        with device_scope(f"{scope}/partition"):
            f_row = feat[node]
            b_row = bin_[node]
            x_row = Xb[rows, jnp.clip(f_row, 0)]
            go_left = jnp.where(f_row < 0, True, x_row <= b_row)
            node = node * 2 + jnp.where(go_left, 0, 1).astype(jnp.int32)
    with device_scope("tree.leaf"):
        # leaf values from accumulated grad/hess at the final nodes
        n_leaves = 2 ** max_depth
        leaf_g = jnp.zeros(n_leaves, jnp.float32).at[node].add(grad)
        leaf_h = jnp.zeros(n_leaves, jnp.float32).at[node].add(hess)
        leaf_values = -leaf_g / (leaf_h + reg_lambda)
        # training-row predictions come free from the final node assignment
        # — the boosting loop must not pay a full re-descent (d more
        # gathers)
        row_pred = leaf_values[node]
    return tuple(feats_out), tuple(bins_out), leaf_values, feat_gain, \
        row_pred


def _select(table, key):
    """``table[key[r]]`` (an ``[N]`` integer table) or ``table[key[r], r]``
    (``[N, n]``: a table a row) for ``[n]`` keys, with no gather: every
    key is compared with all ``N`` indices and the one entry it selects is
    summed out of zeros (exact; a key outside ``[0, N)`` gives 0). The
    compared axis is the MAJOR one, so the rows stay on the TPU's lanes
    and the ``[N, n]`` comparison fuses into its reduction."""
    iota = jnp.arange(table.shape[0], dtype=jnp.int32)
    entries = table if table.ndim == 2 else table[:, None]
    return jnp.sum(jnp.where(iota[:, None] == key[None, :], entries, 0),
                   axis=0)


def _select_float(table, key):
    """``_select`` for a table of float32 or narrower floats, through the
    values' BITS: what comes back is the entry itself (``-0.0`` and NaN
    payloads included), never a rounded sum."""
    bits = jax.lax.bitcast_convert_type(table.astype(jnp.float32), jnp.int32)
    return jax.lax.bitcast_convert_type(
        _select(bits, key), jnp.float32).astype(table.dtype)


def predict_tree(Xb, feats, bins, leaf_values):
    """[n] leaf values of one tree for the binned rows ``Xb`` [n, d].

    Level by level a row looks up its node's split feature and bin in the
    level's ``[2**level]`` tables, then its own code of that feature among
    the ``d`` it holds, and at the end its leaf. Each lookup takes the
    form ``_compares_all`` gives for its table's size: ``_select`` (the
    leaf through its bits), or the per-row gather. The node a row reaches
    is decided by integer comparisons in either, so the forms agree to the
    bit. ``treeGatherWalks`` counts the traces that kept a gather.

    Where any table is compared, the rows are padded with code-0 rows to
    whole tiles of ``_LANES`` (``_compares_all`` says why), walked, and
    cut off before the leaves are returned: code 0 is a bin and every node
    index stays inside its table, so a pad row walks like any other.
    ``Xb`` is not batched over rounds or classes, so under
    ``predict_ensemble`` the pad is made once. ``treeWalkPaddedRows`` sums
    the rows added over the traces. Rows sharded over a mesh's data axis
    are walked as they are: a pad would reshard them."""
    from transmogrifai_tpu.parallel.mesh import num_data_shards
    from transmogrifai_tpu.utils.profiling import sweep_counters
    n, d = Xb.shape
    select_code = _compares_all(d, _SELECT_MAX_WIDTH)
    select_leaf = _compares_all(leaf_values.shape[0], _SELECT_MAX_NODES)
    select_node = [_compares_all(f.shape[0], _SELECT_MAX_NODES) for f in feats]
    if not (select_code and select_leaf):  # the level tables are shorter
        sweep_counters.count_run(tree_gather_walks=1)
    pad = -n % _LANES if num_data_shards() == 1 and (
        select_code or select_leaf or any(select_node)) else 0
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        sweep_counters.count_run(tree_walk_padded_rows=pad)
    if select_code:
        codes = Xb.T.astype(jnp.int32)  # [d, n]: rows on the lanes
    else:
        rows = jnp.arange(n + pad)
    node = jnp.zeros(n + pad, dtype=jnp.int32)
    for level in range(len(feats)):
        with device_scope(f"L{level}"):
            f_tab = feats[level].astype(jnp.int32)
            b_tab = bins[level].astype(jnp.int32)
            if select_node[level]:
                f, b = _select(f_tab, node), _select(b_tab, node)
            else:
                f, b = f_tab[node], b_tab[node]
            # a node that does not split (feature -1) sends its rows left
            x = _select(codes, f) if select_code else Xb[rows, jnp.clip(f, 0)]
            go_left = (f < 0) | (x <= b)
            node = node * 2 + jnp.where(go_left, 0, 1).astype(jnp.int32)
    with device_scope("leaf"):
        if select_leaf:
            leaves = _select_float(leaf_values, node)
        else:
            leaves = leaf_values[node]
    return leaves[:n] if pad else leaves


# ---------------------------------------------------------------------------
# boosting / forest training loops
# ---------------------------------------------------------------------------

def forest_rows_carried(n: int, subsample: float, *, bootstrap: bool,
                        hist: str, data_axis=None) -> int:
    """THE static count of rows a round of ``train_ensemble`` can hand its
    grower, of the ``n`` it is given. A forest round on the sorted engine
    of one shard carries only the rows its Poisson(``subsample``) bootstrap
    drew: a row of weight 0 adds zeros to every histogram and leaf sum and
    decides no split, and at rate 1 that is ``exp(-1)`` = 36.8% of every
    slot the grower would move, at every level of every tree. The live
    count is binomial (deviation under ``0.49 * sqrt(n)``), so the cap
    stands more than 30 deviations above its mean, rounded up to whole
    ``_SORT_BLOCK`` blocks; whether a forest's draws hold it is counted,
    not assumed (``drawn_rows``). Everything else carries ``n``: boosting
    (every row's prediction feeds the next round), the scatter engine
    (small fits, CPU, GSPMD), a shard of ``train_ensemble_sharded`` (its
    live count differs a shard), and a table so small that the cap is
    ``n`` (under about 2,000 rows)."""
    if not (bootstrap and hist == "sorted" and data_axis is None):
        return n
    cap = math.ceil((1.0 - math.exp(-subsample)) * n + 16.0 * math.sqrt(n))
    return min(n, -(-cap // _SORT_BLOCK) * _SORT_BLOCK)


@functools.partial(jax.jit, static_argnames=("n", "n_rounds", "seed",
                                             "subsample"))
def forest_draws(*, n: int, n_rounds: int, seed: int, subsample: float):
    """[n_rounds, n] int32: the Poisson bootstrap draw of each round of a
    forest over ``n`` rows, by the keys ``train_ensemble``'s rounds use. A
    function of its static arguments alone."""
    def draw(key):
        return jax.random.poisson(jax.random.split(key)[0], subsample, (n,))

    return jax.lax.map(draw, jax.random.split(jax.random.PRNGKey(seed),
                                              n_rounds))


@jax.jit
def _most_drawn(draws):
    """The largest count of drawn rows (positive weight) over the rounds."""
    return jnp.max(jnp.sum(draws > 0, axis=1))


@functools.lru_cache(maxsize=None)
def _draws_hold(n: int, n_rounds: int, seed: int, subsample: float,
                n_cap: int) -> bool:
    """Whether no round of ``forest_draws`` draws more than ``n_cap`` rows:
    counted on the device and pulled ONCE a process and key (the one host
    sync the mechanism costs, in a process's first train of a shape)."""
    return int(_most_drawn(forest_draws(
        n=n, n_rounds=n_rounds, seed=seed, subsample=subsample))) <= n_cap


def drawn_rows(n: int, *, n_rounds: int, seed: int, subsample: float,
               hist: str, data_axis=None):
    """The ``draws`` to hand ``train_ensemble`` / ``train_score_stacked``
    for a forest over ``n`` rows, or ``None`` where its rounds carry all
    rows: the engine, the axis or the table's size rule compaction out
    (``forest_rows_carried``), or some round draws more rows than the cap
    holds (30 deviations out: the forest then grows on all rows, by the
    program it always had, so no drawn row is ever dropped)."""
    n_cap = forest_rows_carried(n, subsample, bootstrap=True, hist=hist,
                                data_axis=data_axis)
    if n_cap == n or not _draws_hold(n, n_rounds, seed, subsample, n_cap):
        return None
    return forest_draws(n=n, n_rounds=n_rounds, seed=seed,
                        subsample=subsample)


@functools.partial(jax.jit, static_argnames=(
    "n_rounds", "max_depth", "n_bins", "n_out", "loss", "seed",
    "bootstrap", "subsample", "colsample", "max_hist_nodes",
    "hist", "data_axis"))
def train_ensemble(Xb, y, w, draws=None, *, n_rounds: int, max_depth: int,
                   n_bins: int, n_out: int, loss: str, learning_rate,
                   reg_lambda, gamma, min_child_weight, subsample,
                   colsample, base_score, bootstrap: bool, seed: int,
                   max_hist_nodes: int = _MAX_HIST_NODES,
                   hist: str = "scatter", data_axis=None):
    """Train a whole ensemble in one scanned program.

    loss: 'logistic' (n_out=1), 'softmax' (n_out=K one-vs-all), 'squared'.
    bootstrap=True grows independent trees on Poisson(1) row weights from
    the base margin (random forest); otherwise rounds are boosted.

    ``draws`` (``drawn_rows``: a forest on the sorted engine whose draws
    the cap holds) makes a round carry only the rows its bootstrap drew:
    the rows of positive weight are compacted to the front of ``n_cap``
    slots (``forest_rows_carried``) once a round, before the round's
    ``n_out`` trees grow, and the grower sees ``n_cap`` rows (the slots
    past the live count weigh 0, as dead rows did). The draws are an
    argument no batch axis reaches, so under ``train_score_stacked``'s
    ``vmap``s the indices are computed once a round a program and each
    fold gathers its own rows by them. Without ``draws`` a forest draws
    inside the program and moves all ``n`` rows, as every other ensemble
    does. Draws the cap does not hold (``drawn_rows`` hands none out) give
    NaN leaves, never a forest of fewer rows.
    """
    n, d = Xb.shape
    key0 = jax.random.PRNGKey(seed)
    n_cap = n
    if draws is not None:
        n_cap = forest_rows_carried(n, subsample, bootstrap=bootstrap,
                                    hist=hist, data_axis=data_axis)
        if n_cap == n or draws.shape != (n_rounds, n):
            raise ValueError(
                f"draws {draws.shape}: for a forest on the sorted engine of "
                f"one shard over rows the cap is under ({n_cap} of {n}), "
                f"one draw a round ({n_rounds})")

    def margins_zero(rows=n):
        return jnp.broadcast_to(base_score, (rows, n_out)
                                ).astype(jnp.float32)

    def grads(margin, y=y):
        if loss == "logistic":
            p = jax.nn.sigmoid(margin[:, 0])
            return (p - y)[:, None], (p * (1 - p))[:, None]
        if loss == "softmax":
            t = jax.nn.one_hot(y.astype(jnp.int32), n_out)
            p = jax.nn.sigmoid(margin)  # one-vs-all logistic per class
            return p - t, p * (1 - p)
        if loss == "squared_onehot":
            # multiclass forest: per-class regression trees on the one-hot
            # target, all classes vmapped in THIS one program (leaf value =
            # weighted class frequency, the gini-style probability estimate)
            t = jax.nn.one_hot(y.astype(jnp.int32), n_out)
            return margin - t, jnp.ones_like(margin)
        return margin - y[:, None], jnp.ones_like(margin)

    def one_round(carry, key_draw):
        margin = carry
        key, draw = key_draw if draws is not None else (key_draw, None)
        if draw is None:  # else a function of the gathered labels, below
            g, h = grads(margin)
        k_rows, k_cols = jax.random.split(key)
        if data_axis is not None:
            # distributed: row-sampling draws must be INDEPENDENT per
            # shard (fold in the shard index) while the feature mask
            # below must stay IDENTICAL across shards (k_cols unfolded)
            k_rows = jax.random.fold_in(k_rows,
                                        jax.lax.axis_index(data_axis))
        if draw is not None:
            rw = draw.astype(jnp.float32)
        elif bootstrap:
            rw = jax.random.poisson(k_rows, subsample, (n,)).astype(jnp.float32)
        elif subsample < 1.0:
            rw = (jax.random.uniform(k_rows, (n,)) < subsample
                  ).astype(jnp.float32)
        else:
            rw = jnp.ones(n, jnp.float32)
        rw = rw * w
        fmask = (jax.random.uniform(k_cols, (d,)) < colsample
                 ).astype(jnp.float32)
        fmask = jnp.where(jnp.sum(fmask) < 1.0, jnp.ones(d, jnp.float32),
                          fmask)

        def grow_round(Xb, g, h, rw):
            def grow_one(gk, hk):
                return grow_tree(Xb, gk * rw, hk * rw, fmask,
                                 max_depth=max_depth, n_bins=n_bins,
                                 reg_lambda=reg_lambda, gamma=gamma,
                                 min_child_weight=min_child_weight,
                                 max_hist_nodes=max_hist_nodes, hist=hist,
                                 data_axis=data_axis)

            if n_out == 1:  # the one-output programs as they always were
                return jax.vmap(grow_one, in_axes=(1, 1))(g, h)
            # the K one-vs-all trees of a round share nothing but the rows:
            # grown one after another inside the program (a ``vmap`` over
            # them multiplies the grower's temporaries, and on the TPU its
            # compile time, by K, and one chip runs them in turn anyway)
            return jax.lax.map(lambda gh: grow_one(*gh), (g.T, h.T))

        # feats/bins: tuples of [n_out, 2^level]; leaves [n_out, 2^depth];
        # preds [n_out, n] come from the grower's final node assignment
        # (no re-descent)
        if draw is None:
            feats, bins, leaves, gains, preds = grow_round(Xb, g, h, rw)
            if bootstrap:
                new_margin = margin  # forest trees are independent
            else:
                new_margin = margin + learning_rate * preds.T
            return new_margin, ((feats, bins, leaves),
                                jnp.sum(gains, axis=0))

        # a forest round on the rows its bootstrap drew: their positions are
        # one cumsum and one unique-index scatter that no batch axis reaches
        with device_scope("tree.compact"):
            live = draw > 0
            pos = _long_cumsum(live.astype(jnp.int32))
            n_live = pos[-1]
            # dead rows (and live ones past a cap that does not hold them)
            # get DISTINCT out-of-range slots and drop
            slot = jnp.where(live, pos - 1,
                             n_cap + jnp.arange(n, dtype=jnp.int32))
            rows = jnp.zeros(n_cap, jnp.int32).at[slot].set(
                jnp.arange(n, dtype=jnp.int32), mode="drop",
                unique_indices=True)
            held = jnp.arange(n_cap, dtype=jnp.int32) < n_live
            rw_c = jnp.where(held, rw[rows], 0.0)
            # a forest's margin is the base score in every round, so the
            # gradients are a function of the gathered labels
            g_c, h_c = grads(margins_zero(n_cap), y[rows])
            Xb_c = Xb[rows]
        feats, bins, leaves, gains, _ = grow_round(Xb_c, g_c, h_c, rw_c)
        leaves = jnp.where(n_live <= n_cap, leaves, jnp.nan)
        return margin, ((feats, bins, leaves), jnp.sum(gains, axis=0))

    keys = jax.random.split(key0, n_rounds)
    _, (trees, gains) = jax.lax.scan(
        one_round, margins_zero(),
        keys if draws is None else (keys, draws))
    # trees: pytree with leading [n_rounds] axis; gains: [n_rounds, d]
    return trees, jnp.sum(gains, axis=0)


def train_ensemble_sharded(ctx, Xb, y, w, **kw):
    """Distributed ensemble fit: the SORTED engine under an explicit
    ``shard_map`` over the mesh's data axis.

    Each shard keeps its own rows' sort bookkeeping (order/counts) and
    contributes per-level local histograms; one [N, d, B] psum per level
    (plus one for the leaf sums) replicates the split decisions — the
    XLA-collective analog of XGBoost's Rabit all-reduce / Spark MLlib's
    executor histogram aggregation (SURVEY §2.7 P5), now on the engine
    that is 5-7x faster per level than the scatter path. Row sampling
    folds the shard index into the per-round key (independent draws);
    the colsample mask deliberately does not (must match across shards).

    ``Xb``/``y``/``w`` must be row-sharded over ``ctx.mesh``'s data axis
    (rows padded to the shard multiple with weight 0 — what
    ``parallel.mesh.shard_training_rows`` produces). Returns the same
    (trees, gains) as ``train_ensemble``, replicated.
    """
    from jax.sharding import PartitionSpec as P
    from transmogrifai_tpu.parallel.mesh import DATA_AXIS

    def shard_fn(Xb_s, y_s, w_s):
        return train_ensemble(Xb_s, y_s, w_s, hist="sorted",
                              data_axis=DATA_AXIS, **kw)

    fn = jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(), check_vma=False)
    return fn(Xb, y, w)


@functools.partial(jax.jit, static_argnames=(
    "n_rounds", "max_depth", "n_bins", "loss", "subsample",
    "colsample", "bootstrap", "seed", "hist", "forest_margin", "n_out"))
def train_score_stacked(Xb, y, w, Xva, base, lr, lam, gam, mcw,
                        draws=None, *,
                        n_rounds: int, max_depth: int, n_bins: int,
                        loss: str, subsample, colsample,
                        bootstrap: bool, seed: int, hist: str,
                        forest_margin: bool, n_out: int = 1):
    """ONE compiled program for a whole (family, depth-group) of the CV
    sweep: train all ``k`` folds x ``L`` same-shape grid lanes and score
    their validation folds, returning ``[k, L, n_va]`` scores, or with
    ``n_out`` one-vs-all outputs a lane ``[k, L, n_out, n_va]`` class
    scores (the class axis before the rows, which stay on the TPU's lanes).

    ``Xb/Xva``: ``[k, n, d]`` stacked int bin codes (one fold gather of
    the dataset-level ``fold_sweep_plan`` codes — no re-binning; the
    validation rows are scored by ``predict_ensemble`` under the scope
    ``tree.predict``, on a TPU with no row-long gather);
    ``y/w``: ``[k, n]``; ``base``: ``[k]`` per-fold base scores
    (host-computed with the loop path's exact f32/f64 arithmetic —
    ``tree_stack_fold_bases`` — so stacked-vs-loop parity stays bitwise);
    ``lr/lam/gam/mcw``: ``[L]`` per-lane hyperparameter scalars riding as
    batched operands; ``draws``: a forest's ``drawn_rows`` (every fold and
    lane grows on the rows of the one draw a round), else ``None``. The fold axis is the outer ``vmap``, lanes the
    inner one, so the existing ``lax.scan``-over-rounds grower batches:
    the sorted engine's one-hot contraction gains MXU batch dims
    (node-count-independent, the extra axis feeds the systolic array),
    and the scatter engine's histograms fold every batch axis into the
    node axis via the ``custom_vmap`` rule in ``ops/histograms.py`` —
    one flat scatter per level for the whole (fold x lane x class)
    batch. ``forest_margin`` re-centers forest-classifier probabilities
    at 0, matching ``grid_predict_scores`` (past one output the class
    votes stay in ``[0, 1]``: their argmax is the prediction).
    """

    def fold_fn(Xb_k, y_k, w_k, Xva_k, base_k):
        def lane_fn(lr_i, lam_i, gam_i, mcw_i):
            trees, _gains = train_ensemble(
                Xb_k, y_k, w_k, draws, n_rounds=n_rounds, max_depth=max_depth,
                n_bins=n_bins, n_out=n_out, loss=loss, learning_rate=lr_i,
                reg_lambda=lam_i, gamma=gam_i, min_child_weight=mcw_i,
                subsample=subsample, colsample=colsample,
                base_score=base_k, bootstrap=bootstrap, seed=seed,
                hist=hist)
            out = predict_ensemble(Xva_k, trees, n_out=n_out,
                                   learning_rate=lr_i, base_score=base_k,
                                   bootstrap=bootstrap)
            if n_out > 1:  # class scores, rows minor
                s = out.T  # [n_out, n_va]
                return jnp.clip(s, 0.0, 1.0) if forest_margin else s
            s = out[:, 0]
            if forest_margin:
                s = jnp.clip(s, 0.0, 1.0) - 0.5  # margin at 0
            return s

        return jax.vmap(lane_fn)(lr, lam, gam, mcw)

    return jax.vmap(fold_fn)(Xb, y, w, Xva, base)


def predict_ensemble(Xb, trees, *, n_out: int, learning_rate, base_score,
                     bootstrap: bool):
    """[n, n_out] margins of a stacked ensemble (``trees``: per-level
    ``[R, n_out, 2**level]`` tables and ``[R, n_out, 2**depth]`` leaves):
    ``predict_tree`` over rounds and classes, under the device scope
    ``tree.predict`` (its levels read ``tree.predict/L<level>``). A forest
    is the mean of its trees: a regression forest's caller adds the base
    its trees grew from (their leaves are residuals of the label's mean,
    ``_TreePredictor._stacked_base_mode``)."""
    walk = functools.partial(predict_tree, Xb)
    with device_scope("tree.predict"):
        preds = jax.vmap(jax.vmap(walk))(*trees)  # [R, n_out, n]
    if bootstrap:
        return jnp.mean(preds, axis=0).T  # [n, n_out]
    return base_score + learning_rate * jnp.sum(preds, axis=0).T


# ---------------------------------------------------------------------------
# fitted model
# ---------------------------------------------------------------------------

class TreeEnsembleModel(PredictionModel):
    """Fitted ensemble. kind: 'gbt_classifier' | 'gbt_regressor' |
    'rf_classifier' | 'rf_regressor'."""

    def __init__(self, kind: str = "gbt_classifier", n_out: int = 1,
                 learning_rate: float = 0.3, base_score: float = 0.0,
                 max_depth: int = 6, uid: Optional[str] = None):
        self.kind = kind
        self.n_out = n_out
        self.learning_rate = learning_rate
        self.base_score = base_score
        self.max_depth = max_depth
        self.bin_edges: Optional[np.ndarray] = None
        self.trees = None  # (feats tuple, bins tuple, leaves) stacked [R,...]
        self.feature_gains = None  # [d] accumulated split gains (importance)
        super().__init__(uid=uid)

    @property
    def is_forest(self) -> bool:
        return self.kind.startswith("rf")

    @property
    def is_classifier(self) -> bool:
        return self.kind.endswith("classifier")

    def device_params(self):
        # the base score is a value of the training data (a GBT
        # classifier's is the log-odds of its split's label mean): it
        # enters every program as an argument, so that a model fitted on
        # another table runs the same program (a host array: nothing is
        # dispatched to hand it over)
        return (jnp.asarray(self.bin_edges), self.trees,
                np.asarray(self.base_score, np.float32))

    def quantize_device_params(self, precision):
        from transmogrifai_tpu.utils.precision import ExactTensor, fits_int16
        edges, (feats, bins, leaves), base = self.device_params()
        if precision == "int8" and all(fits_int16(a)
                                       for a in (*feats, *bins)):
            # node traversal compares binned int data: int16 vs int32
            # promotes exactly, so the threshold path is bitwise-safe
            feats = tuple(jnp.asarray(a, jnp.int16) for a in feats)
            bins = tuple(jnp.asarray(a, jnp.int16) for a in bins)
        # bin edges stay f32 master values at every rung (ExactTensor
        # pins them through the builder's generic float cast), and so
        # does the base score, an offset on the margin; leaf values take
        # the rung's activation dtype like any float param
        return (ExactTensor(edges), (feats, bins, leaves), ExactTensor(base))

    def device_apply(self, params, col: fr.VectorColumn) -> fr.PredictionColumn:
        edges, trees, base = params
        Xb = bin_data(col.values, edges)
        out = predict_ensemble(
            Xb, trees, n_out=self.n_out,
            learning_rate=self.learning_rate, base_score=base,
            bootstrap=self.is_forest)  # [n, n_out]
        n = out.shape[0]
        if not self.is_classifier:
            if self.is_forest:      # its trees fit residuals of the base
                out = out + base
            empty = jnp.zeros((n, 0), jnp.float32)
            return fr.PredictionColumn(out[:, 0], empty, empty)
        if self.is_forest:
            # leaves hold class probabilities directly
            if self.n_out == 1:
                p1 = jnp.clip(out[:, 0], 0.0, 1.0)
                prob = jnp.stack([1 - p1, p1], axis=1)
            else:
                s = jnp.clip(out, 0.0, 1.0)
                prob = s / jnp.maximum(jnp.sum(s, axis=1, keepdims=True), 1e-12)
            raw = prob
        else:
            if self.n_out == 1:
                p1 = jax.nn.sigmoid(out[:, 0])
                prob = jnp.stack([1 - p1, p1], axis=1)
                raw = jnp.stack([-out[:, 0], out[:, 0]], axis=1)
            else:
                prob = jax.nn.softmax(out, axis=1)
                raw = out
        pred = jnp.argmax(prob, axis=1).astype(jnp.float32)
        return fr.PredictionColumn(pred, raw, prob)

    # -- persistence ---------------------------------------------------------
    def fitted_state(self):
        feats, bins, leaves = self.trees
        state = {"bin_edges": np.asarray(self.bin_edges),
                 "leaves": np.asarray(leaves)}
        if self.feature_gains is not None:
            state["feature_gains"] = np.asarray(self.feature_gains)
        for l, (f, b) in enumerate(zip(feats, bins)):
            state[f"feat_l{l}"] = np.asarray(f)
            state[f"bin_l{l}"] = np.asarray(b)
        return state

    def set_fitted_state(self, state):
        self.bin_edges = np.asarray(state["bin_edges"])
        leaves = jnp.asarray(state["leaves"])
        if "feature_gains" in state:
            self.feature_gains = np.asarray(state["feature_gains"])
        feats, bins = [], []
        for l in range(self.max_depth):
            feats.append(jnp.asarray(state[f"feat_l{l}"]))
            bins.append(jnp.asarray(state[f"bin_l{l}"]))
        self.trees = (tuple(feats), tuple(bins), leaves)

    def config(self):
        base = self.base_score
        if np.ndim(base):  # per-class vector (imported multiclass GBMs)
            base = [float(b) for b in np.asarray(base)]
        return {"kind": self.kind, "n_out": self.n_out,
                "learning_rate": self.learning_rate,
                "base_score": base, "max_depth": self.max_depth}

    @classmethod
    def from_config(cls, config, uid=None):
        config = dict(config)
        if isinstance(config.get("base_score"), (list, tuple)):
            config["base_score"] = np.asarray(config["base_score"],
                                              np.float32)
        return cls(uid=uid, **config)

    def feature_contributions(self) -> np.ndarray:
        """Gain-based importance shares (reference ModelInsights extracts
        real gain importances per model type, ``ModelInsights.scala:64-858``;
        XGBoost 'total_gain' semantics): each feature's share of the total
        split gain accumulated during growth. Falls back to depth-weighted
        split frequency for models restored from pre-gain manifests."""
        if self.feature_gains is not None:
            imp = np.maximum(np.asarray(self.feature_gains, np.float64), 0.0)
            total = imp.sum()
            return imp / total if total > 0 else imp
        feats, _, _ = self.trees
        d = int(self.bin_edges.shape[0])
        imp = np.zeros(d)
        for level, f in enumerate(feats):
            arr = np.asarray(f).reshape(-1)
            wgt = 1.0 / (2 ** level)
            for v in arr[arr >= 0]:
                imp[int(v)] += wgt
        total = imp.sum()
        return imp / total if total > 0 else imp


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

class _TreePredictor(Predictor):
    kind = "gbt_classifier"
    loss = "logistic"
    bootstrap = False

    default_params = {
        "num_rounds": 50,        # trees (forest) / boosting rounds (gbt)
        "max_depth": 6,
        "max_bins": 64,
        "learning_rate": 0.3,    # eta / stepSize
        "reg_lambda": 1.0,
        "gamma": 0.0,
        "min_child_weight": 1.0,
        "subsample": 1.0,
        "colsample": 1.0,
        "seed": 42,
    }

    # forest synonyms accepted in grids
    _ALIASES = {"num_trees": "num_rounds", "eta": "learning_rate",
                "step_size": "learning_rate"}

    def __init__(self, uid=None, **params):
        params = {self._ALIASES.get(k, k): v for k, v in params.items()}
        super().__init__(uid=uid, **params)

    def _loss_and_nout(self, y, _stats=None) -> tuple[str, int, float]:
        """(loss, n_out, base score). ``_stats`` is the selector's
        once-per-sweep host pull of ``(max(y), mean(y),
        clip(mean(y), 1e-6, 1-1e-6))`` — each value produced by the SAME
        device expression this method would run, so the threaded route is
        bitwise-identical to the per-family blocking pull it elides on
        the one-sync dispatch path."""
        if self.loss == "squared":
            mean = _stats[1] if _stats is not None else jnp.mean(y)
            return "squared", 1, float(mean)
        y_max = (_stats[0] if _stats is not None
                 else np.asarray(jnp.max(y)))
        n_classes = int(y_max) + 1
        if n_classes <= 2:
            clipped = (_stats[2] if _stats is not None
                       else jnp.clip(jnp.mean(y), 1e-6, 1 - 1e-6))
            p = float(clipped)
            base = 0.0 if self.bootstrap else float(np.log(p / (1 - p)))
            return "logistic", 1, base
        return "softmax", n_classes, 0.0

    def _stacked_base_mode(self, loss: str) -> str:
        """How the fold x grid-stacked program derives each fold's base
        score IN-PROGRAM — must mirror ``_loss_and_nout``'s base exactly
        (the stacked-vs-loop parity contract), so overrides pair with it:
        ``"mean"`` = fold label mean (squared losses, forests included:
        a regression forest's trees fit residuals of that mean, which every
        prediction of it adds back: ``TreeEnsembleModel.device_apply``,
        ``tree_stack_scores``, ``grid_predict_scores``), ``"logodds"`` =
        log-odds of the fold's positive rate, ``"zero"`` = 0 (forest
        classifiers, and one-vs-all boosting past two classes)."""
        if loss == "squared":
            return "mean"
        return "zero" if self.bootstrap or loss == "softmax" else "logodds"

    def _edges_of(self, X, max_bins: int):
        """Quantile edges; device path for device-resident X (no host pull),
        host percentile for plain numpy input."""
        if isinstance(X, jax.Array):
            return quantile_bin_edges_device(X, max_bins=max_bins)
        return jnp.asarray(quantile_bin_edges(np.asarray(X), max_bins))

    def _binned(self, X, max_bins: int) -> tuple:
        """``(edges, codes, max_bins)`` of ``X``; the quantile and
        ``bin_data`` programs build under the compile site ``bin_data``."""
        from transmogrifai_tpu.utils.devicewatch import compile_telemetry
        with compile_telemetry.building("bin_data"):
            edges = self._edges_of(X, max_bins)
            return edges, bin_data(X, edges), max_bins

    def _forest_draws(self, n: int, trees: int, *, n_rounds: int, seed: int,
                      subsample: float, hist_mode: str):
        """The ``draws`` of a forest program about to be dispatched
        (``drawn_rows``; ``None`` for any other program), counted where it
        is dispatched, from static shapes (a warm process does not
        retrace): ``n`` rows a tree given and the rows a round carries,
        times the program's ``trees``. The analytic FLOPs and the
        lane-memory estimate beside the call sites stay by all rows."""
        if not self.bootstrap:
            return None
        from transmogrifai_tpu.utils.profiling import sweep_counters
        draws = drawn_rows(n, n_rounds=n_rounds, seed=seed,
                           subsample=subsample, hist=hist_mode)
        carried = n if draws is None else forest_rows_carried(
            n, subsample, bootstrap=True, hist=hist_mode)
        sweep_counters.count_run(forest_rows_total=n * trees,
                                 forest_rows_carried=carried * trees)
        return draws

    def fit_arrays(self, X, y, w, params, _binned=None, _lnb=None):
        params = {self._ALIASES.get(k, k): v for k, v in params.items()}
        p = {**self.default_params, **params}
        # (loss, n_out, base) involves blocking device->host scalar pulls
        # (max/mean of y); grid sweeps compute it once and thread it here
        loss, n_out, base = _lnb if _lnb is not None \
            else self._loss_and_nout(y)
        if _binned is not None and int(p["max_bins"]) == _binned[2]:
            edges, Xb = _binned[0], _binned[1]
        else:
            edges, Xb, _ = self._binned(X, int(p["max_bins"]))
        subsample = float(p["subsample"]) if not self.bootstrap else 1.0
        from transmogrifai_tpu.utils import flops
        n, d = int(Xb.shape[0]), int(Xb.shape[1])
        depth, rounds, B = int(p["max_depth"]), int(p["num_rounds"]), \
            int(p["max_bins"])
        try:
            n_devices = len(Xb.devices())
        except Exception:  # failure-ok: device probe; default to single-device route
            n_devices = 1
        hist_mode = _hist_engine(n, n_devices)
        if hist_mode.startswith("sorted"):
            # per level: padded-row one-hot contraction 4*n*d*B MXU MACs
            # (g+h stats) + layout/partition cumsums ~10n + split eval
            per_tree = sum(4.0 * n * d * B + 10.0 * n
                           + 12.0 * (2 ** lv) * d * B
                           for lv in range(depth))
        else:
            # per level: flat-index + 2 scatter adds ~5nd update ops,
            # routing ~4n, split eval ~12*nodes*d*B; device update-ops,
            # not MXU FLOPs — scatter histogram work is bandwidth-bound
            # (see utils/flops.py docstring)
            per_tree = sum(5.0 * n * d + 4.0 * n + 12.0 * (2 ** lv) * d * B
                           for lv in range(depth))
        flops.add("tree", rounds * n_out * per_tree)
        draws = self._forest_draws(n, rounds * n_out, n_rounds=rounds,
                                   seed=int(p["seed"]), subsample=subsample,
                                   hist_mode=hist_mode)
        ens_kw = dict(
            n_rounds=int(p["num_rounds"]), max_depth=int(p["max_depth"]),
            n_bins=int(p["max_bins"]), n_out=n_out, loss=loss,
            learning_rate=jnp.float32(p["learning_rate"]),
            reg_lambda=jnp.float32(p["reg_lambda"]),
            gamma=jnp.float32(p["gamma"]),
            min_child_weight=jnp.float32(p["min_child_weight"]),
            subsample=subsample,
            colsample=float(p["colsample"]),
            base_score=jnp.float32(base),
            bootstrap=self.bootstrap, seed=int(p["seed"]))
        if hist_mode == "sorted_sharded":
            from transmogrifai_tpu.parallel.mesh import current_mesh
            trees, gains = train_ensemble_sharded(current_mesh(), Xb, y, w,
                                                  **ens_kw)
        else:
            trees, gains = train_ensemble(
                Xb, y, w, draws, max_hist_nodes=_MAX_HIST_NODES,
                hist=hist_mode, **ens_kw)
        model = TreeEnsembleModel(
            kind=self.kind, n_out=n_out,
            learning_rate=float(p["learning_rate"]), base_score=base,
            max_depth=int(p["max_depth"]))
        model.bin_edges = edges
        model.trees = jax.tree_util.tree_map(lambda a: a, trees)
        model.feature_gains = gains  # device view; host pull is lazy
        return model


    def fold_sweep_plan(self, X, grid):
        """Dataset-level binning context for the selector's per-fold sweep:
        ``{max_bins: (edges, codes [n, d], max_bins)}`` computed ONCE on the
        full prepared training matrix; each fold's codes are then a cheap
        row gather instead of a fresh device quantile sort + searchsorted
        per fold (the sweep's k-fold re-binning was pure waste — edges
        barely move between a fold's (1 - 1/k) subset and the full matrix).

        Documented ``bin_once`` approximation: fold edges come from the
        whole training matrix, the XGBoost global-sketch analog; metrics
        shift by sub-bin-width amounts."""
        merged = [{self._ALIASES.get(k, k): v for k, v in g.items()}
                  for g in grid]
        plan: dict[int, tuple] = {}
        for g in merged:
            mb = int({**self.default_params, **self.params, **g}["max_bins"])
            if mb not in plan:
                plan[mb] = self._binned(X, mb)
        return plan

    def grid_fit_arrays(self, X, y, w, grid, _fold_plan=None,
                        _fold_rows=None):
        """Sequential grid (tree programs differ per static depth/rounds),
        but quantile-bin ONCE per (fold, family): edges depend only on X and
        max_bins, so grid points sharing max_bins reuse one binned matrix
        instead of paying a device sort + searchsorted each. With a
        ``_fold_plan`` (the selector's per-dataset ``fold_sweep_plan``) the
        binning collapses further to one row gather of the dataset-level
        codes (``_fold_rows`` are this fold's training row ids)."""
        merged = [{self._ALIASES.get(k, k): v for k, v in g.items()}
                  for g in grid]
        binned: dict[int, tuple] = {}
        lnb = self._loss_and_nout(y)  # ONE device sync for the whole grid
        models = []
        for g in merged:
            mb = int({**self.default_params, **self.params, **g}["max_bins"])
            if mb not in binned:
                if _fold_plan is not None and _fold_rows is not None \
                        and mb in _fold_plan:
                    edges, codes_full, _ = _fold_plan[mb]
                    binned[mb] = (edges,
                                  jnp.take(codes_full, _fold_rows, axis=0),
                                  mb)
                else:
                    binned[mb] = self._binned(X, mb)
            models.append(self.fit_arrays(X, y, w, {**self.params, **g},
                                          _binned=binned[mb], _lnb=lnb))
        return models

    def grid_predict_scores(self, models, X):
        """Batched scoring when every grid model shares tree shapes (same
        max_depth/n_out): stack tree params and vmap one predict program."""
        if not models or not all(isinstance(m, TreeEnsembleModel)
                                 for m in models):
            return None
        m0 = models[0]
        if any(m.max_depth != m0.max_depth or m.n_out != m0.n_out
               or m.trees[2].shape != m0.trees[2].shape for m in models):
            return None
        if m0.n_out != 1:
            # the per-fold loop's batched metric takes a scalar score; the
            # fold-stacked path scores every class (``tree_stack_scores``)
            return None
        edges0 = m0.bin_edges
        same_edges = all(np.array_equal(m.bin_edges, edges0) for m in models)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[m.trees for m in models])
        Xb = bin_data(X, jnp.asarray(edges0)) if same_edges else None

        def score_one(trees, lr, base):
            out = predict_ensemble(Xb, trees, n_out=1, learning_rate=lr,
                                   base_score=base, bootstrap=m0.is_forest)
            s = out[:, 0]
            if m0.is_forest and m0.is_classifier:
                s = jnp.clip(s, 0.0, 1.0) - 0.5  # margin at 0
            return s

        if Xb is None:
            return None
        lrs = jnp.asarray([m.learning_rate for m in models], jnp.float32)
        bases = jnp.asarray([m.base_score for m in models], jnp.float32)
        scores = jax.vmap(score_one)(stacked, lrs, bases)
        if m0.is_forest and not m0.is_classifier:
            # a regression forest's base, added as the stacked sweep adds
            # it (``tree_stack_scores``): one rounding, the same in both
            return scores + bases[:, None]
        return scores

    # -- fold x grid-stacked sweep (round 8) ---------------------------------
    def tree_stack_groups(self, grid):
        """Group the grid by compiled-program shape — the static arguments
        of ``train_ensemble``: ``(max_depth, num_rounds, max_bins,
        subsample, colsample, seed)``. Each group's lanes share one
        compiled stacked program; the per-lane scalars (learning_rate,
        reg_lambda, gamma, min_child_weight) ride as batched operands.
        Returns ``[{lanes, params, max_depth, num_rounds, max_bins,
        subsample, colsample, seed}]`` in first-seen order (deterministic,
        so checkpoint group indices are stable across runs)."""
        merged = [{**self.default_params, **self.params,
                   **{self._ALIASES.get(k, k): v for k, v in g.items()}}
                  for g in grid]
        groups: dict[tuple, dict] = {}
        for i, p in enumerate(merged):
            # forests ignore the subsample grid value (fit_arrays pins the
            # Poisson rate to 1.0), so it must not split their groups
            sub = 1.0 if self.bootstrap else float(p["subsample"])
            key = (int(p["max_depth"]), int(p["num_rounds"]),
                   int(p["max_bins"]), sub, float(p["colsample"]),
                   int(p["seed"]))
            g = groups.setdefault(key, {
                "lanes": [], "params": [], "max_depth": key[0],
                "num_rounds": key[1], "max_bins": key[2],
                "subsample": key[3], "colsample": key[4], "seed": key[5]})
            g["lanes"].append(i)
            g["params"].append(p)
        return list(groups.values())

    def tree_stack_lnb(self, y, _stats=None):
        """``(loss, n_out, base)`` of the family's stacked unit:
        ``_loss_and_nout`` with the selector's once-per-sweep ``_stats``
        hint, which elides the ONE blocking device sync (max of y) a
        family would pay (signature-gated: a subclass overriding
        ``_loss_and_nout`` with the old arity keeps its own probe)."""
        import inspect
        if _stats is not None and "_stats" in \
                inspect.signature(self._loss_and_nout).parameters:
            return self._loss_and_nout(y, _stats=_stats)
        return self._loss_and_nout(y)

    def tree_stack_bytes(self, k: int, n_tr: int, n_va: int, d: int,
                         group: dict, n_out: int = 1
                         ) -> tuple[float, float]:
        """``(shared_bytes, per_lane_bytes)`` HBM estimate for one stacked
        depth-group — the tree-specific extension of the selector's
        ``fold_stack_unit_width`` guard. Shared: the stacked int8/int32
        code gathers plus labels/weights. Per lane (times k folds): the
        boosting margins/grad/hess of each of the ``n_out`` outputs and the
        row-weight residency, both levels' (g, h) node-stat histograms and
        the sorted engine's materialized one-hot chunk when that engine is
        selected (once: the one-vs-all trees of a round grow one after
        another), and the ``[k, L, n_out, n_va]`` score slab. The selector
        divides the budget by this to split a group into lane chunks
        instead of falling all the way back to the per-fold loop."""
        B = int(group["max_bins"])
        depth = int(group["max_depth"])
        csize = 1 if B <= 127 else 4
        shared = float(k) * (float(n_tr + n_va) * d * csize
                             + 8.0 * n_tr + 4.0 * n_va)
        nodes = min(2 ** max(depth - 1, 0), _MAX_HIST_NODES)
        hist_bytes = 16.0 * nodes * d * B  # (g, h) x (level, prev) f32
        if _hist_engine(n_tr, stacked=True) == "sorted":
            hist_bytes += min(float(_SORT_OH_BUDGET), 4.0 * n_tr * d * B)
        outs = max(int(n_out), 1)
        per_lane = float(k) * ((16.0 + 12.0 * outs) * n_tr + hist_bytes
                               + 8.0 * n_va * outs)
        return shared, per_lane

    def tree_stack_fold_bases(self, fold_means, loss: str) -> np.ndarray:
        """``[k]`` per-fold base scores from the folds' label means,
        replicating ``_loss_and_nout``'s exact f32-clip + f64-log
        arithmetic on HOST so stacked-vs-loop metric parity is bitwise
        (an in-program f32 log differs by ~1 ulp, enough to move binned-
        metric bucket boundaries at scale)."""
        mode = self._stacked_base_mode(loss)
        means = np.asarray(fold_means, np.float32)
        if mode == "zero":
            return np.zeros(means.shape[0], np.float32)
        if mode == "mean":
            return means
        out = []
        for m in means:
            p = float(np.clip(m, np.float32(1e-6), np.float32(1 - 1e-6)))
            out.append(np.log(p / (1.0 - p)))
        return np.asarray(out, np.float32)

    def tree_stack_scores(self, Xb, y, w, Xva, lane_params, lnb,
                          fold_means=None):
        """``[k, L, n_va]`` validation scores for one (family,
        depth-group), or ``[k, L, n_out, n_va]`` class scores where the
        family grows ``n_out`` one-vs-all trees a member: the selector
        fast path's fused train+score unit. ``Xb/Xva`` are the stacked
        fold gathers of the dataset-level bin codes, ``lane_params`` the
        merged param dicts of this chunk's lanes (same static shape —
        ``tree_stack_groups`` guarantees it), ``lnb`` the family-level
        ``tree_stack_lnb``, and ``fold_means`` the folds' label means (the
        selector pulls them once per sweep; computed here — one sync —
        when absent)."""
        loss, n_out, _base = lnb
        p0 = lane_params[0]
        k, n_tr, d = (int(Xb.shape[0]), int(Xb.shape[1]), int(Xb.shape[2]))
        L = len(lane_params)
        if fold_means is None and self._stacked_base_mode(loss) != "zero":
            # each fold's mean comes from the SAME unbatched program the
            # loop path runs (a batched row-mean may re-associate)
            fold_means = np.asarray(jnp.stack(
                [jnp.mean(y[f]) for f in range(k)]))
        bases = jnp.asarray(self.tree_stack_fold_bases(
            fold_means if fold_means is not None else np.zeros(k), loss))
        lrs = jnp.asarray([p["learning_rate"] for p in lane_params],
                          jnp.float32)
        lams = jnp.asarray([p["reg_lambda"] for p in lane_params],
                           jnp.float32)
        gams = jnp.asarray([p["gamma"] for p in lane_params], jnp.float32)
        mcws = jnp.asarray([p["min_child_weight"] for p in lane_params],
                           jnp.float32)
        depth, rounds, B = (int(p0["max_depth"]), int(p0["num_rounds"]),
                            int(p0["max_bins"]))
        hist_mode = _hist_engine(n_tr, stacked=True)
        from transmogrifai_tpu.utils import flops
        if hist_mode == "sorted":
            per_tree = sum(4.0 * n_tr * d * B + 10.0 * n_tr
                           + 12.0 * (2 ** lv) * d * B
                           for lv in range(depth))
        else:
            per_tree = sum(5.0 * n_tr * d + 4.0 * n_tr
                           + 12.0 * (2 ** lv) * d * B
                           for lv in range(depth))
        flops.add("tree", k * L * rounds * n_out * per_tree)
        subsample = 1.0 if self.bootstrap else float(p0["subsample"])
        draws = self._forest_draws(n_tr, k * L * rounds * n_out,
                                   n_rounds=rounds, seed=int(p0["seed"]),
                                   subsample=subsample, hist_mode=hist_mode)
        classifier = self.kind.endswith("classifier")
        scores = train_score_stacked(
            Xb, y, w, Xva, bases, lrs, lams, gams, mcws, draws,
            n_rounds=rounds, max_depth=depth, n_bins=B, loss=loss,
            subsample=subsample,
            colsample=float(p0["colsample"]), bootstrap=self.bootstrap,
            seed=int(p0["seed"]), hist=hist_mode,
            forest_margin=self.bootstrap and classifier, n_out=n_out)
        if self.bootstrap and not classifier:
            # a regression forest's trees fit residuals of the fold's mean;
            # its prediction adds the mean back
            return scores + bases[:, None, None]
        return scores

    # -- winner refit (round 9) ----------------------------------------------
    def refit_winner(self, X, y, w, params, *, warm=None, lane=None,
                     hints=None):
        """Full-data winner refit reusing the sweep's dataset-level bin
        codes: ``hints["bin_plans"]`` carries ``fold_sweep_plan``'s
        ``{max_bins: (edges, codes, max_bins)}`` computed on this SAME
        full training matrix, so the refit's duplicate quantile sort +
        searchsorted pass is deleted outright — ``fit_arrays`` would
        recompute byte-identical edges and codes from the identical
        ``X``, making the reuse bitwise-exact, not approximate. Loss/
        n_out/base are recomputed exactly as the serial refit always did
        (an O(1) scalar pull). Trees have no parameter warm start —
        ensemble growth cannot resume from fold trees."""
        merged = {self._ALIASES.get(k, k): v for k, v in params.items()}
        mb = int({**self.default_params, **self.params, **merged}
                 ["max_bins"])
        binned = ((hints or {}).get("bin_plans") or {}).get(mb)
        model = self.fit_arrays(X, y, w, params, _binned=binned)
        return model, binned is not None


class OpGBTClassifier(_TreePredictor):
    """Gradient-boosted classification trees (Spark OpGBTClassifier parity;
    one-vs-all logistic boosting for multiclass)."""
    kind = "gbt_classifier"
    loss = "logistic"
    bootstrap = False


class OpGBTRegressor(_TreePredictor):
    kind = "gbt_regressor"
    loss = "squared"
    bootstrap = False


class OpXGBoostClassifier(OpGBTClassifier):
    """XGBoost-parity surface (eta, lambda, gamma, min_child_weight,
    subsample/colsample) on the native histogram booster."""


class OpXGBoostRegressor(OpGBTRegressor):
    pass


class _ForestMixin:
    bootstrap = True

    default_params = {**_TreePredictor.default_params,
                      "num_rounds": 50, "max_depth": 12, "learning_rate": 1.0,
                      "subsample": 1.0, "colsample": 0.7,
                      "reg_lambda": 1e-3}


class OpRandomForestClassifier(_ForestMixin, _TreePredictor):
    """Bootstrap-aggregated probability trees (Spark RF parity).

    Multiclass grows per-class regression trees on the one-hot target with
    the class axis vmapped inside ONE compiled ensemble program (not K
    sequential host-loop fits)."""
    kind = "rf_classifier"
    loss = "squared"      # CART variance-reduction on the 0/1 target

    def _loss_and_nout(self, y, _stats=None):
        y_max = (_stats[0] if _stats is not None
                 else np.asarray(jnp.max(y)))
        n_classes = int(y_max) + 1
        if n_classes <= 2:
            return "squared", 1, 0.0
        return "squared_onehot", n_classes, 0.0

    def _stacked_base_mode(self, loss: str) -> str:
        return "zero"  # class-probability trees grow from a zero margin


class OpRandomForestRegressor(_ForestMixin, _TreePredictor):
    kind = "rf_regressor"
    loss = "squared"


class OpDecisionTreeClassifier(OpRandomForestClassifier):
    """Single CART tree: forest of one, no bootstrap, all features."""
    default_params = {**OpRandomForestClassifier.default_params,
                      "num_rounds": 1, "colsample": 1.0}

    def fit_arrays(self, X, y, w, params, _binned=None, _lnb=None):
        params = {**params, "num_rounds": 1, "colsample": 1.0}
        self.bootstrap = False  # a single tree sees the full sample
        try:
            return super().fit_arrays(X, y, w, params, _binned=_binned,
                                      _lnb=_lnb)
        finally:
            self.bootstrap = True


class OpDecisionTreeRegressor(OpRandomForestRegressor):
    default_params = {**OpRandomForestRegressor.default_params,
                      "num_rounds": 1, "colsample": 1.0}

    def fit_arrays(self, X, y, w, params, _binned=None, _lnb=None):
        params = {**params, "num_rounds": 1, "colsample": 1.0}
        self.bootstrap = False
        try:
            return super().fit_arrays(X, y, w, params, _binned=_binned,
                                      _lnb=_lnb)
        finally:
            self.bootstrap = True


