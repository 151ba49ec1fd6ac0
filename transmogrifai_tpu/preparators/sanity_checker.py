"""SanityChecker: automated feature validation & cleaning.

Parity: reference ``core/.../stages/impl/preparators/SanityChecker.scala:
232-656`` (+ ``SanityCheckerMetadata``, ``DerivedFeatureFilterUtils``,
``MinVarianceFilter``) — a BinaryEstimator (label RealNN, features OPVector
-> cleaned OPVector) that samples rows (``sampleUpperLimit``), computes
per-column statistics, label correlations (Pearson or Spearman), the
feature-feature correlation matrix, and per-categorical-group contingency
stats (Cramér's V, PMI, association-rule confidence), then **drops columns**
failing: minVariance, max/min label correlation, maxFeatureCorr (drop the
later column of a too-correlated pair, ``DerivedFeatureFilterUtils.scala:
376-380``), maxCramersV, maxRuleConfidence — with whole-feature-group
removal (text shared-hash columns protected per ``protectTextSharedHash``).
Emits a ``SanityCheckerSummary`` consumed by ModelInsights.

TPU-first: every statistic is a monoid pytree reduced over the device mesh —
masked moments ride one fused ``shard_map`` + ``psum/pmin/pmax`` program
(the analog of the reference's ``reduceByKey(_+_)`` at
``SanityChecker.scala:265-272``), the feature-feature matrix is a single
[d,n]x[n,d] MXU matmul with the feature axis shardable over the "model"
mesh axis (the O(d²) wide-feature decomposition, SURVEY §5), and ALL
categorical contingency tables compute at once as ``X^T @ onehot(y)``. Only
tiny [d]-shaped results reach the host for the drop decisions. Mesh-padded
rows carry weight 0 and contribute monoid identity.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.parallel import mesh as pmesh
from transmogrifai_tpu.parallel.collectives import (
    mesh_reduce_stats, tree_pmax, tree_pmin, tree_psum,
)
from transmogrifai_tpu.stages.base import DeviceTransformer, Estimator
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.utils.stats import contingency_stats
from transmogrifai_tpu.vector_metadata import (
    VectorColumnMetadata, VectorMetadata,
)

__all__ = ["SanityChecker", "DropIndicesModel", "SanityCheckerSummary"]

_BIG = jnp.float32(3.4e38)

#: feature types whose shared-hash columns are protected from group removal
#: (reference DerivedFeatureFilterUtils.isTextSharedHash)
_TEXTY = ("Text", "TextArea", "TextMap", "TextAreaMap")


def _is_text_shared_hash(cm) -> bool:
    return (cm.grouping is None and cm.indicator_value is None
            and any(t in _TEXTY for t in cm.parent_feature_type))


@dataclass
class ColumnStats:
    name: str
    mean: float
    variance: float
    min: float
    max: float
    corr_label: float
    dropped: bool = False
    reasons: list = field(default_factory=list)


@dataclass
class SanityCheckerSummary:
    n_rows: int
    names: list
    column_stats: list            # list[ColumnStats]
    categorical_stats: dict       # group -> {"cramersV":, "maxRuleConfidence":, "supports":}
    dropped: list                 # names
    feature_corr: Optional[list] = None   # d x d matrix (when computed)
    correlation_type: str = "pearson"
    sample_fraction: float = 1.0

    def to_json(self) -> dict:
        return {
            "nRows": self.n_rows,
            "correlationType": self.correlation_type,
            "sampleFraction": self.sample_fraction,
            "columnStats": [{
                "name": c.name, "mean": c.mean, "variance": c.variance,
                "min": c.min, "max": c.max, "corrLabel": c.corr_label,
                "dropped": c.dropped, "reasons": list(c.reasons),
            } for c in self.column_stats],
            "categoricalStats": self.categorical_stats,
            "dropped": list(self.dropped),
        }


def _local_moments(X, Xr, y, yr, m):
    """Per-shard monoid stats: sums/extrema of the raw matrix plus the
    correlation cross-moments on the (possibly rank-transformed) matrix.
    Masked rows contribute identity (0 for sums, ±inf for extrema)."""
    mm = m[:, None]
    ym = yr * m
    return {
        "cnt": jnp.sum(m),
        "sx": jnp.sum(X * mm, axis=0),
        "sx2": jnp.sum(X * X * mm, axis=0),
        "mn": jnp.min(jnp.where(mm > 0, X, _BIG), axis=0),
        "mx": jnp.max(jnp.where(mm > 0, X, -_BIG), axis=0),
        "sr": jnp.sum(Xr * mm, axis=0),
        "sr2": jnp.sum(Xr * Xr * mm, axis=0),
        "sry": jnp.sum(Xr * ym[:, None], axis=0),
        "sy": jnp.sum(ym),
        "sy2": jnp.sum(yr * ym),
    }


def _combine_moments(tree):
    """Mixed-monoid mesh combine: psum the sums, pmin/pmax the extrema."""
    out = tree_psum({k: v for k, v in tree.items() if k not in ("mn", "mx")})
    out["mn"] = tree_pmin({"mn": tree["mn"]})["mn"]
    out["mx"] = tree_pmax({"mx": tree["mx"]})["mx"]
    return out


_jit_moments = jax.jit(_local_moments)


def _rank_1d(x):
    """Tie-averaged ranks of one vector (Spearman building block)."""
    s = jnp.sort(x)
    left = jnp.searchsorted(s, x, side="left")
    right = jnp.searchsorted(s, x, side="right")
    return 0.5 * (left + right + 1).astype(jnp.float32)


@jax.jit
def _ranks(X, m):
    """Tie-averaged ranks per column. Masked rows are pushed to +inf so
    every real row's rank is unaffected; their own ranks are weighted out
    downstream."""
    return jax.vmap(_rank_1d, in_axes=1, out_axes=1)(
        jnp.where(m[:, None] > 0, X, _BIG))


@jax.jit
def _rank_vec(y, m):
    return _rank_1d(jnp.where(m > 0, y, _BIG))


@jax.jit
def _contingency(X, y_onehot_masked):
    return X.T @ y_onehot_masked


@functools.partial(jax.jit, static_argnames=("in_sharding", "out_sharding"))
def _feature_corr_jit(Xr, m, in_sharding=None, out_sharding=None):
    mm = m[:, None]
    cnt = jnp.maximum(jnp.sum(m), 1.0)
    mean = jnp.sum(Xr * mm, axis=0) / cnt
    Xc = (Xr - mean) * mm
    sd = jnp.sqrt(jnp.maximum(jnp.sum(Xc * Xc, axis=0) / cnt, 1e-12))
    Z = Xc / sd
    if in_sharding is not None:
        Z = jax.lax.with_sharding_constraint(Z, in_sharding)
        C = (Z.T @ Z) / cnt
        return jax.lax.with_sharding_constraint(C, out_sharding)
    return (Z.T @ Z) / cnt


def _feature_corr(Xr, m, mesh_ctx):
    """Weighted correlation matrix of (rank-)columns as one MXU matmul.
    Under a mesh: rows contract over "data" (XLA inserts the psum) and the
    [d,d] output shards its leading axis over "model" — the feature-width
    (tensor-parallel-like) decomposition for O(d²) stats. Shardings ride as
    hashable static args so the compiled program caches per shape+mesh."""
    if mesh_ctx is None:
        return _feature_corr_jit(Xr, m)
    return _feature_corr_jit(
        Xr, m,
        in_sharding=NamedSharding(mesh_ctx.mesh, P(pmesh.DATA_AXIS, None)),
        out_sharding=NamedSharding(mesh_ctx.mesh, P(pmesh.MODEL_AXIS, None)))


class SanityChecker(Estimator):
    """(label, features) -> cleaned features."""

    in_types = (ft.RealNN, ft.OPVector)
    out_type = ft.OPVector

    def __init__(self,
                 max_correlation: float = 0.95,
                 min_correlation: float = 0.0,
                 min_variance: float = 1e-5,
                 max_feature_correlation: float = 0.99,
                 max_cramers_v: float = 0.95,
                 max_rule_confidence: float = 1.0,
                 min_required_rule_support: float = 0.001,
                 remove_feature_group: bool = True,
                 protect_text_shared_hash: bool = True,
                 correlation_type: str = "pearson",
                 correlation_exclusion: str = "none",
                 compute_feature_corr: bool = True,
                 max_feature_corr_width: int = 4096,
                 sample_upper_limit: int = 1_000_000,
                 sample_seed: int = 42,
                 categorical_label_max_classes: int = 100,
                 uid: Optional[str] = None):
        if correlation_type not in ("pearson", "spearman"):
            raise ValueError(
                f"correlation_type must be pearson|spearman, got "
                f"{correlation_type!r}")
        if correlation_exclusion not in ("none", "hashed_text"):
            raise ValueError(
                f"correlation_exclusion must be none|hashed_text, got "
                f"{correlation_exclusion!r}")
        self.max_correlation = max_correlation
        self.min_correlation = min_correlation
        self.min_variance = min_variance
        self.max_feature_correlation = max_feature_correlation
        self.max_cramers_v = max_cramers_v
        self.max_rule_confidence = max_rule_confidence
        self.min_required_rule_support = min_required_rule_support
        self.remove_feature_group = remove_feature_group
        self.protect_text_shared_hash = protect_text_shared_hash
        self.correlation_type = correlation_type
        self.correlation_exclusion = correlation_exclusion
        self.compute_feature_corr = compute_feature_corr
        self.max_feature_corr_width = max_feature_corr_width
        self.sample_upper_limit = sample_upper_limit
        self.sample_seed = sample_seed
        self.categorical_label_max_classes = categorical_label_max_classes
        super().__init__(uid=uid)

    def fit_model(self, data) -> "DropIndicesModel":
        label_name, feat_name = self.input_names
        col = data.device_col(feat_name)
        X = col.values
        meta: Optional[VectorMetadata] = col.metadata
        y = data.device_col(label_name).values
        n = data.n_rows  # logical rows (device arrays may be mesh-padded)
        d = int(X.shape[1])
        names = (meta.col_names() if meta is not None and meta.size == d
                 else [f"col_{j}" for j in range(d)])
        mask = data.row_mask()

        # ---- row-sampling cap (reference sampleUpperLimit, :60-92) ---------
        sample_fraction = 1.0
        if n > self.sample_upper_limit:
            rng = np.random.default_rng(self.sample_seed)
            idx = np.sort(rng.choice(n, size=self.sample_upper_limit,
                                     replace=False))
            jidx = jnp.asarray(idx)
            X, y = X[jidx], y[jidx]
            mask = jnp.ones(idx.size, jnp.float32)
            X = pmesh.pad_and_shard_rows(X)
            y = pmesh.pad_and_shard_rows(y)
            mask = pmesh.pad_and_shard_rows(mask)
            sample_fraction = self.sample_upper_limit / n
            n_used = self.sample_upper_limit
        else:
            n_used = n

        # ---- moment + correlation monoid pass ------------------------------
        if self.correlation_type == "spearman":
            Xr = _ranks(X, mask)
            yr = _rank_vec(y, mask)
        else:
            Xr, yr = X, y

        ctx = pmesh.current_mesh()
        rows = int(X.shape[0])
        use_mesh = ctx is not None and rows % ctx.n_data == 0
        if use_mesh:
            stats = mesh_reduce_stats(ctx, _local_moments, X, Xr, y, yr, mask,
                                      reduce=_combine_moments)
        else:
            stats = _jit_moments(X, Xr, y, yr, mask)
        stats = {k: np.asarray(v, np.float64) for k, v in stats.items()}
        cnt = max(stats["cnt"], 1.0)
        mean = stats["sx"] / cnt
        var = np.maximum(stats["sx2"] / cnt - mean ** 2, 0.0)
        xmin, xmax = stats["mn"], stats["mx"]
        mean_r = stats["sr"] / cnt
        var_r = np.maximum(stats["sr2"] / cnt - mean_r ** 2, 1e-12)
        ymean = stats["sy"] / cnt
        yvar = max(stats["sy2"] / cnt - ymean ** 2, 1e-12)
        cov = stats["sry"] / cnt - mean_r * ymean
        corr = cov / (np.sqrt(var_r) * np.sqrt(yvar))

        # columns excluded from every correlation rule (reference
        # CorrelationExclusion.HashedText)
        corr_excluded: set[int] = set()
        if self.correlation_exclusion == "hashed_text" and meta is not None \
                and meta.size == d:
            corr_excluded = {j for j, cm in enumerate(meta.columns)
                            if _is_text_shared_hash(cm)}

        # categorical groups from provenance metadata
        groups: dict[str, list[int]] = {}
        if meta is not None and meta.size == d:
            for j, cm in enumerate(meta.columns):
                g = cm.feature_group()
                if g is not None and cm.indicator_value is not None:
                    groups.setdefault(g, []).append(j)

        # contingency stats per group via one matmul for all columns
        cat_stats: dict[str, dict] = {}
        y_np = np.asarray(y)
        m_np = np.asarray(mask)
        classes = np.unique(y_np[m_np > 0])
        if groups and classes.size <= self.categorical_label_max_classes \
                and classes.size >= 2:
            y_onehot = (y_np[:, None] == classes[None, :]).astype(np.float32)
            y_onehot *= m_np[:, None]  # padded rows contribute nothing
            M = np.asarray(_contingency(X, jnp.asarray(y_onehot)), np.float64)
            for g, idxs in groups.items():
                cs = contingency_stats(M[idxs])
                cat_stats[g] = {
                    "cramersV": cs.cramers_v,
                    "mutualInfo": cs.mutual_info,
                    "maxRuleConfidences": cs.max_rule_confidences.tolist(),
                    "supports": cs.supports.tolist(),
                }

        # feature-feature correlation matrix (one MXU matmul)
        fcorr = None
        if self.compute_feature_corr and d <= self.max_feature_corr_width:
            fcorr = np.asarray(_feature_corr(Xr, mask, ctx if use_mesh
                                             else None), np.float64)

        # ---- drop decisions (reference DerivedFeatureFilterUtils.
        # reasonsToRemove ordering) ------------------------------------------
        col_stats = [ColumnStats(names[j], mean[j], var[j], xmin[j], xmax[j],
                                 float("nan") if j in corr_excluded
                                 else corr[j])
                     for j in range(d)]
        for j, c in enumerate(col_stats):
            if c.variance <= self.min_variance:
                c.reasons.append("variance too low")
            if j in corr_excluded:
                continue
            acorr = abs(c.corr_label)
            if np.isfinite(acorr):
                if acorr > self.max_correlation:
                    c.reasons.append("label correlation too high (leakage)")
                elif acorr < self.min_correlation:
                    c.reasons.append("label correlation too low")
        if fcorr is not None and self.max_feature_correlation < 1.0:
            # drop the LATER column of a too-correlated pair (reference:
            # featureCorrs.take(cl.index) — only earlier columns considered);
            # one vectorized pass over the strict lower triangle, Python only
            # touches actual hits
            lower = np.tril(fcorr, -1)
            A = np.where(np.isfinite(lower), np.abs(lower), 0.0)
            if corr_excluded:
                excl = np.zeros(d, bool)
                excl[list(corr_excluded)] = True
                A[excl, :] = 0.0
                A[:, excl] = 0.0
            over = A > self.max_feature_correlation
            first_i = np.argmax(over, axis=1)  # first too-correlated earlier col
            for j in np.nonzero(over.any(axis=1))[0]:
                i = int(first_i[j])
                col_stats[j].reasons.append(
                    f"feature correlation {fcorr[j, i]:.4f} with "
                    f"{names[i]} too high")
        group_dropped: set[str] = set()
        for g, idxs in groups.items():
            st = cat_stats.get(g)
            if st is None:
                continue
            if st["cramersV"] > self.max_cramers_v:
                group_dropped.add(g)
                for j in idxs:
                    col_stats[j].reasons.append("Cramér's V too high (leakage)")
            else:
                conf = np.asarray(st["maxRuleConfidences"])
                sup = np.asarray(st["supports"])
                if np.any((conf >= self.max_rule_confidence)
                          & (sup >= self.min_required_rule_support)):
                    group_dropped.add(g)
                    for j in idxs:
                        col_stats[j].reasons.append(
                            "association rule confidence too high")
        if self.remove_feature_group and meta is not None and meta.size == d:
            # a label-corr/Cramér's-V drop on any indicator removes its whole
            # group (reference parentCramersV/parentCorr), except protected
            # text shared-hash columns
            for g, idxs in groups.items():
                if g in group_dropped:
                    continue
                if any("leakage" in r for j in idxs
                       for r in col_stats[j].reasons):
                    for j in idxs:
                        if self.protect_text_shared_hash and \
                                _is_text_shared_hash(meta.columns[j]):
                            continue
                        if not col_stats[j].reasons:
                            col_stats[j].reasons.append(
                                "feature group removed (leaky sibling)")

        keep = [j for j, c in enumerate(col_stats) if not c.reasons]
        if not keep:
            # never drop everything: keep the highest-|corr| column
            with np.errstate(invalid="ignore"):
                acorr = np.abs(corr)
            acorr[~np.isfinite(acorr)] = -1.0
            j = int(np.argmax(acorr))
            col_stats[j].reasons.clear()
            keep = [j]
        for c in col_stats:
            c.dropped = bool(c.reasons)

        summary = SanityCheckerSummary(
            n_rows=n_used, names=names, column_stats=col_stats,
            categorical_stats=cat_stats,
            dropped=[c.name for c in col_stats if c.dropped],
            feature_corr=fcorr.tolist() if fcorr is not None else None,
            correlation_type=self.correlation_type,
            sample_fraction=sample_fraction)
        pad = bucketed_width(len(keep)) - len(keep)
        new_meta = _padded(meta.select(keep), pad) \
            if meta is not None and meta.size == d else None
        return DropIndicesModel(keep_indices=keep, out_meta=new_meta,
                                summary=summary, pad=pad)


#: which columns pass is decided by the data (a hash bucket that two rows
#: fill passes the variance rule, one that a single row fills does not), so
#: two samples of one table keep widths a few columns apart, and every
#: program downstream is compiled for its width. From ``_BUCKET_FROM``
#: kept columns on, the checked vector is therefore filled up with zero
#: columns to a whole number of ``_WIDTH_BUCKET``-column tiles (at most 1
#: part in 32 of it): a retrain on a fresh sample finds its programs
#: compiled. A zero column has no variance, takes no weight and no split.
_WIDTH_BUCKET = 128
_BUCKET_FROM = 4096
_PAD_FEATURE = "sanityCheckerPadding"
#: a column index no vector has: the gather fills it with zeros
_NO_COLUMN = np.iinfo(np.int32).max
_TAKE_BLOCK_ROWS = 4096


def bucketed_width(kept: int) -> int:
    """The width of the checked vector that keeps ``kept`` columns."""
    if kept < _BUCKET_FROM:
        return kept
    return -(-kept // _WIDTH_BUCKET) * _WIDTH_BUCKET


def _padded(meta: VectorMetadata, pad: int) -> VectorMetadata:
    """``meta`` with the provenance of ``pad`` zero columns appended."""
    if not pad:
        return meta
    zero = VectorColumnMetadata(parent_feature=(_PAD_FEATURE,),
                                parent_feature_type=("Real",),
                                descriptor_value="zero")
    return VectorMetadata(meta.name, meta.columns + (zero,) * pad,
                          meta.history).reindexed(0)


def _take_columns(X, keep):
    """``X[:, keep]``, zeros where ``keep`` is ``_NO_COLUMN``. XLA gathers
    along the minor axis through transposed copies of the whole operand (1.6
    times its bytes in temporaries: 7.4 GB for a 4.75 GB matrix), so the
    matrix is gathered a block of rows at a time into the preallocated
    result; the last block is moved back to end at the last row, and
    rewrites rows the block before it wrote."""
    n, d = X.shape
    B = min(_TAKE_BLOCK_ROWS, n)

    def body(i, out):
        start = jnp.minimum(i * B, n - B)
        rows = jax.lax.dynamic_slice(X, (start, 0), (B, d))
        return jax.lax.dynamic_update_slice(
            out, jnp.take(rows, keep, axis=1, mode="fill", fill_value=0),
            (start, 0))

    return jax.lax.fori_loop(0, -(-n // max(B, 1)), body,
                             jnp.zeros((n, keep.shape[0]), X.dtype))


class DropIndicesModel(DeviceTransformer):
    """Gathers the kept columns; reindexed provenance metadata rides along."""

    in_types = (ft.RealNN, ft.OPVector)
    out_type = ft.OPVector

    def __init__(self, keep_indices=(), out_meta: Optional[VectorMetadata] = None,
                 summary: Optional[SanityCheckerSummary] = None,
                 pad: int = 0, uid: Optional[str] = None):
        self.keep_indices = [int(i) for i in keep_indices]
        self.out_meta = out_meta
        self.summary = summary
        self.pad = int(pad)     # zero columns after the kept ones
        super().__init__(uid=uid)

    def runtime_input_names(self):
        return (self.input_names[1],) if len(self.input_names) == 2 \
            else self.input_names

    def device_params(self):
        return jnp.asarray(self.keep_indices + [_NO_COLUMN] * self.pad,
                           jnp.int32)

    def device_apply(self, params, col: fr.VectorColumn) -> fr.VectorColumn:
        meta = self.out_meta
        if meta is None and col.metadata is not None \
                and col.metadata.size == int(col.values.shape[1]):
            meta = _padded(col.metadata.select(self.keep_indices), self.pad)
        return fr.VectorColumn(_take_columns(col.values, params), meta)

    def transform_row(self, *values):
        vec = np.asarray(values[-1], dtype=np.float32)
        kept = vec[np.asarray(self.keep_indices, dtype=np.int64)]
        return np.concatenate([kept, np.zeros(self.pad, np.float32)])

    def config(self):
        return {
            "keep_indices": self.keep_indices,
            "pad": self.pad,
            "out_meta": self.out_meta.to_json() if self.out_meta else None,
            "summary": self.summary.to_json() if self.summary else None,
        }

    @classmethod
    def from_config(cls, config, uid=None):
        meta = (VectorMetadata.from_json(config["out_meta"])
                if config.get("out_meta") else None)
        return cls(keep_indices=config.get("keep_indices", ()),
                   out_meta=meta, pad=config.get("pad", 0), uid=uid)
