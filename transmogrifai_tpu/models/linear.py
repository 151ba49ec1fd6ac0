"""Linear model family: logistic regression, linear SVC, linear regression.

Parity: reference ``stages/impl/classification/{OpLogisticRegression,
OpLinearSVC}.scala`` and ``stages/impl/regression/OpLinearRegression.scala``
— same hyperparameter surface (regParam, elasticNetParam, maxIter, tol,
fitIntercept, standardization).

TPU-first: training is full-batch gradient descent (Adam) expressed as one
``lax.scan`` jitted program — dense X rides in HBM, per-step compute is a
pair of [n,d]x[d,C] matmuls on the MXU in f32. The hyperparameter grid
trains as a *stacked leading axis* under ``vmap`` (``grid_fit_arrays``):
all L1/L2 candidates descend simultaneously in one XLA program, which is
the TPU replacement for the reference's CV thread pool (SURVEY §2.7 P3).
Standardization is folded into the weights at the end so scoring needs no
scaler state. Least squares up to 4,096 features is not descended but
solved from fold-weighted Gram matrices (``_gram_folds``), as the
reference's Spark solver takes the normal equations there.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.models.base import PredictionModel, Predictor

__all__ = [
    "OpLogisticRegression", "OpLinearSVC", "OpLinearRegression",
    "LinearClassificationModel", "LinearRegressionModel",
]


# ---------------------------------------------------------------------------
# shared trainer
# ---------------------------------------------------------------------------

def _moments(X, w):
    """Weighted mean and deviation of every column, and which columns vary
    at all under the weighting."""
    wsum = jnp.maximum(jnp.sum(w), 1.0)
    mu = jnp.sum(X * w[:, None], axis=0) / wsum
    var = jnp.sum(((X - mu) ** 2) * w[:, None], axis=0) / wsum
    sd = jnp.sqrt(jnp.maximum(var, 1e-12))
    return mu, jnp.where(sd < 1e-6, 1.0, sd), var > 1e-12


def _standardize_stats(X, w):
    return _moments(X, w)[:2]


#: the products with the feature matrix run in three bfloat16 passes: no
#: standardized copy of the matrix is made (a lane's mean and scale are
#: folded into its weights), and one pass would round what is left of a
#: column's offset between the lanes by more than the column's spread
_X_PRECISION = jax.lax.Precision.HIGH


#: a fitted model's score of a row is an exact product: the rows are raw
#: (a text length of thousands is no bfloat16 number, and its weight is not
#: small), and one bfloat16 pass moved a served probability by up to 8e-2
#: against the same weights applied in float32 (chip runs, PR 33); the
#: product is as wide as the classes, so the passes cost nothing
_SCORE_PRECISION = "highest"


def _lane_stats(X, wf, standardize: bool):
    """Per weight row of ``wf [F, n]``: the weight sum, the weighted mean
    and deviation of every column, and which columns vary at all under it;
    reductions over ``X``, no copy of it."""
    F, d = wf.shape[0], X.shape[1]
    wsum = jnp.maximum(jnp.sum(wf, axis=1), 1.0)
    if not standardize:
        return wsum, jnp.zeros((F, d)), jnp.ones((F, d)), jnp.ones((F, d))
    mu, sd, varies = jax.vmap(lambda w: _moments(X, w))(wf)
    return wsum, mu, sd, varies.astype(X.dtype)


#: the one-pass step (``_onepass_terms``) serves matrices this wide and
#: wider: the narrowest width timed on the v5e, where the kernel's step
#: took a third of the XLA program's (PERF.md, section 6); the tables of
#: 28 and 54 columns keep the XLA program
_ONEPASS_MIN_D = 256
#: bytes of the float32 rows of one block of the one-pass step, and the
#: most rows a block takes (the lanes' per-row terms of a narrow matrix's
#: block would outgrow VMEM)
_ONEPASS_BLOCK_BYTES = 8 << 20
_ONEPASS_MAX_ROWS = 4096
#: lanes of the one-pass step pad to whole bfloat16 sublane tiles
_ONEPASS_LANE_TILE = 16
#: VMEM the one-pass kernel asks Mosaic for, of the v5e's 128 MiB
_ONEPASS_VMEM_LIMIT = 100 << 20
#: bytes of VMEM the kernel takes an element of its block of rows, of its
#: lanes' weights (``P x d``) and of their per-row terms (``P x b``), the
#: columns padded to whole 128-lane tiles: at or above what Mosaic asked
#: for on the v5e at 29 shapes of 256 to 65,536 columns and 16 to 1,440
#: lanes x classes (PERF.md, section 6)
_VMEM_PER_ROW_ELEMENT = 20
_VMEM_PER_WEIGHT = 16
_VMEM_PER_TERM = 8


def _one_pass_ok(X, lanes: int, outputs: int) -> bool:
    """Whether a descent of ``lanes`` lanes of ``outputs`` columns each
    over ``X`` takes the one-pass step: on a TPU, off a mesh (row-sharded
    ``X`` keeps the XLA program), from ``_ONEPASS_MIN_D`` columns, and
    where the kernel fits its VMEM (``_onepass_vmem_bytes``)."""
    from transmogrifai_tpu.parallel import mesh as pmesh
    n, d = (int(s) for s in X.shape)
    return (jax.default_backend() == "tpu" and pmesh.current_mesh() is None
            and d >= _ONEPASS_MIN_D
            and _onepass_vmem_bytes(n, d, lanes, outputs)
            <= _ONEPASS_VMEM_LIMIT)


def _onepass_vmem_bytes(n: int, d: int, lanes: int, outputs: int) -> int:
    """An upper bound on the VMEM the one-pass kernel asks for: its block
    of rows (two buffers) with their centred and split copies, the lanes'
    split weights with the gradient's accumulator, and the lanes' per-row
    terms. It grows with the columns x lanes x classes and, past 16,384
    columns, with a block that cannot shrink below one 128-row tile
    (``tests/test_tpu_compile.py`` compiles both sides of the limit)."""
    b, dp = _onepass_rows(n, d), -(-d // 128) * 128
    P = outputs * -(-lanes // _ONEPASS_LANE_TILE) * _ONEPASS_LANE_TILE
    return (_VMEM_PER_ROW_ELEMENT * b * dp + _VMEM_PER_WEIGHT * P * dp
            + _VMEM_PER_TERM * P * b)


def _onepass_rows(n: int, d: int) -> int:
    """Rows of a block of the one-pass step: ``_ONEPASS_BLOCK_BYTES`` of
    float32 rows in whole 128-row tiles, at least one tile and no more
    tiles than ``n`` holds whole."""
    rows = min(_ONEPASS_BLOCK_BYTES // (4 * d), _ONEPASS_MAX_ROWS)
    return int(max(128, min(rows // 128 * 128, n // 128 * 128)))


def _split_bf16(a):
    """``a`` as the sum of two bfloat16 arrays: its high part, rounded to
    the nearest even on the float32 number's bits (so exact in bfloat16),
    and the rest, rounded. Split by a pair of conversions inside the
    kernel, the products read as one bfloat16 pass on the v5e, 2e-3 off
    a gradient where XLA's ``HIGH`` read 2e-5; split so, 5e-6."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _onepass_block(x, center, inv_scale, w_cat, off, w_rows, y, y_mean, y_sd,
                   *, loss_kind: str, lanes: int):
    """One block of rows of the one-pass step. ``x [b, d]`` raw rows,
    centred and scaled here; ``w_cat [2P, d]`` the lanes' weights split in
    bfloat16 (the high parts over the low ones), ``P = C x lanes`` rows
    class-major (row ``c * lanes + l``); ``off [P, 1]``; ``w_rows [lanes,
    b]`` each lane's row weights; ``y [1, b]``;
    ``y_mean``/``y_sd [lanes, 1]`` the squared loss's target moments.
    Each product is three bfloat16 products summed in float32
    (``Precision.HIGH``), two MXU passes over the rows: the low half of the
    lanes rides the high half's pass. Returns the block's share of the
    weight gradient ``[P, d]``, of the margins' gradient ``[P, b]`` (the
    bias gradient once summed over rows) and of the loss ``[lanes, b]``."""
    f32 = jnp.float32
    x_hi, x_lo = _split_bf16((x - center) * inv_scale)
    P = w_cat.shape[0] // 2
    nt = (((1,), (1,)), ((), ()))
    z2 = jax.lax.dot_general(w_cat, x_hi, nt, preferred_element_type=f32)
    z = z2[:P] + z2[P:] + jax.lax.dot_general(
        w_cat[:P], x_lo, nt, preferred_element_type=f32) + off    # [P, b]
    if loss_kind == "softmax":
        zs = [z[c * lanes:(c + 1) * lanes] for c in range(P // lanes)]
        top = functools.reduce(jnp.maximum, zs)
        es = [jnp.exp(zc - top) for zc in zs]
        total = functools.reduce(jnp.add, es)
        hot = [(y == c).astype(f32) for c in range(len(zs))]
        loss = top + jnp.log(total) - functools.reduce(
            jnp.add, [h * zc for h, zc in zip(hot, zs)])
        dz = jnp.concatenate([e / total - h for e, h in zip(es, hot)])
        r = jnp.concatenate([w_rows] * len(zs)) * dz
    elif loss_kind == "hinge":
        sign = 2.0 * y - 1.0
        margin = 1.0 - sign * z
        loss = jnp.maximum(0.0, margin)
        r = w_rows * jnp.where(margin > 0.0, -sign, 0.0)
    else:  # squared, against the lane's standardized target
        diff = z - (y - y_mean) / y_sd
        loss = 0.5 * diff * diff
        r = w_rows * diff
    r_hi, r_lo = _split_bf16(r)
    g2 = jnp.dot(jnp.concatenate([r_hi, r_lo]), x_hi,
                 preferred_element_type=f32)
    g = g2[:P] + g2[P:] + jnp.dot(r_hi, x_lo, preferred_element_type=f32)
    return g, r, loss * w_rows


def _onepass_terms(X, center, inv_scale, We, off, w_rows, y_row, y_mean,
                   y_sd, *, loss_kind: str):
    """The data term of every lane of ``_linear_descent`` and its gradient
    from ONE read of ``X [n, d]``: a Pallas kernel walks the whole
    ``_onepass_rows`` blocks in order, each loaded into VMEM once for the
    lanes' margins, loss derivative and ``Xs^T r``, with the gradient
    accumulated in VMEM over the grid; the rows past the last whole block
    (all of them, in a table of fewer than 128) take the same
    ``_onepass_block`` in XLA. ``We [L, d, C]`` and ``off
    [L, C]`` are the lanes' weights on the centred and scaled matrix and
    their offsets; ``w_rows [Lp, n]``, ``y_mean``/``y_sd [Lp, 1]`` come
    lane-padded from ``_onepass_rows_of``. Returns the row-weighted sums
    ``(loss [L], dWe [L, d, C], db [L, C])``, each lane's still to be
    divided by its weight sum. Whole-number weights sum exactly, as in
    ``objective``: where a fold's labels are exactly balanced, its
    intercept's gradient at zero is exactly 0, and Adam would make a whole
    step of any rounding left there."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, d = X.shape
    L, _, C = We.shape
    Lp = w_rows.shape[0]
    P = C * Lp
    WT = jnp.zeros((C, Lp, d), jnp.float32).at[:, :L].set(
        jnp.transpose(We, (2, 0, 1))).reshape(P, d)
    w_cat = jnp.concatenate(_split_bf16(WT))
    offT = jnp.zeros((C, Lp), jnp.float32).at[:, :L].set(off.T).reshape(P, 1)
    center, inv_scale = center.reshape(1, d), inv_scale.reshape(1, d)
    block = functools.partial(_onepass_block, loss_kind=loss_kind, lanes=Lp)
    b = _onepass_rows(n, d)
    whole = n // b

    def kernel(x_ref, c_ref, s_ref, w_ref, off_ref, wr_ref, y_ref, ym_ref,
               ysd_ref, g_ref, r_ref, l_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            g_ref[...] = jnp.zeros_like(g_ref)
            r_ref[...] = jnp.zeros_like(r_ref)
            l_ref[...] = jnp.zeros_like(l_ref)

        g, r, loss = block(x_ref[...], c_ref[...], s_ref[...], w_ref[...],
                           off_ref[...], wr_ref[...], y_ref[...],
                           ym_ref[...], ysd_ref[...])
        g_ref[...] += g
        r_ref[...] += r
        l_ref[...] += loss

    fixed = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))  # noqa: E731
    rows = lambda lead: pl.BlockSpec((lead, b), lambda i: (0, i))  # noqa: E731
    if not whole:   # a table of fewer rows than one block is all tail
        g = jnp.zeros((P, d), jnp.float32)
        bias, loss = jnp.zeros(P, jnp.float32), jnp.zeros(Lp, jnp.float32)
    else:
        g, r, lw = pl.pallas_call(
            kernel, grid=(whole,),
            in_specs=[pl.BlockSpec((b, d), lambda i: (i, 0)), fixed((1, d)),
                      fixed((1, d)), fixed((2 * P, d)), fixed((P, 1)),
                      rows(Lp), rows(1), fixed((Lp, 1)), fixed((Lp, 1))],
            out_specs=[fixed((P, d)), fixed((P, b)), fixed((Lp, b))],
            out_shape=[jax.ShapeDtypeStruct((P, d), jnp.float32),
                       jax.ShapeDtypeStruct((P, b), jnp.float32),
                       jax.ShapeDtypeStruct((Lp, b), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_ONEPASS_VMEM_LIMIT),
            interpret=jax.default_backend() != "tpu",
        )(X, center, inv_scale, w_cat, offT, w_rows, y_row, y_mean, y_sd)
        bias, loss = jnp.sum(r, axis=1), jnp.sum(lw, axis=1)
    if n > whole * b:
        lo = whole * b
        gt, rt, lt = block(X[lo:], center, inv_scale, w_cat, offT,
                           w_rows[:, lo:], y_row[:, lo:], y_mean, y_sd)
        g, bias, loss = g + gt, bias + jnp.sum(rt, axis=1), \
            loss + jnp.sum(lt, axis=1)
    dWe = jnp.transpose(g.reshape(C, Lp, d)[:, :L], (1, 2, 0))
    return loss[:L], dWe, bias.reshape(C, Lp)[:, :L].T


def _onepass_rows_of(w, y, y_mean, y_sd):
    """The one-pass step's row operands, made once a descent: each lane's
    row weights ``w [L, n]`` and the squared loss's target moments ``[L]``,
    padded with empty lanes to whole ``_ONEPASS_LANE_TILE`` lanes, and the
    labels as one row."""
    pad = -w.shape[0] % _ONEPASS_LANE_TILE

    def lane(a, fill):
        return jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)
    return (lane(w, 0.0), y[None, :], lane(y_mean[:, None], 0.0),
            lane(y_sd[:, None], 1.0))


def _linear_descent(X, y, wf, reg_param, elastic_net, W_init, b_init, *,
                    loss_kind: str, n_classes: int, max_iter: int,
                    fit_intercept: bool, standardize: bool):
    """THE first-order linear trainer: ``F`` row weightings of one resident
    matrix (``wf [F, n]``: a fold is a weighting, 0 on the rows it leaves
    out) x ``G`` grid points (``reg_param``, ``elastic_net`` ``[G]``) as
    ``F x G`` lanes of one Adam descent. Every lane reads the one ``X [n,
    d]``: a lane's standardization (its weighting's mean and scale) and, for
    the squared loss, its target's, are folded into its weights, so one
    product ``X @ [d, lanes x classes]`` a step serves all lanes and no
    lane-sized copy of ``X`` exists. Inside that product ``X`` is centred
    and scaled by the moments of ALL its rows (an elementwise operand the
    compiler fuses into the product), so a lane folds in only what its own
    moments differ by: with raw columns of large offset the fold cancels in
    float32, and the hinge loss turns that rounding into a fold metric 1e-4
    off at 40,000 rows. A column constant under a lane's weighting (a hash
    bucket whose rows all validate that fold) gets no gradient there: the
    centred product leaves rounding where the raw one left exact zeros, and
    Adam would scale it up to whole steps. Squared loss trains against the
    STANDARDIZED target: Adam(0.1) x max_iter steps can only travel
    ~max_iter/10 from 0, so raw targets of large mean or scale would
    under-fit. ``W_init``/``b_init`` (``[F*G, d, C]``/``[F*G, C]``, in
    ORIGINAL feature space) warm-start the descent; ``None`` starts from
    zero. Where ``_one_pass_ok`` holds, a step's data term and gradient
    come from ONE read of ``X`` (``_onepass_terms``); it is asked while the
    program is traced, and jax traces afresh for arguments of another
    sharding, as a row-sharded ``X`` under a mesh is. Returns
    original-space ``(W [F, G, d, C], b [F, G, C], last loss [F, G])``."""
    n, d = X.shape
    F, G = wf.shape[0], reg_param.shape[0]
    C = n_classes if loss_kind == "softmax" else 1
    one_pass = _one_pass_ok(X, F * G, C)
    wsum_f, mu_f, sd_f, live_f = _lane_stats(X, wf, standardize)
    if standardize:
        center, scale = _standardize_stats(X, jnp.ones(n, X.dtype))
    else:
        center, scale = jnp.zeros(d), jnp.ones(d)
    inv_scale = 1.0 / scale
    if loss_kind == "squared" and fit_intercept:
        ym_f = jnp.sum(y * wf, axis=1) / wsum_f
        ysd_f = jnp.sqrt(jnp.maximum(
            jnp.sum(((y - ym_f[:, None]) ** 2) * wf, axis=1) / wsum_f,
            1e-12))
    else:
        ym_f, ysd_f = jnp.zeros(F), jnp.ones(F)
    # lanes are fold-major: lane f * G + g
    rep = lambda a: jnp.repeat(a, G, axis=0)  # noqa: E731
    w, wsum, mu, sd = rep(wf), rep(wsum_f), rep(mu_f), rep(sd_f)
    live = rep(live_f)[:, :, None]
    ym, ysd = rep(ym_f), rep(ysd_f)
    reg, en = jnp.tile(reg_param, F), jnp.tile(elastic_net, F)
    if loss_kind == "softmax":
        onehot = jax.nn.one_hot(y.astype(jnp.int32), C, axis=0)  # [C, n]
    else:
        sign = 2.0 * y - 1.0

    def objective(params):
        W, b = params                              # fit space [L,d,C] [L,C]
        We = W * (scale / sd)[:, :, None]
        off = b + jnp.einsum("ld,ldc->lc", (center - mu) / sd, W)
        z = jnp.einsum("nd,ldc->cln", (X - center) * inv_scale, We,
                       precision=_X_PRECISION) \
            + off.T[:, :, None]                    # [C, L, n]: rows minor
        if loss_kind == "softmax":
            logp = jax.nn.log_softmax(z, axis=0)
            per_row = -jnp.sum(logp * onehot[:, None, :], axis=0)
        elif loss_kind == "hinge":
            per_row = jnp.maximum(0.0, 1.0 - sign * z[0])
        else:  # squared, against the lane's standardized target
            per_row = 0.5 * (z[0] - (y - ym[:, None]) / ysd[:, None]) ** 2
        data_loss = jnp.sum(per_row * w, axis=1) / wsum
        lane_loss = data_loss + penalty(W)
        # lanes share nothing but X: the sum's gradient is each lane's own
        return jnp.sum(lane_loss), lane_loss

    def penalty(W):
        l2 = 0.5 * jnp.sum(W ** 2, axis=(1, 2))
        l1 = jnp.sum(jnp.abs(W), axis=(1, 2))
        return reg * ((1.0 - en) * l2 + en * l1)

    if one_pass:
        rows_op = _onepass_rows_of(w, y, ym, ysd)

    def one_pass_value_and_grad(params):
        """``objective``'s lane losses and gradient, its data term from
        ``_onepass_terms`` and by the chain rule through ``We`` and
        ``off``, its penalty by ``jax.value_and_grad``."""
        W, b = params
        lift = (center - mu) / sd
        We = W * (scale / sd)[:, :, None]
        off = b + jnp.einsum("ld,ldc->lc", lift, W)
        data_loss, dWe, db = _onepass_terms(
            X, center, inv_scale, We, off, *rows_op, loss_kind=loss_kind)
        data_loss, dWe, db = (data_loss / wsum, dWe / wsum[:, None, None],
                              db / wsum[:, None])

        def summed_penalty(W):
            lanes = penalty(W)
            return jnp.sum(lanes), lanes
        (_, pen), dpen = jax.value_and_grad(summed_penalty, has_aux=True)(W)
        dW = dWe * (scale / sd)[:, :, None] + lift[:, :, None] \
            * db[:, None, :] + dpen
        return data_loss + pen, (dW, db)

    # columns and target centered by the same weights: the squared loss's
    # fit-space intercept is 0 whatever W is, and its gradient at 0 is
    # rounding noise, which Adam would scale up to whole steps
    train_b = fit_intercept and not (loss_kind == "squared" and standardize)
    if W_init is None:
        W0 = jnp.zeros((F * G, d, C), jnp.float32)
        b0 = jnp.zeros((F * G, C), jnp.float32)
    else:  # original space -> each lane's fit space
        W0 = W_init * sd[:, :, None] / ysd[:, None, None]
        b0 = (b_init + jnp.einsum("ld,ldc->lc", mu, W_init)
              - ym[:, None]) / ysd[:, None]
        if fit_intercept and not train_b:
            b0 = jnp.zeros_like(b0)
    opt = optax.adam(0.1)

    def step(carry, _):
        params, opt_state = carry
        if one_pass:
            lane_loss, grads = one_pass_value_and_grad(params)
        else:
            (_, lane_loss), grads = jax.value_and_grad(
                objective, has_aux=True)(params)
        # a column constant under the lane's weighting has no gradient; what
        # the centred product leaves there is rounding, which Adam would
        # scale up to whole steps
        grads = (grads[0] * live,
                 grads[1] if train_b else jnp.zeros_like(grads[1]))
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), lane_loss

    ((W, b), _), losses = jax.lax.scan(
        step, ((W0, b0), opt.init((W0, b0))), None, length=max_iter)
    # fold target standardization (squared loss) then feature
    # standardization back into original space
    W = W * ysd[:, None, None]
    b = b * ysd[:, None] + ym[:, None]
    W_orig = W / sd[:, :, None]
    b_orig = b - jnp.einsum("ld,ldc->lc", mu / sd, W)
    return (W_orig.reshape(F, G, d, C), b_orig.reshape(F, G, C),
            losses[-1].reshape(F, G))


@functools.partial(jax.jit, static_argnames=("loss_kind", "n_classes",
                                             "max_iter", "fit_intercept",
                                             "standardize"))
def _train_linear(X, y, wf, reg_param, elastic_net, *, loss_kind: str,
                  n_classes: int, max_iter: int, fit_intercept: bool,
                  standardize: bool):
    """``_linear_descent`` from zero: row weightings ``wf [F, n]`` x grid
    ``[G]``. The regularization scalars are traced, so one compiled program
    serves every grid of a shape."""
    return _linear_descent(
        X, y, wf, reg_param, elastic_net, None, None, loss_kind=loss_kind,
        n_classes=n_classes, max_iter=max_iter, fit_intercept=fit_intercept,
        standardize=standardize)


def _train_linear_from(X, y, w, reg_param, elastic_net, W_init, b_init, *,
                       loss_kind: str, max_iter: int, fit_intercept: bool,
                       standardize: bool):
    """Warm-started linear refit (round 9): one lane of ``_linear_descent``
    initialized from ``W_init``/``b_init`` given in ORIGINAL feature space
    (what the stacked fold parameters are in after fold-back). Compiled via
    ``compile_refit`` with the init buffers donated (they are dead once
    consumed)."""
    W, b, loss = _linear_descent(
        X, y, w[None], reg_param[None], elastic_net[None], W_init[None],
        b_init[None], loss_kind=loss_kind, n_classes=W_init.shape[-1],
        max_iter=max_iter, fit_intercept=fit_intercept,
        standardize=standardize)
    return W[0, 0], b[0, 0], loss[0, 0]


_WARM_PROGRAM = None  # lazily compiled (backend known only at first use)


def _linear_warm_program():
    """The donated-buffer compiled warm-refit program (SNIPPETS [1]'s
    ``donate_argnums`` compile-helper pattern): argnums 5/6 are the
    W/b init arrays, consumed exactly once."""
    global _WARM_PROGRAM
    if _WARM_PROGRAM is None:
        from transmogrifai_tpu.models.base import compile_refit
        _WARM_PROGRAM = compile_refit(
            _train_linear_from, donate_argnums=(5, 6),
            static_argnames=("loss_kind", "max_iter", "fit_intercept",
                             "standardize"))
    return _WARM_PROGRAM


@functools.partial(jax.jit, static_argnames=("n_iter", "fit_intercept",
                                             "standardize"))
def _train_logistic_newton(X, y, w, reg_param, *, n_iter: int = 15,
                           fit_intercept: bool, standardize: bool):
    """Binary L2 logistic via damped Newton/IRLS — the workhorse grid
    points (elastic_net=0) converge in ~10 steps instead of hundreds of
    first-order ones; each step is two MXU matmuls (X^T R X, X^T r) and a
    [d+1,d+1] solve. Spark's LR uses L-BFGS for the same reason; Newton is
    the TPU-friendly second-order choice because the Hessian build is a
    matmul.

    Trained in margin space u (z = Xs @ u + b); returns the equivalent
    2-column softmax weights so outputs match ``_train_linear`` exactly.
    """
    n, d = X.shape
    if standardize:
        mu, sd = _standardize_stats(X, w)
        Xs = (X - mu) / sd
    else:
        mu, sd = jnp.zeros(d), jnp.ones(d)
        Xs = X
    wsum = jnp.maximum(jnp.sum(w), 1.0)
    # softmax-space penalty reg*0.5*||W||^2 with W=[-u/2, u/2] equals
    # margin-space 0.5*(reg/2)*||u||^2
    lam = reg_param * 0.5
    Xb = jnp.concatenate([Xs, jnp.ones((n, 1), Xs.dtype)], axis=1)

    penalty_mask = jnp.ones(d + 1).at[-1].set(0.0)  # intercept unpenalized

    def step(uv, _):
        z = Xb @ uv
        p = jax.nn.sigmoid(z)
        r = w * (p - y) / wsum
        R = w * jnp.maximum(p * (1.0 - p), 1e-6) / wsum
        g = Xb.T @ r + lam * penalty_mask * uv
        H = (Xb * R[:, None]).T @ Xb
        # Levenberg damping sized to the problem: with reg_param=0 a
        # perfectly collinear one-hot block makes H singular and a 1e-8
        # ridge amplifies float32 noise to NaN within a few iterations
        H = H + jnp.diag(lam * penalty_mask + 1e-4)
        delta = jax.scipy.linalg.solve(H, g, assume_a="pos")
        if not fit_intercept:
            delta = delta.at[-1].set(0.0)
        # a non-finite step (defective solve) must not poison the carry —
        # keep the previous iterate instead
        new = uv - delta
        return jnp.where(jnp.all(jnp.isfinite(new)), new, uv), 0.0

    uv0 = jnp.zeros(d + 1, jnp.float32)
    uv, _ = jax.lax.scan(step, uv0, None, length=n_iter)
    u, bu = uv[:d], uv[d]
    # margin space -> equivalent 2-column softmax weights, unstandardized
    W = jnp.stack([-u / 2.0, u / 2.0], axis=1) / sd[:, None]
    b = jnp.stack([-bu / 2.0, bu / 2.0])
    b = b - (mu / sd) @ jnp.stack([-u / 2.0, u / 2.0], axis=1)
    return W, b, jnp.float32(0.0)


def _over_chunks(n: int, chunk: int, add, acc):
    """``add(acc, lo, size)`` over every chunk of ``chunk`` rows of ``n``,
    the last one shorter: a walk of a resident matrix that makes no array
    of its size."""
    whole, tail = divmod(n, chunk)
    acc = jax.lax.fori_loop(
        0, whole, lambda i, a: add(a, i * chunk, chunk), acc)
    return add(acc, whole * chunk, tail) if tail else acc


def _rows_of(a, lo, size, axis=0):
    return jax.lax.dynamic_slice_in_dim(a, lo, size, axis=axis)


#: bytes of one row chunk's working set in ``_newton_in_place`` (the
#: chunk's standardized rows and every lane's weighted copy of them)
_NEWTON_CHUNK_BYTES = 256 << 20


def _newton_chunk_rows(n: int, d: int, lanes: int) -> int:
    """Rows a chunk of ``_newton_in_place``: ``_NEWTON_CHUNK_BYTES`` of one
    float32 and ``lanes`` bfloat16 rows of ``d + 1`` columns, a multiple of
    512, the whole matrix where it is smaller."""
    rows = _NEWTON_CHUNK_BYTES // ((d + 1) * (4 + 2 * lanes))
    return int(min(n, max(512, rows // 512 * 512)))


def _newton_copies_fit(k: int, n: int, d: int) -> bool:
    """Whether ``_train_logistic_newton`` has room for its three
    matrix-sized arrays a fold (the fold's rows, their standardized copy
    with the ones column, the Hessian build's weighted copy) inside the
    stacked budget. Under a mesh they are sharded, and the in-place form
    (a scan over row chunks of ONE device's matrix) does not apply."""
    from transmogrifai_tpu.parallel import mesh as pmesh
    from transmogrifai_tpu.utils.devicewatch import stacked_hbm_budget
    return (pmesh.current_mesh() is not None
            or 3.0 * 4.0 * k * n * max(d, 1) <= stacked_hbm_budget())


@functools.partial(jax.jit, static_argnames=("chunk", "n_iter",
                                             "fit_intercept", "standardize"))
def _newton_in_place(X, y, wf, reg_param, *, chunk: int, n_iter: int = 15,
                     fit_intercept: bool, standardize: bool):
    """``_train_logistic_newton`` for a matrix too large to copy: ``k`` row
    weightings of the resident ``X [n, d]`` (``wf [k, n]``: a fold is a
    weighting, 0 on the rows it leaves out) x ``g`` strengths
    (``reg_param [g]``) as ``k x g`` lanes of one damped Newton iteration.
    A step walks ``X`` in chunks of ``chunk`` rows. A chunk is centred and
    scaled ONCE, by the moments of all rows, with a ones column beside it
    (``Xb``); a lane's own standardization is the map ``T`` from that to
    its weighting's moments (a diagonal and one row), so its margins are
    ``Xb @ (T uv)``, its gradient ``T^T (Xb^T r)`` and its Hessian
    ``T^T (A^T A) T``, ``A`` the chunk's rows scaled by the root of their
    curvature. Margins and gradient are exact products (``HIGHEST``: they
    decide where the iteration ends); ``A^T A`` runs in bfloat16, one pass
    of the MXU, positive semi-definite by construction (it decides only how
    fast the iteration gets there). No array of the matrix's size is made.
    Returns original-space ``(Ws [k, g, d, 2], bs [k, g, 2])``."""
    n, d = X.shape
    k, g = wf.shape[0], reg_param.shape[0]
    lanes = k * g                                # fold-major: f * g + j
    exact = jax.lax.Precision.HIGHEST

    wsum = jnp.maximum(jnp.sum(wf, axis=1), 1.0)
    if standardize:
        center, scale = _standardize_stats(X, jnp.ones(n, X.dtype))

        def add_moments(acc, lo, size):
            # about the mean of all rows, which a weighting's own mean is
            # close to: the second moment loses nothing to the offset
            Xc = _rows_of(X, lo, size) - center
            w = _rows_of(wf, lo, size, 1)
            return (acc[0] + jnp.matmul(w, Xc, precision=exact),
                    acc[1] + jnp.matmul(w, Xc * Xc, precision=exact))
        s1, s2 = _over_chunks(n, chunk, add_moments,
                              (jnp.zeros((k, d)),) * 2)
        off = s1 / wsum[:, None]
        sd = jnp.sqrt(jnp.maximum(s2 / wsum[:, None] - off * off, 1e-12))
        mu, sd = center + off, jnp.where(sd < 1e-6, 1.0, sd)
    else:
        center, scale = jnp.zeros(d), jnp.ones(d)
        mu, sd = jnp.zeros((k, d)), jnp.ones((k, d))
    # T [k, d+1, d+1]: [Xs_f, 1] = [Xg, 1] @ T_f
    T = jax.vmap(lambda m, s: jnp.zeros((d + 1, d + 1)).at[
        jnp.arange(d), jnp.arange(d)].set(scale / s).at[d, :d].set(
        (center - m) / s).at[d, d].set(1.0))(mu, sd)
    T = jnp.repeat(T, g, axis=0)                              # [lanes, ..]
    lam = jnp.tile(reg_param * 0.5, k)[:, None]
    penalty_mask = jnp.ones(d + 1).at[-1].set(0.0)  # intercept unpenalized
    w_lane = jnp.repeat(wf / wsum[:, None], g, axis=0)        # [lanes, n]

    def add_rows(V, acc, lo, size):
        Xb = jnp.concatenate([(_rows_of(X, lo, size) - center) / scale,
                              jnp.ones((size, 1), X.dtype)], axis=1)
        w = _rows_of(w_lane, lo, size, 1).T                    # [c, lanes]
        p = jax.nn.sigmoid(jnp.matmul(Xb, V, precision=exact))
        r = w * (p - _rows_of(y, lo, size)[:, None])
        root = jnp.sqrt(w * jnp.maximum(p * (1.0 - p), 1e-6))
        grams = []
        for lane in range(lanes):
            A = (Xb * root[:, lane:lane + 1]).astype(jnp.bfloat16)
            grams.append(jnp.matmul(A.T, A,
                                    preferred_element_type=jnp.float32))
        return (acc[0] + jnp.matmul(Xb.T, r, precision=exact),
                acc[1] + jnp.stack(grams))

    def step(uv, _):
        V = jnp.einsum("lde,le->dl", T, uv, precision=exact)
        acc = _over_chunks(
            n, chunk, functools.partial(add_rows, V),
            (jnp.zeros((d + 1, lanes), jnp.float32),
             jnp.zeros((lanes, d + 1, d + 1), jnp.float32)))
        grad = jnp.einsum("lde,dl->le", T, acc[0], precision=exact) \
            + lam * penalty_mask * uv
        H = jnp.einsum("lda,lde,leb->lab", T, acc[1], T, precision=exact)
        # Levenberg damping, as in ``_train_logistic_newton``
        H = H + (lam * penalty_mask + 1e-4)[..., None] \
            * jnp.eye(d + 1, dtype=jnp.float32)
        delta = jax.vmap(functools.partial(
            jax.scipy.linalg.solve, assume_a="pos"))(H, grad)
        if not fit_intercept:
            delta = delta.at[:, -1].set(0.0)
        new = uv - delta
        ok = jnp.all(jnp.isfinite(new), axis=-1, keepdims=True)
        return jnp.where(ok, new, uv), 0.0

    uv, _ = jax.lax.scan(step, jnp.zeros((lanes, d + 1), jnp.float32), None,
                         length=n_iter)
    uv = uv.reshape(k, g, d + 1)
    # margin space -> equivalent 2-column softmax weights, unstandardized
    half = uv[..., :d] / 2.0 / sd[:, None]
    b_half = uv[..., d] / 2.0 - jnp.sum(mu[:, None] * half, axis=-1)
    return (jnp.stack([-half, half], axis=-1),
            jnp.stack([-b_half, b_half], axis=-1))


#: bytes of one row chunk's working set in ``_gram_folds`` (the chunk's
#: centred rows and every fold's weighted copy of them)
_GRAM_CHUNK_BYTES = 64 << 20
#: coordinate descent on a lane's Gram ends once no coefficient moved more
#: than this (in units of the lane's standardized target) in a sweep over
#: the columns, or after this many sweeps
_CD_TOL = 1e-6
_CD_MAX_SWEEPS = 1000


def _gram_chunk_rows(n: int, d: int, k: int) -> int:
    """Rows a chunk of ``_gram_folds``: ``_GRAM_CHUNK_BYTES`` of the
    chunk's ``d + 2`` float32 columns, a weighted copy of them a fold and
    a fold's masked copy for the column ranges, a multiple of 512, the
    whole matrix where it is smaller."""
    rows = _GRAM_CHUNK_BYTES // (4 * (d + 2) * (3 * k + 1))
    return int(min(n, max(512, rows // 512 * 512)))


def _cd_elastic_net(Q, q, l1, l2, W0):
    """``argmin_W ½ WᵀQW - qᵀW + l2 ½‖W‖² + l1 ‖W‖₁`` by cyclic coordinate
    descent with covariance updates (Friedman, Hastie, Tibshirani 2010,
    "Regularization Paths for Generalized Linear Models via Coordinate
    Descent", section 2.2): a coefficient's update reads its row of the Gram
    ``Q``, never the rows of the data. From ``W0``, until no coefficient
    moves more than ``_CD_TOL`` in a sweep, at most ``_CD_MAX_SWEEPS``
    sweeps; the residual ``q - QW`` is recomputed at the start of each."""
    exact = jax.lax.Precision.HIGHEST
    d = q.shape[0]

    def coordinate(j, carry):
        W, r, moved = carry
        z = r[j] + Q[j, j] * W[j]
        new = jnp.sign(z) * jnp.maximum(jnp.abs(z) - l1, 0.0) \
            / (Q[j, j] + l2)
        delta = new - W[j]
        return (W.at[j].set(new), r - Q[:, j] * delta,
                jnp.maximum(moved, jnp.abs(delta)))

    def sweep(carry):
        W, _, done = carry
        r = q - jnp.matmul(Q, W, precision=exact)
        W, _, moved = jax.lax.fori_loop(0, d, coordinate,
                                        (W, r, jnp.float32(0.0)))
        return W, moved, done + 1

    W, _, _ = jax.lax.while_loop(
        lambda c: (c[1] > _CD_TOL) & (c[2] < _CD_MAX_SWEEPS), sweep,
        (W0, jnp.float32(jnp.inf), jnp.int32(0)))
    return W


@functools.partial(jax.jit, static_argnames=("chunk",))
def _gram_folds(X, y, wf, reg_param, elastic_net, *, chunk: int):
    """Least squares with the elastic net, SOLVED from the normal equations,
    as Spark's ``LinearRegression`` does through ``WeightedLeastSquares``
    up to 4,096 features: ``k`` row weightings of the resident ``X [n, d]``
    (``wf [k, n]``: a fold is a weighting, 0 on the rows it leaves out) x
    ``g`` grid points (``reg_param``, ``elastic_net`` ``[g]``) as ``k x g``
    lanes.

    ONE pass over ``X`` in chunks of ``chunk`` rows (``_over_chunks``),
    each centred and scaled by the moments of all rows, with a ones column
    and the target centred by its mean of all rows beside it (``Z``),
    accumulates every weighting's Gram ``Zᵀ diag(w_f) Z [d+2, d+2]``
    (at ``HIGHEST``: here the Gram decides the answer, not only how fast
    it is reached) and the range of every column among the rows the
    weighting holds. From its Gram each lane takes what ``_linear_descent``
    defines: columns and target standardized by the weighting's own
    moments, the penalty ``reg·((1-α)·½‖W‖² + α‖W‖₁)`` on those fit-space
    weights, the intercept unpenalized (it falls out of the centring); a
    column constant among the weighting's rows gets weight 0. Then a lane
    of ``α = 0`` is the Cholesky solve of ``Q + reg·I`` (one step of
    refinement on the residual), and a lane of ``α > 0`` coordinate descent
    on ``Q`` from the ridge solution of its L2 share (``_cd_elastic_net``).
    Spark takes OWL-QN for the elastic-net points; coordinate descent
    reaches the optimum of the same convex objective by another road.
    Returns original-space ``(Ws [k, g, d, 1], bs [k, g, 1])``."""
    n, d = X.shape
    k, g = wf.shape[0], reg_param.shape[0]
    exact = jax.lax.Precision.HIGHEST
    center, scale = _standardize_stats(X, jnp.ones(n, X.dtype))
    y_mean = jnp.mean(y)

    def add(acc, lo, size):
        rows = _rows_of(X, lo, size)
        Z = jnp.concatenate([(rows - center) / scale,
                             jnp.ones((size, 1), X.dtype),
                             (_rows_of(y, lo, size) - y_mean)[:, None]],
                            axis=1)
        w = _rows_of(wf, lo, size, 1)                       # [k, c]
        held = (w > 0)[:, :, None]
        return (acc[0] + jnp.einsum("kci,cj->kij", w[:, :, None] * Z[None],
                                    Z, precision=exact),
                jnp.minimum(acc[1], jnp.min(
                    jnp.where(held, rows[None], jnp.inf), axis=1)),
                jnp.maximum(acc[2], jnp.max(
                    jnp.where(held, rows[None], -jnp.inf), axis=1)))

    S, lowest, highest = _over_chunks(n, chunk, add, (
        jnp.zeros((k, d + 2, d + 2), jnp.float32),
        jnp.full((k, d), jnp.inf, jnp.float32),
        jnp.full((k, d), -jnp.inf, jnp.float32)))
    # each weighting's moments, in the centred and scaled space of Z
    wsum = jnp.maximum(S[:, d, d], 1.0)
    m = S[:, :d, d] / wsum[:, None]
    m_y = S[:, d + 1, d] / wsum
    cov = S[:, :d, :d] / wsum[:, None, None] - m[:, :, None] * m[:, None, :]
    c_xy = S[:, :d, d + 1] / wsum[:, None] - m * m_y[:, None]
    v_y = S[:, d + 1, d + 1] / wsum - m_y * m_y
    var = jnp.diagonal(cov, axis1=1, axis2=2)
    live = (highest > lowest) & (var * scale * scale > 1e-12)
    sd = jnp.where(live, jnp.sqrt(jnp.maximum(var, 1e-30)), 1.0)
    y_sd = jnp.sqrt(jnp.maximum(v_y, 1e-12))
    both = live[:, :, None] & live[:, None, :]
    Q = jnp.where(both, cov / (sd[:, :, None] * sd[:, None, :]),
                  jnp.eye(d, dtype=jnp.float32))
    q = jnp.where(live, c_xy / (sd * y_sd[:, None]), 0.0)

    def lane(Q, q, reg, en):
        l2 = reg * (1.0 - en)
        A = Q + l2 * jnp.eye(d, dtype=jnp.float32)
        factor = jax.scipy.linalg.cho_factor(A)
        W = jax.scipy.linalg.cho_solve(factor, q)
        W = W + jax.scipy.linalg.cho_solve(
            factor, q - jnp.matmul(A, W, precision=exact))
        return jnp.where(en > 0, _cd_elastic_net(Q, q, reg * en, l2, W), W)

    # lanes are fold-major: lane f * g + j
    rep = lambda a: jnp.repeat(a, g, axis=0)  # noqa: E731
    W = jax.vmap(lane)(rep(Q), rep(q), jnp.tile(reg_param, k),
                       jnp.tile(elastic_net, k))              # [k g, d]
    # standardized space -> original space
    W = W * (rep(y_sd)[:, None] / (rep(sd) * scale[None, :]))
    mu = rep(center[None, :] + m * scale[None, :])
    b = y_mean + rep(m_y) - jnp.sum(W * mu, axis=1)
    return W.reshape(k, g, d, 1), b.reshape(k, g, 1)


def _shard_candidates(*arrs):
    """Shard the leading (candidate/grid) axis over the mesh "model" axis
    when one is active — the grid sweep then runs 2-D parallel: rows over
    "data" (X is row-sharded), candidates over "model" (SURVEY §2.7 P3)."""
    from transmogrifai_tpu.parallel import mesh as pmesh
    ctx = pmesh.current_mesh()
    if ctx is None or ctx.n_model <= 1 or arrs[0].shape[0] % ctx.n_model:
        return arrs
    return tuple(jax.device_put(a, ctx.model_sharding(
        *([None] * (a.ndim - 1)))) for a in arrs)


def _grid_scalars(grid: Sequence[dict], defaults: dict):
    rp = jnp.asarray([float({**defaults, **g}["reg_param"]) for g in grid],
                     jnp.float32)
    en = jnp.asarray([float({**defaults, **g}["elastic_net_param"])
                      for g in grid], jnp.float32)
    return rp, en


def _run_grid(X, y, wf, grid: Sequence[dict], defaults: dict, kw: dict):
    """Train the whole grid under each row weighting of ``wf [F, n]`` as
    one program over the resident ``X [n, d]`` (``_train_linear``). Static
    config (max_iter etc.) must agree across the grid; the regularization
    scalars are the batched axes. Returns ``Ws [F, G, d, C]``,
    ``bs [F, G, C]``, ``last loss [F, G]``."""
    from transmogrifai_tpu.utils import flops
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rp, en = _shard_candidates(*_grid_scalars(grid, defaults))
    n, d = X.shape
    C = kw["n_classes"] if kw["loss_kind"] == "softmax" else 1
    # per Adam step: forward z = X@W (2ndC) + backward grads (~4ndC)
    flops.add("linear", int(wf.shape[0]) * len(grid) * kw["max_iter"]
              * 6.0 * int(n) * int(d) * C)
    lanes = int(wf.shape[0]) * len(grid)
    sweep_counters.count_run(
        linear_descent_lanes=lanes,
        linear_onepass_lanes=lanes if _one_pass_ok(X, lanes, C) else 0)
    return _train_linear(X, y, wf, rp, en, **kw)


def _fold_rows(Xf, yf, wf):
    """Folds handed over as arrays of their own (``Xf [k, n, d]``) laid
    end to end as one matrix, each fold a weighting of its own rows: the
    layout ``_train_linear`` reads."""
    k, n, d = Xf.shape
    eye = jnp.eye(k, dtype=wf.dtype)
    w_rows = (eye[:, :, None] * wf[None, :, :]).reshape(k, k * n)
    return Xf.reshape(k * n, d), yf.reshape(k * n), w_rows


@functools.partial(jax.jit, static_argnames=("precision",))
def _fold_scores(X, Wm, bm, va_idx, precision=_X_PRECISION):
    """``[k, G, n_va]``: every lane's score of every row of the resident
    ``X`` in one product, each fold's lanes read at that fold's validation
    rows ``va_idx [k, n_va]``."""
    scores = jnp.einsum("nd,kgd->kgn", X, Wm, precision=precision) \
        + bm[:, :, None]
    return jnp.take_along_axis(scores, va_idx[:, None, :], axis=2)


@jax.jit
def _fold_class_scores(X, Ws, bs, va_idx):
    """``[k, G, C, n_va]``: ``_fold_scores`` past two classes, every
    class's score of a lane. The class axis comes before the rows, which
    stay on the TPU's lanes (a trailing axis of 7 classes pads to 128)."""
    z = jnp.einsum("nd,kgdc->kgcn", X, Ws, precision=_X_PRECISION) \
        + bs[:, :, :, None]
    return jnp.take_along_axis(z, va_idx[:, None, None, :], axis=3)


def _merge_grid_parts(parts, order):
    """Reassemble per-static-group stacked params ``[(Ws [k, g_i, d, C],
    bs [k, g_i, C]), ...]`` into grid order along the grid axis."""
    if len(parts) == 1:
        Ws, bs = parts[0]
    else:
        Ws = jnp.concatenate([p[0] for p in parts], axis=1)
        bs = jnp.concatenate([p[1] for p in parts], axis=1)
    if list(order) != sorted(order):
        inv = jnp.asarray(np.argsort(np.asarray(order)))
        Ws, bs = Ws[:, inv], bs[:, inv]
    return Ws, bs


# ---------------------------------------------------------------------------
# fitted models
# ---------------------------------------------------------------------------

class LinearClassificationModel(PredictionModel):
    """argmax over class logits; binary emits 2-class raw/probability."""

    def __init__(self, weights=None, intercept=None, probabilistic: bool = True,
                 uid: Optional[str] = None):
        # weights may be device arrays during the CV sweep (no host pull);
        # they convert lazily on serialization/introspection
        self.weights = weights if weights is not None else np.zeros((0, 2))
        self.intercept = intercept if intercept is not None else np.zeros(2)
        self.probabilistic = probabilistic
        super().__init__(uid=uid)

    def device_params(self):
        return (jnp.asarray(self.weights, jnp.float32),
                jnp.asarray(self.intercept, jnp.float32))

    def quantize_device_params(self, precision):
        if precision != "int8":
            return None
        from transmogrifai_tpu.utils.precision import quantize_weights
        W, b = self.device_params()
        return (quantize_weights(W), b)

    def device_apply(self, params, col: fr.VectorColumn) -> fr.PredictionColumn:
        W, b = params
        with jax.default_matmul_precision(_SCORE_PRECISION):
            z = col.values @ W + b
        if z.shape[1] == 1:  # margin-only binary (SVC)
            z = jnp.concatenate([-z, z], axis=1)
        prob = jax.nn.softmax(z, axis=-1) if self.probabilistic \
            else jax.nn.one_hot(jnp.argmax(z, axis=-1), z.shape[1])
        pred = jnp.argmax(z, axis=-1).astype(jnp.float32)
        return fr.PredictionColumn(pred, z, prob)

    def fitted_state(self):
        return {"weights": np.asarray(self.weights, np.float64),
                "intercept": np.asarray(self.intercept, np.float64),
                "probabilistic": self.probabilistic}

    def set_fitted_state(self, state):
        self.weights = np.asarray(state["weights"], np.float64)
        self.intercept = np.asarray(state["intercept"], np.float64)
        self.probabilistic = bool(state.get("probabilistic", True))

    def config(self):
        return {"probabilistic": self.probabilistic}

    @classmethod
    def from_config(cls, config, uid=None):
        return cls(probabilistic=config.get("probabilistic", True), uid=uid)

    def feature_contributions(self) -> np.ndarray:
        """Per-feature coefficients (binary: positive-class column) for
        ModelInsights."""
        W = np.asarray(self.weights)
        return W[:, -1] if W.shape[1] >= 2 else W[:, 0]


class LinearRegressionModel(PredictionModel):
    def __init__(self, weights=None, intercept=0.0,
                 uid: Optional[str] = None):
        self.weights = weights if weights is not None else np.zeros(0)
        self.intercept = intercept
        super().__init__(uid=uid)

    def device_params(self):
        return (jnp.asarray(self.weights, jnp.float32),
                jnp.asarray(self.intercept, jnp.float32))

    def quantize_device_params(self, precision):
        if precision != "int8":
            return None
        from transmogrifai_tpu.utils.precision import quantize_weights
        W, b = self.device_params()
        return (quantize_weights(W), b)

    def device_apply(self, params, col: fr.VectorColumn) -> fr.PredictionColumn:
        W, b = params
        with jax.default_matmul_precision(_SCORE_PRECISION):
            yhat = col.values @ W + b
        n = yhat.shape[0]
        empty = jnp.zeros((n, 0), jnp.float32)
        return fr.PredictionColumn(yhat, empty, empty)

    def fitted_state(self):
        return {"weights": np.asarray(self.weights, np.float64),
                "intercept": np.float64(self.intercept)}

    def set_fitted_state(self, state):
        self.weights = np.asarray(state["weights"], np.float64)
        self.intercept = float(state["intercept"])

    def config(self):
        return {}

    @classmethod
    def from_config(cls, config, uid=None):
        return cls(uid=uid)

    def feature_contributions(self) -> np.ndarray:
        return np.asarray(self.weights)


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

class _LinearPredictor(Predictor):
    loss_kind = "softmax"
    probabilistic = True
    #: the precision of the sweep's product of the raw matrix with a lane's
    #: scalar score weights (``_fold_scores``)
    _fold_score_precision = _X_PRECISION

    default_params = {
        "reg_param": 0.0,
        "elastic_net_param": 0.0,
        "max_iter": 200,
        "fit_intercept": True,
        "standardization": True,
        "tol": 1e-6,
    }

    def _static_kw(self, params, n_classes: int) -> dict:
        return dict(loss_kind=self.loss_kind, n_classes=n_classes,
                    max_iter=int(params["max_iter"]),
                    fit_intercept=bool(params["fit_intercept"]),
                    standardize=bool(params["standardization"]))

    def _n_classes(self, y) -> int:
        if self.loss_kind != "softmax":
            return 2
        return max(int(np.asarray(jnp.max(y))) + 1, 2)

    def _make_model(self, W, b) -> PredictionModel:
        # W/b stay device-resident; host conversion happens lazily
        if self.loss_kind == "squared":
            return LinearRegressionModel(weights=W[:, 0], intercept=b[0])
        return LinearClassificationModel(
            weights=W, intercept=b, probabilistic=self.probabilistic)

    def fit_arrays(self, X, y, w, params):
        kw = self._static_kw(params, self._n_classes(y))
        Ws, bs, _ = _run_grid(X, y, w[None], [params], self.params, kw)
        return self._make_model(Ws[0, 0], bs[0, 0])

    def grid_fit_arrays(self, X, y, w, grid):
        if not grid:
            return []
        # per-model weights stay device views — no host pull in the sweep
        Ws, bs = self._lane_params(X, y, w[None], grid, self._n_classes(y))
        return [self._make_model(Ws[0, j], bs[0, j])
                for j in range(len(grid))]

    def grid_predict_scores(self, models, X):
        """All grid candidates score in one einsum: [G, n] margins
        (classification) or predictions (regression)."""
        if not models:
            return None
        W = jnp.stack([jnp.asarray(m.weights, jnp.float32) for m in models])
        b = jnp.stack([jnp.asarray(m.intercept, jnp.float32) for m in models])
        if self.loss_kind == "squared":
            return jnp.einsum("nd,gd->gn", X, W,
                              precision=self._fold_score_precision) \
                + b[:, None]
        z = jnp.einsum("nd,gdc->gnc", X, W) + b[:, None, :]
        if z.shape[-1] == 1:       # margin-only (SVC)
            return z[:, :, 0]
        if z.shape[-1] == 2:       # binary margin
            return z[:, :, 1] - z[:, :, 0]
        return None                # multiclass: no scalar score

    def _grid_n_classes(self, y, _n_classes=None) -> int:
        """The family's class count for a stacked sweep batch: the
        selector's once-per-sweep hint when given (saves the per-family
        blocking ``max(y)`` pull on the one-sync dispatch path — only
        softmax families ever paid it), else the family's own probe.
        The hint is computed from the SAME stacked label batch with the
        same expression, so both routes agree exactly."""
        if _n_classes is not None and self.loss_kind == "softmax":
            return int(_n_classes)
        return self._n_classes(y)

    def _fold_stacked_params_gated(self, X, y, w, grid, _n_classes=None):
        """Call ``_fold_stacked_params`` threading ``_n_classes`` only when
        the (possibly subclass-overridden) signature accepts it — same gate
        as ``Predictor.grid_scores_folds``, so pre-round-9 overrides with
        the old arity keep working."""
        import inspect
        kw = {}
        if _n_classes is not None and "_n_classes" in \
                inspect.signature(self._fold_stacked_params).parameters:
            kw["_n_classes"] = _n_classes
        return self._fold_stacked_params(X, y, w, grid, **kw)

    # -- fold-stacked sweep --------------------------------------------------
    def _lane_params(self, X, y, wf, grid, n_classes: int):
        """Every row weighting of ``wf [k, n]`` x |grid| points over the
        resident ``X [n, d]``, one program per distinct static-flag combo;
        returns the stacked ``(Ws [k, G, d, C], bs [k, G, C])`` in grid
        order (device-resident)."""
        # group grid points by their static flags (max_iter/intercept/
        # standardization are compile-time constants), so a mixed grid
        # never silently trains with another point's flags
        merged = [{**self.params, **g} for g in grid]
        by_kw: dict[tuple, list[int]] = {}
        for i, g in enumerate(merged):
            key = (int(g["max_iter"]), bool(g["fit_intercept"]),
                   bool(g["standardization"]))
            by_kw.setdefault(key, []).append(i)
        parts, order = [], []
        for idxs in by_kw.values():
            kw = self._static_kw(merged[idxs[0]], n_classes)
            Ws, bs, _ = _run_grid(X, y, wf, [grid[i] for i in idxs],
                                  self.params, kw)
            parts.append((Ws, bs))
            order.extend(idxs)
        return _merge_grid_parts(parts, order)

    def _fold_stacked_params(self, X, y, w, grid, _n_classes=None):
        """Folds given as arrays of their own (``X [k, n, d]``): laid end
        to end, each a weighting of its own rows, for ``_lane_params``."""
        return self._lane_params(*_fold_rows(X, y, w), grid,
                                 self._grid_n_classes(y, _n_classes))

    def _batch_params(self, batch, grid, n_classes: int):
        """Stacked parameters of every fold x grid point of ``batch``,
        each fold a row weighting of the resident matrix."""
        return self._lane_params(batch.X, batch.y, batch.fold_weights(),
                                 grid, n_classes)

    def _margin_params(self, Ws, bs):
        """``(Wm [k, G, d], bm [k, G])``: the one weight vector a lane's
        scalar score needs (the prediction, the hinge margin, or the
        binary margin ``z1 - z0``), or None past two classes, where a lane
        is scored on every class (``_fold_class_scores``). Scoring with it
        makes no ``[.., classes, rows]`` logits."""
        if Ws.shape[-1] == 1:      # squared loss, margin-only (SVC)
            return Ws[..., 0], bs[..., 0]
        if Ws.shape[-1] == 2:      # binary margin
            return Ws[..., 1] - Ws[..., 0], bs[..., 1] - bs[..., 0]
        return None

    def sweep_folds(self, batch, grid, _n_classes=None):
        """The selector's stacked unit without a copy of the matrix: every
        fold trains as a row weighting of ``batch.X``, every lane scores
        all of its rows in one product, and each fold's lanes are read at
        that fold's validation rows: ``[k, G, n_va]`` scalar scores, or
        ``[k, G, C, n_va]`` class scores past two classes."""
        if not grid:
            return None, None
        Ws, bs = self._batch_params(
            batch, grid, self._grid_n_classes(batch.y, _n_classes))
        margin = self._margin_params(Ws, bs)
        va_idx = jnp.asarray(batch.va_idx)
        if margin is None:
            return _fold_class_scores(batch.X, Ws, bs, va_idx), (Ws, bs)
        return _fold_scores(batch.X, *margin, va_idx,
                            precision=self._fold_score_precision), (Ws, bs)

    def fold_stack_bytes(self, batch, grid) -> float:
        # no copy of the matrix: the lanes' per-row intermediates only,
        # which past two classes are as wide as the classes (the logits,
        # their log-softmax and gradient, the class scores)
        classes = (batch.n_classes_hint() if self.loss_kind == "softmax"
                   else 2)
        return (4.0 * batch.k * int(batch.X.shape[0]) * max(len(grid), 1)
                * self.fold_stack_unit_width(grid) * max(classes, 2) / 2.0)

    def grid_fit_arrays_folds(self, X, y, w, grid):
        """``[k][G]`` fitted models whose weights stay device views of the
        stacked result (no host pull in the sweep)."""
        if not grid:
            return []
        Ws, bs = self._fold_stacked_params(X, y, w, grid)
        return [[self._make_model(Ws[f, j], bs[f, j])
                 for j in range(len(grid))] for f in range(int(X.shape[0]))]

    def _scores_from_stacked(self, Ws, bs, Xva):
        """``[k, G, n_va]`` scores straight from stacked parameters
        (``[k, G, C, n_va]`` class scores past two classes)."""
        if self.loss_kind == "squared":
            return jnp.einsum("knd,kgd->kgn", Xva, Ws[..., 0],
                              precision=self._fold_score_precision) \
                + bs[..., 0][:, :, None]
        if Ws.shape[-1] > 2:
            return jnp.einsum("knd,kgdc->kgcn", Xva, Ws) \
                + bs[:, :, :, None]
        z = jnp.einsum("knd,kgdc->kgnc", Xva, Ws) + bs[:, :, None, :]
        if z.shape[-1] == 1:       # margin-only (SVC)
            return z[..., 0]
        return z[..., 1] - z[..., 0]   # binary margin

    def grid_scores_folds(self, X, y, w, grid, Xva, _n_classes=None):
        """Fused sweep unit: stacked parameters -> stacked scores with no
        per-(fold, grid) model materialization in between."""
        if not grid:
            return None
        Ws, bs = self._fold_stacked_params_gated(X, y, w, grid,
                                                 _n_classes=_n_classes)
        return self._scores_from_stacked(Ws, bs, Xva)

    def grid_scores_folds_retained(self, X, y, w, grid, Xva,
                                   _n_classes=None):
        """One-sync dispatch unit: stacked scores PLUS the stacked fold
        parameters ``(Ws [k, G, d, C], bs [k, G, C])`` retained as the
        winner refit's warm-start handle (device views — the arrays
        already exist; retaining them just extends their lifetime to the
        refit). A subclass overriding ``grid_scores_folds`` itself keeps
        its semantics: delegate there (no warm handle) instead of
        silently bypassing the override with the fused body."""
        if type(self).grid_scores_folds is not \
                _LinearPredictor.grid_scores_folds:
            return super().grid_scores_folds_retained(
                X, y, w, grid, Xva, _n_classes=_n_classes)
        if not grid:
            return None, None
        Ws, bs = self._fold_stacked_params_gated(X, y, w, grid,
                                                 _n_classes=_n_classes)
        return self._scores_from_stacked(Ws, bs, Xva), (Ws, bs)

    # -- warm winner refit (round 9) -----------------------------------------
    def supports_warm_refit(self) -> bool:
        return True

    def refit_winner(self, X, y, w, params, *, warm=None, lane=None,
                     hints=None):
        """Full-data winner refit. With a ``warm`` handle (the sweep's
        stacked fold parameters) the Adam descent initializes from the
        fold-AVERAGED winning-lane parameters — a near-optimum start for
        the convex losses — through the donated-buffer compiled program
        (``_linear_warm_program``); the grid's G-1 losing lanes and the
        fold axis collapse, so this is the stacked machinery at G=1.
        Without one (loop-path sweeps, gating off) the refit is the exact
        cold ``fit_arrays`` the serial path always ran."""
        from transmogrifai_tpu.utils.profiling import sweep_counters
        p = {**self.params, **params}
        if warm is None or lane is None:
            return self.fit_arrays(X, y, w, p), False
        Ws, bs = warm
        W_init = jnp.mean(jnp.asarray(Ws, jnp.float32)[:, int(lane)],
                          axis=0)
        sweep_counters.count_run(
            linear_descent_lanes=1,
            linear_onepass_lanes=int(_one_pass_ok(X, 1, W_init.shape[-1])))
        b_init = jnp.mean(jnp.asarray(bs, jnp.float32)[:, int(lane)],
                          axis=0)
        kw = self._static_kw(p, self._n_classes(y))
        kw.pop("n_classes")
        W, b, _ = _linear_warm_program()(
            X, y, w, jnp.float32(p["reg_param"]),
            jnp.float32(p["elastic_net_param"]), W_init, b_init, **kw)
        return self._make_model(W, b), True

    def grid_predict_scores_folds(self, models, X):
        """[k, G, n_va] validation scores in one einsum over the stacked
        fold axis — the selector computes every fold's metrics from this
        with a single host sync per family."""
        if not models or not models[0]:
            return None
        W = jnp.stack([jnp.stack([jnp.asarray(m.weights, jnp.float32)
                                  for m in row]) for row in models])
        b = jnp.stack([jnp.stack([jnp.asarray(m.intercept, jnp.float32)
                                  for m in row]) for row in models])
        return self._scores_from_stacked(W, b, X)


class OpLogisticRegression(_LinearPredictor):
    """Multinomial/binary logistic regression (softmax NLL + elastic net).

    Binary L2-only fits (elastic_net_param=0, the AutoML default grid's
    workhorse) take the Newton/IRLS fast path — ~15 second-order steps
    instead of ``max_iter`` first-order ones; L1 points and multiclass stay
    on the Adam path. Capped at ``_NEWTON_MAX_D`` features (the Hessian is
    [d+1, d+1]).
    """

    loss_kind = "softmax"
    probabilistic = True

    _NEWTON_MAX_D = 2048

    def _newton_ok(self, params, d: int, n_classes: int) -> bool:
        return (float(params.get("elastic_net_param", 0.0)) == 0.0
                and int(d) <= self._NEWTON_MAX_D
                and n_classes == 2)

    def fit_arrays(self, X, y, w, params):
        params = {**self.params, **params}
        if self._newton_ok(params, X.shape[1], self._n_classes(y)):
            Ws, bs = self._newton_points(
                X, y, w, jnp.asarray([params["reg_param"]], jnp.float32),
                bool(params["fit_intercept"]),
                bool(params["standardization"]))
            return self._make_model(Ws[0], bs[0])
        return super().fit_arrays(X, y, w, params)

    def _newton_points(self, X, y, w, rp, fit_b: bool, std_b: bool):
        """The Newton points ``rp [g]`` on one matrix ``X [n, d]`` under
        the row weights ``w [n]``: ``(Ws [g, d, 2], bs [g, 2])``, on copies
        of the matrix where they fit the budget, else in place."""
        from transmogrifai_tpu.utils import flops
        n, d = (int(v) for v in X.shape)
        g = int(rp.shape[0])
        # per Newton step: z/grad matvecs 4n(d+1) + Hessian build
        # 2n(d+1)^2 + dense solve (2/3)(d+1)^3
        flops.add("linear", g * 15 * (
            4.0 * n * (d + 1) + 2.0 * n * (d + 1) ** 2
            + (2.0 / 3.0) * (d + 1) ** 3))
        if _newton_copies_fit(1, n, d):
            Ws, bs, _ = jax.vmap(lambda r: _train_logistic_newton(
                X, y, w, r, fit_intercept=fit_b, standardize=std_b))(rp)
            return Ws, bs
        Ws, bs = _newton_in_place(
            X, y, w[None], rp, chunk=_newton_chunk_rows(n, d, g),
            fit_intercept=fit_b, standardize=std_b)
        return Ws[0], bs[0]

    @staticmethod
    def _by_flags(merged, idxs):
        """``((fit_intercept, standardization), [grid indices])`` groups of
        the points ``idxs``: those flags are compile-time constants, so a
        group is one program and no point trains with another's."""
        groups: dict[tuple[bool, bool], list[int]] = {}
        for i in idxs:
            groups.setdefault((bool(merged[i]["fit_intercept"]),
                               bool(merged[i]["standardization"])),
                              []).append(i)
        return groups.items()

    def grid_fit_arrays(self, X, y, w, grid):
        if not grid:
            return []
        merged = [{**self.params, **g} for g in grid]
        n_classes = self._n_classes(y)  # ONE device sync for the whole grid
        newton_idx = [i for i, g in enumerate(merged)
                      if self._newton_ok(g, X.shape[1], n_classes)]
        if not newton_idx:
            return super().grid_fit_arrays(X, y, w, grid)
        adam_idx = [i for i in range(len(grid)) if i not in set(newton_idx)]
        models: list = [None] * len(grid)
        for (fit_b, std_b), idxs in self._by_flags(merged, newton_idx):
            rp = jnp.asarray([merged[i]["reg_param"] for i in idxs],
                             jnp.float32)
            rp, = _shard_candidates(rp)
            Ws, bs = self._newton_points(X, y, w, rp, fit_b, std_b)
            for j, i in enumerate(idxs):
                models[i] = self._make_model(Ws[j], bs[j])
        if adam_idx:
            rest = super().grid_fit_arrays(X, y, w,
                                           [grid[i] for i in adam_idx])
            for j, i in enumerate(adam_idx):
                models[i] = rest[j]
        return models

    def _newton_folds(self, X, y, w, merged, newton_idx):
        """The Newton points of a grid over folds given as arrays of their
        own (``X [k, n, d]``): vmapped over (fold x reg_param), one
        second-order program per (fit_intercept, standardization) combo.
        Returns ``(parts, order)`` for ``_merge_grid_parts``."""
        from transmogrifai_tpu.parallel import mesh as pmesh
        from transmogrifai_tpu.utils import flops
        from transmogrifai_tpu.utils.profiling import sweep_counters
        k, n, d = (int(v) for v in X.shape)
        parts, order = [], []
        for (fit_b, std_b), idxs in self._by_flags(merged, newton_idx):
            rp = jnp.asarray([merged[i]["reg_param"] for i in idxs],
                             jnp.float32)
            if not pmesh.fold_axis_on_model(k):
                rp, = _shard_candidates(rp)
            inner = lambda Xk, yk, wk: jax.vmap(  # noqa: E731
                lambda r: _train_logistic_newton(
                    Xk, yk, wk, r, fit_intercept=fit_b,
                    standardize=std_b))(rp)
            Ws, bs, _ = jax.vmap(inner)(X, y, w)  # [k, g, ...]
            flops.add("linear", k * len(idxs) * 15 * (
                4.0 * n * (d + 1) + 2.0 * n * (d + 1) ** 2
                + (2.0 / 3.0) * (d + 1) ** 3))
            # each fold's standardized matrix with its ones column
            sweep_counters.count_run(operand_bytes=4 * k * n * (d + 1))
            parts.append((Ws, bs))
            order.extend(idxs)
        return parts, order

    def _newton_split(self, grid, d: int, n_classes: int):
        merged = [{**self.params, **g} for g in grid]
        newton_idx = [i for i, g in enumerate(merged)
                      if self._newton_ok(g, d, n_classes)]
        adam_idx = [i for i in range(len(grid)) if i not in set(newton_idx)]
        return merged, newton_idx, adam_idx

    def _fold_stacked_params(self, X, y, w, grid, _n_classes=None):
        """Fold-stacked LR sweep: the Newton points vmap over (fold x
        reg_param) — one second-order program for the whole family's
        workhorse grid across every fold — and the L1/multiclass rest rides
        the fold-stacked Adam path. Same point-by-point routing as the
        per-fold ``grid_fit_arrays``, so both paths pick identical
        optimizers for every grid point (sweep-parity requirement)."""
        # ONE device sync for the family, elided by the selector's hint
        n_classes = self._grid_n_classes(y, _n_classes)
        merged, newton_idx, adam_idx = self._newton_split(
            grid, int(X.shape[2]), n_classes)
        if not newton_idx:
            return super()._fold_stacked_params(X, y, w, grid,
                                                _n_classes=n_classes)
        parts, order = self._newton_folds(X, y, w, merged, newton_idx)
        if adam_idx:
            parts.append(super()._fold_stacked_params(
                X, y, w, [grid[i] for i in adam_idx],
                _n_classes=n_classes))
            order.extend(adam_idx)
        return _merge_grid_parts(parts, order)

    def _newton_lanes(self, batch, merged, newton_idx):
        """The Newton points of a grid over the folds of ``batch`` as row
        weightings of its resident matrix (``_newton_in_place``), one
        program per (fit_intercept, standardization) combo. Returns
        ``(parts, order)`` for ``_merge_grid_parts``."""
        from transmogrifai_tpu.utils import flops
        n, d, k = int(batch.X.shape[0]), batch.d, batch.k
        parts, order = [], []
        for (fit_b, std_b), idxs in self._by_flags(merged, newton_idx):
            rp = jnp.asarray([merged[i]["reg_param"] for i in idxs],
                             jnp.float32)
            parts.append(_newton_in_place(
                batch.X, batch.y, batch.fold_weights(), rp,
                chunk=_newton_chunk_rows(n, d, k * len(idxs)),
                fit_intercept=fit_b, standardize=std_b))
            # every row of the matrix is read under every fold's weighting
            flops.add("linear", k * len(idxs) * 15 * (
                4.0 * n * (d + 1) + 2.0 * n * (d + 1) ** 2
                + (2.0 / 3.0) * (d + 1) ** 3))
            order.extend(idxs)
        return parts, order

    def _batch_params(self, batch, grid, n_classes: int):
        """The Adam points train in place. The Newton points (they exist
        only up to ``_NEWTON_MAX_D`` columns) read the gathered folds,
        each fold's own standardized matrix, where those and their copies
        fit the budget, and otherwise train in place too, a chunk of rows
        at a time."""
        merged, newton_idx, adam_idx = self._newton_split(
            grid, batch.d, n_classes)
        if not newton_idx:
            return super()._batch_params(batch, grid, n_classes)
        if _newton_copies_fit(batch.k, batch.n_tr, batch.d):
            parts, order = self._newton_folds(*batch.training_folds(),
                                              merged, newton_idx)
        else:
            parts, order = self._newton_lanes(batch, merged, newton_idx)
        if adam_idx:
            parts.append(super()._batch_params(
                batch, [grid[i] for i in adam_idx], n_classes))
            order.extend(adam_idx)
        return _merge_grid_parts(parts, order)

    def fold_stack_bytes(self, batch, grid) -> float:
        need = super().fold_stack_bytes(batch, grid)
        if self._newton_split(grid, batch.d, 2)[1]:
            # the gathered training folds, their standardized copy and the
            # Hessian build's weighted copy; where those do not fit, one
            # row chunk's working set and the lanes' Hessians
            copies = 4.0 * batch.k * batch.n_tr * max(batch.d, 1) * 3.0
            lanes = batch.k * max(len(grid), 1)
            rows = _newton_chunk_rows(int(batch.X.shape[0]), batch.d, lanes)
            in_place = (batch.d + 1) * (
                2.0 * rows * (4 + 2 * lanes)
                + 3.0 * 4.0 * lanes * (batch.d + 1))
            need += (copies if _newton_copies_fit(
                batch.k, batch.n_tr, batch.d) else in_place)
        return need

    def refit_winner(self, X, y, w, params, *, warm=None, lane=None,
                     hints=None):
        """Newton-eligible winners (binary pure-L2, the workhorse grid)
        refit COLD: ~15 damped second-order steps converge from zero
        regardless of init, so the cold path keeps the serial refit's
        bitwise result for free. Only Adam-path winners (L1 points) use
        the warm-started descent."""
        p = {**self.params, **params}
        if self._newton_ok(p, X.shape[1], self._n_classes(y)):
            return self.fit_arrays(X, y, w, p), False
        return super().refit_winner(X, y, w, params, warm=warm, lane=lane,
                                    hints=hints)


class OpLinearSVC(_LinearPredictor):
    """Linear SVM (hinge loss); emits margins, probabilities via one-hot."""
    loss_kind = "hinge"
    probabilistic = False


class OpLinearRegression(_LinearPredictor):
    """Least squares + elastic net.

    Up to ``_GRAM_MAX_D`` features (Spark's ``WeightedLeastSquares`` cap),
    with the default intercept and standardization and off a mesh, every
    grid point is SOLVED from fold-weighted Gram matrices
    (``_gram_folds``: one pass over the resident matrix, then a ``d``-sized
    solve a lane), in the stacked sweep, the per-fold loop, ``fit_arrays``
    and the winner's refit alike, so both sweep paths route every point
    identically. Past the cap, with other flags, or under a mesh (the Gram
    would be a sum over row shards, which is not written) the points take
    the Adam descent, counted in ``linearDescentLanes``."""
    loss_kind = "squared"
    probabilistic = False
    #: a least-squares lane's fold score is its prediction, and the fold's
    #: RMSE reads it as it is: three bfloat16 passes over raw columns of
    #: large offset (a latitude of 40.75) moved the sweep's RMSE by 1e-3
    #: against the same weights applied exactly (v5e, PR 37), where the
    #: scoring product is as narrow as the lanes and its passes cost little
    _fold_score_precision = jax.lax.Precision.HIGHEST

    _GRAM_MAX_D = 4096

    def _gram_ok(self, params, d: int) -> bool:
        from transmogrifai_tpu.parallel import mesh as pmesh
        return (int(d) <= self._GRAM_MAX_D
                and bool(params["fit_intercept"])
                and bool(params["standardization"])
                and pmesh.current_mesh() is None)

    def _gram_params(self, X, y, wf, grid):
        """Every weighting of ``wf [k, n]`` x point of ``grid`` from the
        Gram matrices: ``(Ws [k, g, d, 1], bs [k, g, 1])``."""
        from transmogrifai_tpu.utils import flops
        from transmogrifai_tpu.utils.tracing import span
        rp, en = _grid_scalars(grid, self.params)
        n, d = (int(v) for v in X.shape)
        k = int(wf.shape[0])
        # the Gram pass, 2 k n (d + 2)^2, and a lane's solve, about d^3
        flops.add("linear", 2.0 * k * n * (d + 2) ** 2
                  + k * len(grid) * float(d) ** 3)
        with span("linear.gram", folds=k, lanes=k * len(grid), rows=n,
                  columns=d):
            return _gram_folds(X, y, wf, rp, en,
                               chunk=_gram_chunk_rows(n, d, k))

    def _lane_params(self, X, y, wf, grid, n_classes: int):
        merged = [{**self.params, **g} for g in grid]
        d = int(X.shape[1])
        gram = [i for i, g in enumerate(merged) if self._gram_ok(g, d)]
        if not gram:
            return super()._lane_params(X, y, wf, grid, n_classes)
        parts = [self._gram_params(X, y, wf, [grid[i] for i in gram])]
        order = list(gram)
        rest = [i for i in range(len(grid)) if i not in set(gram)]
        if rest:
            parts.append(super()._lane_params(
                X, y, wf, [grid[i] for i in rest], n_classes))
            order.extend(rest)
        return _merge_grid_parts(parts, order)

    def _fold_stacked_params(self, X, y, w, grid, _n_classes=None):
        """Folds as arrays of their own, laid end to end: the same
        point-by-point routing (``_lane_params``) as the per-fold
        ``grid_fit_arrays`` and the sweep's ``_batch_params``, so both
        sweep paths pick the same trainer for every point (and the family
        keeps its stacked form beside its own ``fit_arrays``)."""
        return super()._fold_stacked_params(X, y, w, grid,
                                            _n_classes=_n_classes)

    def fit_arrays(self, X, y, w, params):
        p = {**self.params, **params}
        if not self._gram_ok(p, X.shape[1]):
            return super().fit_arrays(X, y, w, params)
        Ws, bs = self._gram_params(X, y, w[None], [params])
        return self._make_model(Ws[0, 0], bs[0, 0])

    def refit_winner(self, X, y, w, params, *, warm=None, lane=None,
                     hints=None):
        """A solved point needs no warm start: the refit is the exact
        ``fit_arrays``; past the Gram's reach, the descent's warm refit."""
        if self._gram_ok({**self.params, **params}, X.shape[1]):
            return self.fit_arrays(X, y, w, params), False
        return super().refit_winner(X, y, w, params, warm=warm, lane=lane,
                                    hints=hints)
