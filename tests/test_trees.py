"""Tree ensemble tests (parity: reference OpXGBoost/GBT/RF test quality
assertions on synthetic separable data)."""

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import (
    OpBinaryClassificationEvaluator, OpRegressionEvaluator,
)
from transmogrifai_tpu.models.trees import (
    OpDecisionTreeClassifier, OpGBTClassifier, OpGBTRegressor,
    OpRandomForestClassifier, OpRandomForestRegressor,
    bin_data, quantile_bin_edges,
)


def _xor_data(n=600, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(n, 6)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.float64)  # non-linear
    return jnp.asarray(X), jnp.asarray(y)


def _reg_data(n=600, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(n, 5)).astype(np.float32)
    y = np.sin(3 * X[:, 0]) + 0.5 * (X[:, 1] > 0.3) + 0.1 * rng.normal(size=n)
    return jnp.asarray(X), jnp.asarray(y.astype(np.float64))


def test_binning():
    X = np.arange(100, dtype=np.float32).reshape(-1, 1)
    edges = quantile_bin_edges(X, 4)
    assert edges.shape == (1, 3)
    Xb = np.asarray(bin_data(jnp.asarray(X), jnp.asarray(edges)))
    assert Xb.min() == 0 and Xb.max() == 3
    counts = np.bincount(Xb[:, 0])
    assert (counts > 15).all()  # roughly balanced quartiles


def test_gbt_classifier_learns_xor():
    X, y = _xor_data()
    w = jnp.ones_like(y)
    est = OpGBTClassifier(num_rounds=40, max_depth=3, learning_rate=0.3)
    model = est.fit_arrays(X, y, w, est.params)
    pred = model.predict_arrays(X)
    m = OpBinaryClassificationEvaluator().evaluate_arrays(y, pred)
    assert m.au_roc > 0.97
    assert m.error < 0.1
    # linear models cannot learn xor; sanity-check the signal is non-linear
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    lr = OpLogisticRegression()
    lin = lr.fit_arrays(X, y, w, lr.params)
    m_lin = OpBinaryClassificationEvaluator().evaluate_arrays(
        y, lin.predict_arrays(X))
    assert m.au_roc > m_lin.au_roc + 0.2


def test_gbt_save_load_parity():
    X, y = _xor_data(n=300)
    w = jnp.ones_like(y)
    est = OpGBTClassifier(num_rounds=10, max_depth=3)
    model = est.fit_arrays(X, y, w, est.params)
    state = model.fitted_state()
    clone = type(model).from_config(model.config())
    clone.set_fitted_state(state)
    np.testing.assert_allclose(
        np.asarray(model.predict_arrays(X).probability),
        np.asarray(clone.predict_arrays(X).probability), rtol=1e-6)


def test_rf_classifier():
    X, y = _xor_data(seed=3)
    w = jnp.ones_like(y)
    est = OpRandomForestClassifier(num_trees=30, max_depth=5)
    model = est.fit_arrays(X, y, w, est.params)
    m = OpBinaryClassificationEvaluator().evaluate_arrays(
        y, model.predict_arrays(X))
    assert m.au_roc > 0.95
    prob = np.asarray(model.predict_arrays(X).probability)
    assert prob.min() >= 0.0 and prob.max() <= 1.0
    np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-5)


def test_decision_tree_is_deterministic_single_tree():
    X, y = _xor_data(n=200, seed=5)
    w = jnp.ones_like(y)
    est = OpDecisionTreeClassifier(max_depth=4)
    m1 = est.fit_arrays(X, y, w, est.params)
    m2 = est.fit_arrays(X, y, w, est.params)
    np.testing.assert_allclose(
        np.asarray(m1.predict_arrays(X).probability),
        np.asarray(m2.predict_arrays(X).probability))


def test_gbt_regressor():
    X, y = _reg_data()
    w = jnp.ones_like(y)
    est = OpGBTRegressor(num_rounds=50, max_depth=3, learning_rate=0.2)
    model = est.fit_arrays(X, y, w, est.params)
    m = OpRegressionEvaluator().evaluate_arrays(y, model.predict_arrays(X))
    assert m.r2 > 0.85


def test_rf_regressor():
    X, y = _reg_data(seed=7)
    w = jnp.ones_like(y)
    est = OpRandomForestRegressor(num_trees=30, max_depth=6)
    model = est.fit_arrays(X, y, w, est.params)
    m = OpRegressionEvaluator().evaluate_arrays(y, model.predict_arrays(X))
    assert m.r2 > 0.8


def test_multiclass_gbt():
    rng = np.random.default_rng(11)
    n = 450
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(int) + 2 * (X[:, 1] > 0.0).astype(int)
    y = np.where(y == 3, 2, y)  # 3 classes
    Xj, yj = jnp.asarray(X), jnp.asarray(y.astype(np.float64))
    w = jnp.ones_like(yj)
    est = OpGBTClassifier(num_rounds=30, max_depth=3)
    model = est.fit_arrays(Xj, yj, w, est.params)
    out = model.predict_arrays(Xj)
    acc = float((np.asarray(out.prediction) == y).mean())
    assert acc > 0.9
    assert np.asarray(out.probability).shape == (n, 3)


def test_grow_tree_chunked_matches_full():
    """Depth beyond the histogram node budget: the lax.map node-chunked
    path must produce the same tree as the full-histogram (sibling-
    subtraction) path."""
    from transmogrifai_tpu.models.trees import grow_tree
    rng = np.random.default_rng(3)
    n, d, B, depth = 2000, 8, 16, 6
    Xb = jnp.asarray(rng.integers(0, B, size=(n, d)), jnp.int32)
    grad = jnp.asarray(rng.normal(size=n), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.2, 1.0, size=n), jnp.float32)
    mask = jnp.ones(d, jnp.float32)
    kw = dict(max_depth=depth, n_bins=B, reg_lambda=jnp.float32(1.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0))
    f1, b1, l1, g1, p1 = grow_tree(Xb, grad, hess, mask, max_hist_nodes=1024,
                               **kw)
    f2, b2, l2, g2, p2 = grow_tree(Xb, grad, hess, mask, max_hist_nodes=4, **kw)
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(b1, b2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_depth12_forest_trains_bounded():
    """Reference Titanic winner shape: RF depth=12 (README.md:60-80) must
    train with bounded histogram memory — levels 10-11 exceed the node
    budget and take the chunked path."""
    rng = np.random.default_rng(5)
    n = 20_000
    X = rng.normal(size=(n, 12)).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n)) > 0
         ).astype(np.float64)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    w = jnp.ones_like(yj)
    import transmogrifai_tpu.models.trees as T
    old = T._MAX_HIST_NODES
    try:
        T._MAX_HIST_NODES = 256  # force chunking from level 9 on
        est = OpRandomForestClassifier(num_trees=8, max_depth=12)
        model = est.fit_arrays(Xj, yj, w, est.params)
    finally:
        T._MAX_HIST_NODES = old
    pred = model.predict_arrays(Xj)
    m = OpBinaryClassificationEvaluator().evaluate_arrays(yj, pred)
    assert m.au_roc > 0.9


def test_multiclass_rf_single_program():
    """Multiclass RF: per-class trees ride ONE vmapped ensemble program
    (no per-class host-loop refits); probabilities normalize."""
    rng = np.random.default_rng(11)
    n = 900
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = np.clip((X[:, 0] > 0.4).astype(int) + 2 * (X[:, 1] > 0).astype(int),
                0, 2)
    Xj, yj = jnp.asarray(X), jnp.asarray(y.astype(np.float64))
    w = jnp.ones_like(yj)
    est = OpRandomForestClassifier(num_trees=20, max_depth=5)
    model = est.fit_arrays(Xj, yj, w, est.params)
    from transmogrifai_tpu.models.trees import TreeEnsembleModel
    assert isinstance(model, TreeEnsembleModel)  # no wrapper model
    assert model.n_out == 3
    out = model.predict_arrays(Xj)
    prob = np.asarray(out.probability)
    assert prob.shape == (n, 3)
    np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-5)
    acc = float((np.asarray(out.prediction) == y).mean())
    assert acc > 0.85
    # save/load round-trip of the multiclass forest
    state = model.fitted_state()
    m2 = TreeEnsembleModel.from_config(model.config())
    m2.set_fitted_state(state)
    np.testing.assert_allclose(
        np.asarray(m2.predict_arrays(Xj).probability), prob, atol=1e-6)


def test_gain_based_feature_importances():
    """feature_contributions returns split-GAIN shares (reference
    ModelInsights gain importances): the informative feature dominates, the
    pure-noise features get ~nothing, shares sum to 1."""
    rng = np.random.default_rng(13)
    n = 4000
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 2] > 0.1).astype(np.float64)  # only feature 2 matters
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    w = jnp.ones_like(yj)
    est = OpGBTClassifier(num_rounds=10, max_depth=4)
    model = est.fit_arrays(Xj, yj, w, est.params)
    imp = model.feature_contributions()
    assert imp.shape == (6,)
    np.testing.assert_allclose(imp.sum(), 1.0, atol=1e-6)
    assert np.argmax(imp) == 2
    assert imp[2] > 0.8
    # gains survive the save/load round-trip
    from transmogrifai_tpu.models.trees import TreeEnsembleModel
    m2 = TreeEnsembleModel.from_config(model.config())
    m2.set_fitted_state(model.fitted_state())
    np.testing.assert_allclose(m2.feature_contributions(), imp, atol=1e-6)


def test_grow_tree_sorted_matches_scatter():
    """The sort-based MXU histogram path (hist='sorted') must grow the
    same tree as the scatter path: identical split structure and equal
    leaves/gains up to float summation order (on CPU both accumulate in
    f32, so near-ties cannot flip)."""
    from transmogrifai_tpu.models.trees import grow_tree
    rng = np.random.default_rng(11)
    n, d, B, depth = 3000, 7, 16, 6
    Xb = jnp.asarray(rng.integers(0, B, size=(n, d)), jnp.int32)
    grad = jnp.asarray(rng.normal(size=n), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.2, 1.0, size=n), jnp.float32)
    mask = jnp.ones(d, jnp.float32)
    kw = dict(max_depth=depth, n_bins=B, reg_lambda=jnp.float32(1.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0))
    f1, b1, l1, g1, p1 = grow_tree(Xb, grad, hess, mask, hist="scatter", **kw)
    f2, b2, l2, g2, p2 = grow_tree(Xb, grad, hess, mask, hist="sorted", **kw)
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(b1, b2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-3,
                               atol=1e-3)


def test_grow_tree_sorted_weighted_and_empty_nodes():
    """Sorted path with zero-weight rows (fold masks / Poisson bootstrap
    zeros) and empty deep nodes: leaves and histograms must treat weight-0
    rows as present-but-weightless and empty segments as zeros."""
    from transmogrifai_tpu.models.trees import grow_tree
    rng = np.random.default_rng(12)
    n, d, B, depth = 600, 4, 8, 6  # deep: many empty nodes at level 5
    Xb = jnp.asarray(rng.integers(0, B, size=(n, d)), jnp.int32)
    w = jnp.asarray((rng.uniform(size=n) < 0.6).astype(np.float32))
    grad = jnp.asarray(rng.normal(size=n), jnp.float32) * w
    hess = jnp.asarray(rng.uniform(0.2, 1.0, size=n), jnp.float32) * w
    mask = jnp.ones(d, jnp.float32)
    kw = dict(max_depth=depth, n_bins=B, reg_lambda=jnp.float32(1.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0))
    f1, b1, l1, g1, p1 = grow_tree(Xb, grad, hess, mask, hist="scatter", **kw)
    f2, b2, l2, g2, p2 = grow_tree(Xb, grad, hess, mask, hist="sorted", **kw)
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-4)


def _exact_sum_rows(n, d, B, seed, zero_weight=0.0):
    """(Xb, grad, hess) whose sums are exact in float32 in any order
    (eighths and quarters), so two histogram engines that see the same
    rows read the same floats and must pick the same splits."""
    rng = np.random.default_rng(seed)
    Xb = jnp.asarray(rng.integers(0, B, size=(n, d)), jnp.int32)
    w = (rng.uniform(size=n) >= zero_weight).astype(np.float32)
    grad = jnp.asarray(rng.integers(-8, 9, size=n) / 8.0 * w, jnp.float32)
    hess = jnp.asarray(rng.integers(1, 5, size=n) / 4.0 * w, jnp.float32)
    return Xb, grad, hess


@pytest.mark.parametrize("B", [64, 200])
@pytest.mark.parametrize("d", [4, 7, 28])
def test_sorted_packed_rows_round_trip(d, B):
    """One gather of the packed rows equals the three gathers it replaces,
    bitwise: int8 words (B 64) and int32 codes (B 200), d a multiple of 4
    and not."""
    from transmogrifai_tpu.models.trees import _pack_rows, _unpack_rows
    n = 257
    Xb, _, _ = _exact_sum_rows(n, d, B, seed=d + B)
    rng = np.random.default_rng(d * B)
    grad = jnp.asarray(rng.normal(size=n), jnp.float32).at[3].set(-0.0)
    hess = jnp.asarray(rng.uniform(size=n), jnp.float32).at[5].set(jnp.inf)
    src_row = jnp.asarray(rng.integers(0, n, size=4 * n), jnp.int32)
    packed = _pack_rows(Xb, grad, hess, B)
    assert packed.dtype == jnp.int32 and packed.shape[1] % 8 == 0
    codes, gp, hp = _unpack_rows(packed[src_row], d, B)
    assert codes.dtype == (jnp.int8 if B <= 127 else jnp.int32)
    np.testing.assert_array_equal(np.asarray(codes, np.int32),
                                  np.asarray(Xb[src_row]))
    for got, want in ((gp, grad[src_row]), (hp, hess[src_row])):
        np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                      np.asarray(want).view(np.int32))


@pytest.mark.parametrize("folds", [1, 2])
def test_sorted_matches_scatter_blocked_cumsum(folds):
    """Sorted against scatter, identical feats and bins, at a size whose
    partition takes ``_long_cumsum``'s blocked branch and whose levels use
    several block sizes C; zero-weight rows, and nodes that stop
    splitting (empty right children below them); plain and under a fold
    ``vmap`` as the stacked sweep runs it."""
    import jax
    from transmogrifai_tpu.models.trees import _CUMSUM_BLOCK, grow_tree
    n, d, B, depth = 12_000, 7, 16, 8
    assert n > 8 * _CUMSUM_BLOCK
    cases = [_exact_sum_rows(n, d, B, seed=21 + f, zero_weight=0.4)
             for f in range(folds)]
    mask = jnp.ones(d, jnp.float32)
    kw = dict(max_depth=depth, n_bins=B, reg_lambda=jnp.float32(1.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(12.0))

    def grow(hist):
        def one(Xb, grad, hess):
            return grow_tree(Xb, grad, hess, mask, hist=hist, **kw)
        if folds == 1:
            return [one(*cases[0])]
        out = jax.vmap(one)(*(jnp.stack(a) for a in zip(*cases)))
        return [jax.tree_util.tree_map(lambda a: a[f], out)
                for f in range(folds)]

    for ref, got in zip(grow("scatter"), grow("sorted")):
        for a, b in zip(ref[0] + ref[1], got[0] + got[1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        deep = np.asarray(ref[0][-1])
        assert (deep < 0).any() and (deep >= 0).any()  # stopped + split
        np.testing.assert_allclose(np.asarray(ref[2]), np.asarray(got[2]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(ref[4]), np.asarray(got[4]),
                                   atol=1e-6)


def test_sorted_layout_valid_prefix_cumsum():
    """What lets the partition do with ONE long cumsum: the valid slots
    are the first of each node's segment, in node order, so
    ``cumsum(valid)`` is known in closed form from the per-block layout
    (rows before the node + slots up to this one, capped at the node's
    count) — on a layout with empty and one-row nodes. The per-block
    read of ``order`` puts the sorted rows, in order, on the valid
    slots."""
    from transmogrifai_tpu.models.trees import _block_rows, _sorted_layout
    counts = jnp.asarray([0, 1, 5, 0, 17, 1, 0, 8], jnp.int32)
    n, C = 32, 4
    lay = _sorted_layout(counts, n, C)
    nb = lay.valid.shape[0]
    assert nb * C == -(-n // C) * C + counts.shape[0] * C
    starts = jnp.cumsum(counts) - counts
    closed = starts[lay.bnode][:, None] + jnp.clip(
        lay.within + 1, 0, counts[lay.bnode][:, None])
    np.testing.assert_array_equal(
        np.asarray(closed).reshape(-1),
        np.cumsum(np.asarray(lay.valid).reshape(-1)))
    assert int(np.asarray(lay.within).min()) >= 0
    order = jnp.pad(jnp.arange(100, 100 + n, dtype=jnp.int32), (0, 2 * C))
    src_row = np.asarray(_block_rows(order, lay.src_start, C))
    np.testing.assert_array_equal(
        src_row[np.asarray(lay.valid).reshape(-1)], np.arange(100, 100 + n))


@pytest.mark.parametrize("C", [8, 128, 256])
def test_block_rows_reads_unaligned_runs(C):
    """``_block_rows`` (two aligned row gathers + a barrel shifter) equals
    the plain slices ``order[s : s + C]`` at every alignment of ``s``,
    the last row id included."""
    from transmogrifai_tpu.models.trees import _block_rows
    n = 5 * C + 3
    n_order = (n // C + 2) * C
    order = jnp.pad(jnp.arange(7, 7 + n, dtype=jnp.int32), (0, n_order - n))
    starts = np.concatenate([np.arange(0, 2 * C + 1), [n - C, n - 1, n]])
    got = np.asarray(_block_rows(order, jnp.asarray(starts, jnp.int32), C))
    want = np.stack([np.asarray(order)[s:s + C] for s in starts])
    np.testing.assert_array_equal(got.reshape(-1, C), want)


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _count_long_moves(jaxpr, n):
    """(gathers, scatters) among a jaxpr's equations, nested ones
    included, that move ``n`` slots or more one by one: a gather whose
    output, or a scatter whose updates, has a leading dimension >= n."""
    g = s = 0
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "gather" and eqn.outvars[0].aval.shape[:1] >= (n,):
            g += 1
        elif name.startswith("scatter") \
                and eqn.invars[2].aval.shape[:1] >= (n,):
            s += 1
    return g, s


def test_sorted_level_moves_each_row_once():
    """The counter of the per-level row movement: one more level adds at
    most 2 gathers and 1 scatter as long as the rows (the packed row
    gather, and the partition's scatter), where the table-per-slot form
    added 14 and 1. Per-block lookups (``nb`` long) do not count."""
    import jax
    from transmogrifai_tpu.models.trees import _grow_tree_sorted
    n, d, B = 20_000, 28, 64
    Xb, grad, hess = _exact_sum_rows(n, d, B, seed=5)

    def moves(depth):
        jaxpr = jax.make_jaxpr(lambda X, g, h: _grow_tree_sorted(
            X, g, h, jnp.ones(d, jnp.float32), max_depth=depth, n_bins=B,
            reg_lambda=jnp.float32(1.0), gamma=jnp.float32(0.0),
            min_child_weight=jnp.float32(1.0)))(Xb, grad, hess)
        return _count_long_moves(jaxpr.jaxpr, n)

    (g3, s3), (g4, s4) = moves(3), moves(4)
    assert 1 <= g4 - g3 <= 2 and s4 - s3 == 1, ((g3, s3), (g4, s4))


def _long_sums(jaxpr, at_least):
    """The prefix-sum equations (``cumsum``, ``reduce_window*``) of a
    jaxpr, nested ones included, whose operand holds ``at_least``
    elements or more, as (dtype, ndim, size)."""
    out = []
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name.startswith(("cumsum", "reduce_window")):
            aval = eqn.invars[0].aval
            if aval.size >= at_least:
                out.append((str(aval.dtype), aval.ndim, aval.size))
    return out


def test_sorted_level_has_no_prefix_sum_over_the_blocks():
    """Beside the row-movement counter: no level of the sorted grower
    holds a prefix sum over the per-block partial histograms (on a TPU a
    ``reduce-window`` over the whole block axis, a third of a tree
    sweep's device time). Every ``cumsum`` / ``reduce_window`` equation
    over ``n`` elements or more is shorter than the smallest level's
    ``blocks x 2 x d x B`` partials: the partition's sum of ``int32``
    positions in ``_long_cumsum``'s blocked form, ONE a level (the
    layout's sums are ``[N]`` long), and the leaves' two sums of per-row
    floats, whatever the depth."""
    import jax
    from transmogrifai_tpu.models.trees import _SORT_BLOCK, _grow_tree_sorted
    n, d, B = 20_000, 28, 64
    Xb, grad, hess = _exact_sum_rows(n, d, B, seed=5)

    def sums(depth):
        jaxpr = jax.make_jaxpr(lambda X, g, h: _grow_tree_sorted(
            X, g, h, jnp.ones(d, jnp.float32), max_depth=depth, n_bins=B,
            reg_lambda=jnp.float32(1.0), gamma=jnp.float32(0.0),
            min_child_weight=jnp.float32(1.0)))(Xb, grad, hess)
        return _long_sums(jaxpr.jaxpr, n)

    s3, s4 = sums(3), sums(4)
    partials = (n // _SORT_BLOCK) * 2 * d * B   # the shortest block axis
    assert all(size < partials for _, _, size in s4), s4
    ints = [s for s in s4 if s[0] == "int32"]
    assert len(ints) == 4, s4                  # one a level
    # the floats are the leaves' two, whatever the depth
    assert len(s4) - len(ints) == len(s3) - 3 == 2, (s3, s4)


def _node_sums64(Xp, gp, hp, counts, layout, B):
    """[N, d, B] float64 histograms of the padded slots, node by node: the
    plain sums ``_sorted_hist`` has to give."""
    N, d = len(counts), Xp.shape[1]
    pstarts, pends = np.asarray(layout.pstarts), np.asarray(layout.pends)
    want = np.zeros((2, N, d, B))
    for node in range(N):
        rows = slice(pstarts[node], pends[node])
        for s, v in enumerate((gp, hp)):
            for f in range(d):
                want[s, node, f] = np.bincount(
                    Xp[rows, f], weights=v[rows].astype(np.float64),
                    minlength=B)
    return want


#: (counts in blocks a node, what the case is for); C is 8 and the group
#: width the module's own, so ``nb = n / 8 + N`` blocks
_HIST_LAYOUTS = {
    # 5 nodes in under one group of blocks
    "one_group": [3, 40, 1, 17, 20],
    # a block axis that is no multiple of the group width
    "ragged_groups": [130, 7, 150, 1, 61],
    # a node over three and more groups, and one that starts at block 0
    "spans_groups": [420, 3, 300, 2],
    # empty nodes first, last and in a run; a node that ends a group
    "empty_nodes": [0, 0, 128, 0, 0, 0, 90, 140, 0],
}


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("d,exact", [(7, True), (28, False), (54, True)])
@pytest.mark.parametrize("case", list(_HIST_LAYOUTS))
def test_sorted_hist_node_totals(case, d, exact, stacked):
    """``_sorted_hist``'s per-node totals against float64 segment sums of
    the same padded slots: every layout of ``_HIST_LAYOUTS``, narrow and
    wide rows, plain and under the two-axis ``vmap`` of the stacked sweep
    (folds whose rows differ, lanes whose (g, h) differ). With operands
    whose sums are exact in float32 (``_exact_sum_rows``) the result is
    the plain sum to the bit, in whatever order the blocks were added."""
    import jax
    from transmogrifai_tpu.models.trees import (
        _HIST_GROUP, _sorted_hist, _sorted_layout)
    C, B = 8, 16
    blocks = np.asarray(_HIST_LAYOUTS[case])
    rng = np.random.default_rng(len(case) + d)
    # rows a node: its blocks' slots, the last block part filled
    counts = np.where(blocks > 0, blocks * C - rng.integers(0, C, blocks.size),
                      0).astype(np.int32)
    n = int(counts.sum())
    layout = _sorted_layout(jnp.asarray(counts), n, C)
    nb = layout.valid.shape[0]
    assert (nb <= _HIST_GROUP) == (case == "one_group")
    assert nb % _HIST_GROUP != 0
    if case == "spans_groups":
        assert blocks.max() > 3 * _HIST_GROUP

    def operands(seed):
        Xb, g, h = _exact_sum_rows(nb * C, d, B, seed=seed)
        if not exact:
            r = np.random.default_rng(seed)
            g = jnp.asarray(r.normal(size=nb * C), jnp.float32)
            h = jnp.asarray(r.uniform(size=nb * C), jnp.float32)
        vf = layout.valid.reshape(-1).astype(jnp.float32)
        return Xb.astype(jnp.int8), g * vf, h * vf

    def hist(Xp, gp, hp):
        return _sorted_hist(Xp, gp, hp, layout, n_bins=B, C=C,
                            acc_dtype=jnp.float32)

    if stacked:
        folds = [operands(31), operands(32)]
        lanes = [1.0, -0.5]       # exact scalings: a lane's own (g, h)
        Xp = jnp.stack([f[0] for f in folds])
        gp = jnp.stack([jnp.stack([f[1] * s for s in lanes]) for f in folds])
        hp = jnp.stack([jnp.stack([f[2] * s for s in lanes]) for f in folds])
        got = jax.jit(jax.vmap(jax.vmap(hist, in_axes=(None, 0, 0))))(
            Xp, gp, hp)
        cells = [(np.asarray(got[0][i, j]), np.asarray(got[1][i, j]),
                  np.asarray(Xp[i]), np.asarray(gp[i, j]),
                  np.asarray(hp[i, j]))
                 for i in range(2) for j in range(2)]
    else:
        Xp, gp, hp = operands(33)
        got = hist(Xp, gp, hp)
        cells = [(np.asarray(got[0]), np.asarray(got[1]), np.asarray(Xp),
                  np.asarray(gp), np.asarray(hp))]
    for hg, hh, Xp_, gp_, hp_ in cells:
        assert hg.shape == hh.shape == (len(counts), d, B)
        want = _node_sums64(Xp_, gp_, hp_, counts, layout, B)
        for have, w64 in ((hg, want[0]), (hh, want[1])):
            if exact:
                np.testing.assert_array_equal(have, w64.astype(np.float32))
            else:
                np.testing.assert_allclose(have, w64, rtol=0, atol=2e-4)
        assert not hg[counts == 0].any() and not hh[counts == 0].any()


def test_train_ensemble_sorted_multiclass_parity():
    """hist='sorted' must thread through the scanned ensemble under the
    multiclass vmap (per-class independent routing) and bootstrap."""
    from transmogrifai_tpu.models.trees import (
        bin_data, predict_ensemble, quantile_bin_edges, train_ensemble,
    )
    rng = np.random.default_rng(13)
    n, d = 2500, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64) + (X[:, 1] > 0.5)
    edges = quantile_bin_edges(X, 16)
    Xb = bin_data(jnp.asarray(X), jnp.asarray(edges))
    yj = jnp.asarray(y)
    w = jnp.ones_like(yj)
    kw = dict(n_rounds=5, max_depth=4, n_bins=16, n_out=3,
              loss="squared_onehot", learning_rate=jnp.float32(1.0),
              reg_lambda=jnp.float32(1e-3), gamma=jnp.float32(0.0),
              min_child_weight=jnp.float32(1.0), subsample=1.0,
              colsample=1.0, base_score=jnp.float32(0.0), bootstrap=True,
              seed=9)
    t1, g1 = train_ensemble(Xb, yj, w, hist="scatter", **kw)
    t2, g2 = train_ensemble(Xb, yj, w, hist="sorted", **kw)
    p1 = predict_ensemble(Xb, t1, n_out=3, learning_rate=jnp.float32(1.0),
                          base_score=jnp.float32(0.0), bootstrap=True)
    p2 = predict_ensemble(Xb, t2, n_out=3, learning_rate=jnp.float32(1.0),
                          base_score=jnp.float32(0.0), bootstrap=True)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-4)


#: what ``_hist_engine`` answers on a TPU for a fit above ``_SORT_MIN_ROWS``,
#: by (input layout, stacked unit); every other (backend, rows) answers
#: "scatter" whatever the layout
_TPU_BIG_ROUTES = {
    ("one_device", False): "sorted",
    ("one_device", True): "sorted",
    # row-sharded over an active mesh whose data axis divides the rows: the
    # explicit shard_map wrapper; the stacked batch cannot ride it (B1)
    ("sharded_mesh", False): "sorted_sharded",
    ("sharded_mesh", True): "scatter",
    # sharded input but NO active mesh context -> GSPMD scatter; a stacked
    # unit never looks at the input's devices
    ("sharded_no_mesh", False): "scatter",
    ("sharded_no_mesh", True): "sorted",
    # rows the mesh's data axis does not divide
    ("indivisible_rows", False): "scatter",
    ("indivisible_rows", True): "scatter",
}


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("layout", ["one_device", "sharded_mesh",
                                    "sharded_no_mesh", "indivisible_rows"])
@pytest.mark.parametrize("big", [False, True])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_hist_engine_routing(backend, big, layout, stacked, monkeypatch):
    """``_hist_engine`` is the single source of truth for the engine route,
    decided from the backend, the rows, the input's devices, the active
    mesh and whether the unit is the stacked batch — nothing else."""
    import contextlib

    import jax
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.parallel.mesh import make_mesh, use_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    n_data = 8
    rows = trees._SORT_MIN_ROWS if big else trees._SORT_MIN_ROWS - n_data
    assert rows % n_data == 0
    if layout == "indivisible_rows":
        rows += 1
    n_devices = 1 if layout == "one_device" else n_data
    mesh = (use_mesh(make_mesh(n_data=n_data))
            if layout in ("sharded_mesh", "indivisible_rows")
            else contextlib.nullcontext())
    with mesh:
        got = trees._hist_engine(rows, n_devices, stacked=stacked)
    want = (_TPU_BIG_ROUTES[layout, stacked]
            if backend == "tpu" and big else "scatter")
    assert got == want


def test_fit_arrays_routes_by_input_devices(monkeypatch, mesh8):
    """``fit_arrays`` hands the router the fit's rows and the number of
    devices its binned matrix lives on."""
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.parallel.mesh import shard_training_rows
    seen = []

    def spy(n_rows, n_devices=1, *, stacked=False):
        seen.append((n_rows, n_devices, stacked))
        return "scatter"

    monkeypatch.setattr(trees, "_hist_engine", spy)
    X, y = _xor_data(256)
    w = jnp.ones(X.shape[0], jnp.float32)
    est = OpGBTClassifier(num_rounds=1, max_depth=2)
    est.fit_arrays(X, y, w, {})
    est.fit_arrays(*shard_training_rows(X, y, w), {})
    assert seen == [(256, 1, False), (256, 8, False)]


def test_sorted_engine_f32_grows_the_scatter_tree():
    """Off a TPU the sorted engine's contraction runs float32 operands
    (decided in ``_grow_tree_sorted``, nowhere else) and grows the scatter
    engine's tree."""
    from transmogrifai_tpu.models.trees import grow_tree
    X, y = _xor_data(512)
    edges = quantile_bin_edges(np.asarray(X), 32)
    Xb = bin_data(X, jnp.asarray(edges))
    g = (jnp.asarray(y) - 0.5).astype(jnp.float32)
    h = jnp.ones_like(g)
    mask = jnp.ones(Xb.shape[1], jnp.float32)
    kw = dict(max_depth=4, n_bins=32, reg_lambda=jnp.float32(1.0),
              gamma=jnp.float32(0.0), min_child_weight=jnp.float32(1.0))
    ref = grow_tree(Xb, g, h, mask, hist="scatter", **kw)
    f32 = grow_tree(Xb, g, h, mask, hist="sorted", **kw)
    for a, b in zip((*ref[0], *ref[1]), (*f32[0], *f32[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(ref[2]), np.asarray(f32[2]),
                               atol=1e-5)  # identical leaf values


def test_tree_bin_once_fold_plan():
    """fold_sweep_plan computes dataset-level codes once; per-fold
    grid_fit_arrays gathers rows from them (same edges, same models as a
    manual gather)."""
    X, y = _xor_data(400)
    w = jnp.ones(X.shape[0], jnp.float32)
    est = OpGBTClassifier(num_rounds=3, max_depth=3)
    grid = [{"num_rounds": 3, "max_depth": 3}]
    plan = est.fold_sweep_plan(X, grid)
    assert set(plan) == {64} and plan[64][1].shape == X.shape
    rows = jnp.arange(100)
    m_plan = est.grid_fit_arrays(X[rows], y[rows], w[rows], grid,
                                 _fold_plan=plan, _fold_rows=rows)[0]
    # manual reference: same dataset-level edges, same gathered codes
    m_ref = est.fit_arrays(X[rows], y[rows], w[rows], grid[0],
                           _binned=(plan[64][0],
                                    jnp.take(plan[64][1], rows, axis=0), 64))
    np.testing.assert_allclose(np.asarray(m_plan.trees[2]),
                               np.asarray(m_ref.trees[2]), atol=1e-6)


# -- the base score is an argument of the model's programs --------------------

def _fit(kind, seed):
    """A small fitted boosted model on a table drawn from ``seed``."""
    from transmogrifai_tpu.models.trees import OpGBTClassifier, OpGBTRegressor
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(300, 5)).astype(np.float32)
    y = np.sin(2 * X[:, 0]) + X[:, 1] * X[:, 2] + 0.4 + 0.3 * seed
    if kind == "gbt_classifier":
        y = (y > 0.9).astype(np.float64)
    est = {"gbt_classifier": OpGBTClassifier,
           "gbt_regressor": OpGBTRegressor}[kind](
        num_rounds=5, max_depth=3, learning_rate=0.3)
    model = est.fit_arrays(jnp.asarray(X), jnp.asarray(y),
                           jnp.ones(len(X)), est.params)
    return model, X


@pytest.mark.parametrize("path", ["predict_arrays", "fused"])
@pytest.mark.parametrize("kind", ["gbt_regressor", "gbt_classifier"])
def test_score_is_base_plus_rate_times_sum(kind, path):
    """With the base score an argument, the score is still
    ``base_score + learning_rate * sum(tree outputs)`` in float32, to the
    bit, through ``predict_arrays`` and through the fused program."""
    from tree_reference import assert_is_margin, fused_predict, margin_of
    model, X = _fit(kind, seed=1)
    assert abs(model.base_score) > 0.05       # a value of the table
    pred = (model.predict_arrays(jnp.asarray(X)) if path == "predict_arrays"
            else fused_predict(model, X))
    assert_is_margin(margin_of(pred, model), model, X)


@pytest.mark.parametrize("rung", ["bf16", "int8"])
def test_quantized_rungs_add_the_base_score_unrounded(rung):
    """On the rungs below f32 the leaves take the rung's dtype and the base
    score stays the float32 it is (an offset on the margin, pinned like the
    bin edges): the score is float32(base) + the rung's own
    ``learning_rate * sum``, which the same trees under a zero base give."""
    from tree_reference import assert_rung_adds_base_unrounded
    from transmogrifai_tpu.utils.precision import ExactTensor
    model, X = _fit("gbt_regressor", seed=2)
    base = np.float32(model.base_score)
    assert base != np.float32(jnp.asarray(base, jnp.bfloat16))
    edges, (feats, bins, leaves), qbase = model.quantize_device_params(rung)
    assert isinstance(edges, ExactTensor) and isinstance(qbase, ExactTensor)
    assert np.asarray(qbase.value).dtype == np.float32
    assert np.asarray(qbase.value) == base
    assert all(a.dtype == (jnp.int16 if rung == "int8" else jnp.int32)
               for a in (*feats, *bins))
    assert_rung_adds_base_unrounded(model, X, rung)


def test_models_of_two_tables_lower_to_one_module():
    """Nothing of the training data is a constant of the model's program:
    two models fitted on two tables of the same shapes lower
    ``device_apply`` to the same module text, so the second one's programs
    are found in the persistent compile cache."""
    import jax
    from transmogrifai_tpu import frame as fr
    texts, bases = [], []
    for seed in (1, 2):
        model, X = _fit("gbt_classifier", seed)
        bases.append(model.base_score)
        texts.append(jax.jit(model.device_apply).lower(
            model.device_params(),
            fr.VectorColumn(jnp.asarray(X))).as_text())
    assert bases[0] != bases[1]
    assert texts[0] == texts[1]


def test_manifest_keeps_base_score_and_old_manifests_load():
    """The base score is saved where it always was, in the stage's
    ``config``: the record a fitted model writes is the one the parent of
    this change wrote, and such a record loads and scores the same."""
    from transmogrifai_tpu.serialization import (
        fitted_stage_record, restore_fitted_stage,
    )
    model, X = _fit("gbt_classifier", seed=3)
    rec, arrays = fitted_stage_record(model)
    assert rec["config"] == {
        "kind": "gbt_classifier", "n_out": 1, "learning_rate": 0.3,
        "base_score": model.base_score, "max_depth": 3}
    assert isinstance(rec["config"]["base_score"], float)
    assert rec["stateJson"] == {}
    assert sorted(k.partition("||")[2] for k in arrays) == sorted(
        ["bin_edges", "leaves", "feature_gains"]
        + [f"{a}_l{l}" for a in ("feat", "bin") for l in range(3)])
    # a record as it was written before the base score became an argument
    old = {"class": "TreeEnsembleModel",
           "module": "transmogrifai_tpu.models.trees",
           "uid": "TreeEnsembleModel_00000000004d",
           "operationName": rec["operationName"],
           "config": {"kind": "gbt_classifier", "n_out": 1,
                      "learning_rate": 0.3,
                      "base_score": float(model.base_score), "max_depth": 3},
           "stateJson": {}}
    old_arrays = {f"{old['uid']}||{k.partition('||')[2]}": v
                  for k, v in arrays.items()}
    loaded = restore_fitted_stage(old, old_arrays)
    a = model.predict_arrays(jnp.asarray(X))
    b = loaded.predict_arrays(jnp.asarray(X))
    np.testing.assert_array_equal(np.asarray(a.raw_prediction),
                                  np.asarray(b.raw_prediction))
    np.testing.assert_array_equal(np.asarray(a.probability),
                                  np.asarray(b.probability))


# -- the scoring path's lookups compare against whole tables (on a TPU) -------

@pytest.fixture
def as_tpu(monkeypatch):
    """Trace as on a TPU: ``models/trees.py`` asks ``jax.default_backend()``
    which form a lookup takes, and XLA:CPU runs either."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _fresh(fn):
    """``fn`` under a new identity: jax keeps traces by function and
    shapes, and one kept under the other backend's name is the other
    form."""
    return lambda *args: fn(*args)


def _row_long_gathers(jaxpr, n, scope=""):
    """Gathers that look up ``n`` or more indices one by one, among the
    equations traced under ``scope``."""
    return [e for e in _eqns(jaxpr)
            if e.primitive.name == "gather"
            and np.prod(e.invars[1].aval.shape[:-1]) >= n
            and scope in str(e.source_info.name_stack)]


_BIN_CASES = {
    # name -> (X [n, d], edges [d, E]) with the values that test a rule
    "tied_edges": lambda rng: (
        rng.normal(size=(501, 3)),
        np.sort(np.round(rng.normal(size=(3, 31)), 1), axis=1)),
    "equal_to_an_edge": lambda rng: (
        np.round(rng.normal(size=(501, 3)), 1),
        np.sort(np.round(rng.normal(size=(3, 31)), 1), axis=1)),
    "negative_zero": lambda rng: (
        np.array([[-0.0], [0.0], [-1e-30], [1e-30]]),
        np.array([[-1.0, -0.0, 0.0, 1.0]])),
    "infinities": lambda rng: (
        np.array([[-np.inf], [np.inf], [3e38], [-3e38]]),
        np.array([[-np.inf, -1.0, 1.0, np.inf]])),
    "nan": lambda rng: (
        np.array([[np.nan], [0.5], [-np.nan]]),
        np.array([[-1.0, 0.0, 1.0]])),
    "one_column": lambda rng: (
        rng.normal(size=(257, 1)), np.sort(rng.normal(size=(1, 63)), axis=1)),
    "wider_than_the_walk_selects": lambda rng: (
        rng.normal(size=(65, 300)),
        np.sort(rng.normal(size=(300, 15)), axis=1)),
}


@pytest.mark.parametrize("form", ["count", "search"])
@pytest.mark.parametrize("case", sorted(_BIN_CASES))
def test_bin_data_is_searchsorted_left(case, form, request):
    """``bin_data`` gives ``np.searchsorted(edges[f], x, side="left")`` to
    the last code in both its forms: the count of edges below the value
    (a TPU's) and the binary search (everything else's)."""
    import jax
    from tree_reference import bin_codes
    if form == "count":
        request.getfixturevalue("as_tpu")
    X, edges = (np.asarray(a, np.float32)
                for a in _BIN_CASES[case](np.random.default_rng(7)))
    text = str(jax.make_jaxpr(_fresh(bin_data.__wrapped__))(X, edges))
    assert ("gather" in text) == (form == "search")
    got = np.asarray(jax.jit(_fresh(bin_data.__wrapped__))(
        jnp.asarray(X), jnp.asarray(edges)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, bin_codes(X, edges))


def test_bin_data_searches_past_the_edge_cap(as_tpu, monkeypatch):
    """Counting is ``O(edges)``: past ``_COUNT_MAX_EDGES`` (an imported
    booster's thousands of thresholds a feature) the search stays."""
    import jax
    from transmogrifai_tpu.models import trees
    monkeypatch.setattr(trees, "_COUNT_MAX_EDGES", 8)
    X, edges = np.zeros((5, 2), np.float32), np.zeros((2, 9), np.float32)
    assert "gather" in str(jax.make_jaxpr(_fresh(bin_data.__wrapped__))(
        X, edges))
    assert "gather" not in str(jax.make_jaxpr(_fresh(bin_data.__wrapped__))(
        X, edges[:, :8]))


def _random_trees(rng, lead, depth, d, n_bins, dtype=np.int32):
    """Stacked tables of random complete trees: a third of the nodes do not
    split (feature -1, bin ``n_bins``), every third leaf is ``-0.0``."""
    feats, bins = [], []
    for level in range(depth):
        f = rng.integers(0, d, size=lead + (2 ** level,))
        b = rng.integers(0, n_bins, size=f.shape)
        dead = rng.random(f.shape) < 1 / 3
        feats.append(jnp.asarray(np.where(dead, -1, f).astype(dtype)))
        bins.append(jnp.asarray(np.where(dead, n_bins, b).astype(dtype)))
    leaves = rng.normal(size=lead + (2 ** depth,)).astype(np.float32)
    leaves[..., ::3] = -0.0
    return tuple(feats), tuple(bins), jnp.asarray(leaves)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.int16, 4: np.int32}[a.dtype.itemsize])


def _pads(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "pad"]


#: (depth, columns, rows) of the walks compared with the gather walk: every
#: depth and width at a row count that is a multiple of nothing, and the
#: row counts about one tile of the TPU's lanes at ``covtype_multi``'s depth
_WALKS = [(depth, d, 1237) for depth in (1, 3, 6, 12, 13) for d in (28, 300)] \
    + [(12, 28, n) for n in (1, 127, 128, 129)]


@pytest.mark.parametrize("depth,d,n", _WALKS,
                         ids=[f"depth{t}-d{d}-n{n}" for t, d, n in _WALKS])
def test_select_walk_is_the_gather_walk(depth, d, n, as_tpu):
    """The walk whose lookups compare against whole tables reaches the
    gather walk's leaf for every row and returns that leaf's bits (a
    ``-0.0`` leaf included): nodes that do not split, both sides of the
    width threshold (``_SELECT_MAX_WIDTH``: wider frames gather the code)
    and of the table threshold (``_SELECT_MAX_NODES``: depth 13's leaves
    are gathered), and row counts in and out of whole 128-row tiles: the
    walk pads its rows to whole tiles with one ``pad`` and none where they
    are whole, and ``treeWalkPaddedRows`` reads the rows it added."""
    import jax
    from tree_reference import gather_walk
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rng = np.random.default_rng(100 * depth + d + n)
    B = 64
    Xb = jnp.asarray(rng.integers(0, B, size=(n, d)).astype(np.int32))
    tree = _random_trees(rng, (), depth, d, B)
    sweep_counters.reset()
    jaxpr = jax.make_jaxpr(_fresh(trees.predict_tree))(Xb, *tree)
    all_selected = d <= trees._SELECT_MAX_WIDTH \
        and 2 ** depth <= trees._SELECT_MAX_NODES
    run = sweep_counters.run_to_json()
    assert run["treeGatherWalks"] == (0 if all_selected else 1)
    assert run["treeWalkPaddedRows"] == -n % 128
    assert len(_pads(jaxpr.jaxpr)) == (1 if n % 128 else 0)
    assert (not _row_long_gathers(jaxpr.jaxpr, n)) == all_selected
    got = jax.jit(_fresh(trees.predict_tree))(Xb, *tree)
    want = jax.jit(gather_walk)(Xb, *tree)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert (_bits(want) == _bits(np.float32(-0.0))).any()


def test_select_walk_leaves_mesh_rows_unpadded(as_tpu, mesh8):
    """Rows sharded over a mesh's data axis are walked as they are: a pad
    to whole tiles would reshard them."""
    import jax
    from tree_reference import gather_walk
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.parallel.mesh import shard_rows
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rng = np.random.default_rng(5)
    n, d, B = 1240, 28, 64
    Xb = shard_rows(jnp.asarray(rng.integers(0, B, size=(n, d)), jnp.int32))
    tree = _random_trees(rng, (), 12, d, B)
    sweep_counters.reset()
    jaxpr = jax.make_jaxpr(_fresh(trees.predict_tree))(Xb, *tree)
    assert not _pads(jaxpr.jaxpr)
    assert sweep_counters.run_to_json()["treeWalkPaddedRows"] == 0
    got = jax.jit(_fresh(trees.predict_tree))(Xb, *tree)
    np.testing.assert_array_equal(
        _bits(got), _bits(jax.jit(gather_walk)(Xb, *tree)))


_OPERANDS = [(case, 515) for case in ("int16_tables", "int8_codes",
                                      "bf16_leaves", "three_classes")] \
    + [("three_classes", n) for n in (1, 127, 128, 129, 1237)]


@pytest.mark.parametrize("case,n", _OPERANDS,
                         ids=[f"{c}-n{n}" for c, n in _OPERANDS])
def test_select_walk_of_other_operands(case, n, as_tpu, monkeypatch):
    """What the serving rungs and the sweep hand the walk: int16 tables
    (compared after promotion), int8 codes, bfloat16 leaves (returned as
    they are), and a ``[rounds, classes]`` stack through
    ``predict_ensemble`` (against the same ensemble over the gather
    walk) at row counts in and out of whole 128-row tiles: the rows, not
    batched over rounds or classes, are padded once."""
    import jax
    from tree_reference import gather_walk
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rng = np.random.default_rng(3 + n)
    d, B, depth = 28, 64, 5
    Xb = rng.integers(0, B, size=(n, d)).astype(
        np.int8 if case == "int8_codes" else np.int32)
    lead = (4, 3) if case == "three_classes" else ()
    feats, bins, leaves = _random_trees(
        rng, lead, depth, d, B,
        np.int16 if case == "int16_tables" else np.int32)
    if case == "bf16_leaves":
        leaves = leaves.astype(jnp.bfloat16)
    if case == "three_classes":
        kw = dict(n_out=3, learning_rate=jnp.float32(0.3),
                  base_score=jnp.float32(0.1), bootstrap=False)
        sweep_counters.reset()
        jaxpr = jax.make_jaxpr(
            lambda X, t: trees.predict_ensemble(X, t, **kw))(
                Xb, (feats, bins, leaves))
        assert sweep_counters.run_to_json()["treeWalkPaddedRows"] == -n % 128
        assert len(_pads(jaxpr.jaxpr)) == (1 if n % 128 else 0)
        got = jax.jit(lambda X, t: trees.predict_ensemble(X, t, **kw))(
            Xb, (feats, bins, leaves))
        monkeypatch.setattr(trees, "predict_tree", gather_walk)
        want = jax.jit(lambda X, t: trees.predict_ensemble(X, t, **kw))(
            Xb, (feats, bins, leaves))
        assert got.shape == (n, 3)
    else:
        got = jax.jit(_fresh(trees.predict_tree))(Xb, feats, bins, leaves)
        want = jax.jit(gather_walk)(Xb, feats, bins, leaves)
        assert got.dtype == leaves.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("d", [28, 300], ids=["higgs_zoo", "wide"])
@pytest.mark.parametrize("program", ["device_apply", "train_score_stacked"])
def test_scoring_programs_hold_no_row_long_gather(program, d, as_tpu):
    """At ``higgs_zoo``'s shapes (28 columns, 64 bins, depth 12) neither
    the winner's ``device_apply`` nor the sweep's scoring looks anything up
    row by row, and ``treeGatherWalks`` stays 0; on a frame wider than
    ``_SELECT_MAX_WIDTH`` the walk gathers its codes and the counter says
    so (``bin_data`` still counts)."""
    import jax
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rng = np.random.default_rng(11)
    B, depth, rounds = 64, 12, 2
    narrow = d <= trees._SELECT_MAX_WIDTH
    sweep_counters.reset()
    if program == "device_apply":
        n = 1031
        model = trees.TreeEnsembleModel(kind="rf_classifier", n_out=1,
                                        max_depth=depth)
        model.bin_edges = np.sort(rng.normal(size=(d, B - 1)), axis=1
                                  ).astype(np.float32)
        model.trees = _random_trees(rng, (rounds, 1), depth, d, B)
        X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        jaxpr = jax.make_jaxpr(model.device_apply)(
            model.device_params(), fr.VectorColumn(X))
        scope = ""
    else:
        k, n_tr, n = 2, 257, 1031
        args = (jnp.zeros((k, n_tr, d), jnp.int8), jnp.zeros((k, n_tr)),
                jnp.ones((k, n_tr)), jnp.zeros((k, n, d), jnp.int8),
                jnp.zeros(k), *(jnp.ones(1),) * 4)
        jaxpr = jax.make_jaxpr(lambda *a: trees.train_score_stacked(
            *a, n_rounds=rounds, max_depth=depth, n_bins=B, loss="squared",
            subsample=1.0, colsample=0.7, bootstrap=True, seed=0,
            hist="scatter", forest_margin=True))(*args)
        scope = "tree.predict"   # the grower's own gathers are not scoring
        assert _row_long_gathers(jaxpr.jaxpr, n_tr)   # the scope is read
    assert (not _row_long_gathers(jaxpr.jaxpr, n, scope)) == narrow
    assert sweep_counters.run_to_json()["treeGatherWalks"] == \
        (0 if narrow else 1)
