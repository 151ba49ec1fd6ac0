"""A forest round carries only the rows its bootstrap drew
(``models/trees.py::drawn_rows``, ``forest_rows_carried``,
``train_ensemble(draws=)``): the compacted forest is the all-rows forest,
draws the cap does not hold grow on all rows, boosting, single trees and
the sharded body are untouched, and the dispatch sites count rows given
and rows carried.

The sorted engine is asked for by name: off a TPU ``_hist_engine`` answers
``scatter``, and the sorted grower runs in float32 there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_trees import _eqns

from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.trees import (
    drawn_rows, forest_draws, forest_rows_carried, predict_ensemble,
    train_ensemble, train_score_stacked,
)

N, D, B, DEPTH, ROUNDS = 20_000, 8, 32, 5, 3
N_CAP = forest_rows_carried(N, 1.0, bootstrap=True, hist="sorted")

#: loss, outputs, whether the operands are whole (or dyadic) numbers, so
#: that every float32 sum is exact in any order
_FORESTS = {
    "binary_logistic": ("logistic", 1, True),
    "onehot_k3": ("squared_onehot", 3, True),
    "regression": ("squared", 1, False),
}


def _kw(loss, n_out, **over):
    kw = dict(n_rounds=ROUNDS, max_depth=DEPTH, n_bins=B, n_out=n_out,
              loss=loss, learning_rate=jnp.float32(1.0),
              reg_lambda=jnp.float32(1e-3), gamma=jnp.float32(0.0),
              min_child_weight=jnp.float32(1.0), subsample=1.0,
              colsample=0.7, base_score=jnp.float32(0.0), bootstrap=True,
              seed=3, hist="sorted")
    kw.update(over)
    return kw


def _table(loss, n_out, folds, n=N, seed=0):
    """``[folds, n, D]`` codes, labels, unit weights and ``[folds, 1000, D]``
    validation codes."""
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, B, size=(folds, n, D)).astype(np.int8)
    signal = Xb[..., 0] / B + 0.5 * (Xb[..., 1] > B // 2)
    if loss == "squared":
        y = signal + 0.1 * rng.normal(size=signal.shape)
    elif n_out > 1:
        y = np.minimum((signal * n_out / 1.5).astype(np.int64), n_out - 1)
    else:
        y = signal + 0.3 * rng.normal(size=signal.shape) > 0.8
    Xva = rng.integers(0, B, size=(folds, 1000, D)).astype(np.int8)
    return (jnp.asarray(Xb), jnp.asarray(y, jnp.float32),
            jnp.ones((folds, n), jnp.float32), jnp.asarray(Xva))


def _base(loss, y):
    """The base score the estimators give: a regression forest grows from
    the label mean (its residuals' prefix sums stay small), a classifier
    from 0."""
    return jnp.float32(jnp.mean(y) if loss == "squared" else 0.0)


def _draws(kw):
    draws = drawn_rows(N, n_rounds=kw["n_rounds"], seed=kw["seed"],
                       subsample=kw["subsample"], hist=kw["hist"])
    assert draws is not None        # the cap holds the draws of this seed
    return draws


def _train(Xb, y, w, kw, draws):
    """(trees, gains) a fold, ``train_ensemble`` under a fold ``vmap``
    where there is more than one fold (the one ``draws`` for all folds)."""
    fit = functools.partial(train_ensemble, **kw)
    if Xb.shape[0] == 1:
        out = fit(Xb[0], y[0], w[0], draws)
        return jax.tree_util.tree_map(lambda a: a[None], out)
    return jax.vmap(lambda X, y, w: fit(X, y, w, draws))(Xb, y, w)


def _scores(Xva, forest, n_out):
    score = functools.partial(predict_ensemble, n_out=n_out,
                              learning_rate=jnp.float32(1.0),
                              base_score=jnp.float32(0.0), bootstrap=True)
    return np.asarray(jax.vmap(score)(Xva, forest))


#: what float32 allows a leaf of inexact operands: a leaf's gradient sum is
#: a difference of two prefix sums over the rows in leaf order
#: (``_segment_sums``), which run up to a few thousand here (the first
#: leaves' residuals share a sign), so it stands within some 1e-7 of that,
#: over a hessian sum of 1 or more
_LEAF_ATOL = 1e-3


def _assert_same_forest(got, want, exact, leaf_atol=_LEAF_ATOL):
    (feats, bins, leaves), gains = got
    (feats0, bins0, leaves0), gains0 = want
    for level in range(DEPTH):
        np.testing.assert_array_equal(feats[level], feats0[level])
        np.testing.assert_array_equal(bins[level], bins0[level])
    if exact:
        np.testing.assert_array_equal(leaves, leaves0)
        np.testing.assert_array_equal(gains, gains0)
    else:
        np.testing.assert_allclose(leaves, leaves0, rtol=0, atol=leaf_atol)
        np.testing.assert_allclose(gains, gains0, rtol=1e-4)


@pytest.mark.parametrize("folds", [1, 2], ids=["plain", "two_folds"])
@pytest.mark.parametrize("case", sorted(_FORESTS))
def test_compacted_forest_is_the_all_rows_forest(case, folds):
    """At 20,000 rows the cap (15,104) engages: the forest grown on the
    drawn rows has the all-rows forest's split feature and bin at every
    level, its leaves and gains to float32 rounding and its validation
    scores with them (to the bit, and within 1e-6, where the operands sum
    exactly); and the sweep's stacked program scores as the compacted
    forest does."""
    loss, n_out, exact = _FORESTS[case]
    assert N_CAP == 15_104 < N
    Xb, y, w, Xva = _table(loss, n_out, folds)
    kw = _kw(loss, n_out, base_score=_base(loss, y))
    draws = _draws(kw)
    compacted = _train(Xb, y, w, kw, draws)
    all_rows = _train(Xb, y, w, kw, None)
    _assert_same_forest(compacted, all_rows, exact)
    got, want = (_scores(Xva, f[0], n_out) for f in (compacted, all_rows))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 if exact else _LEAF_ATOL)
    stacked = train_score_stacked(
        Xb, y, w, Xva, jnp.full(folds, kw["base_score"]),
        *(jnp.asarray([v], jnp.float32)
          for v in (1.0, 1e-3, 0.0, 1.0)), draws,
        n_rounds=ROUNDS, max_depth=DEPTH, n_bins=B, loss=loss,
        subsample=1.0, colsample=0.7, bootstrap=True, seed=3,
        hist="sorted", forest_margin=False, n_out=n_out)
    stacked = np.asarray(stacked)[:, 0]          # the one lane
    lane = got.transpose(0, 2, 1) if n_out > 1 else got[..., 0]
    np.testing.assert_allclose(stacked, lane, rtol=0, atol=1e-6)


def test_draws_match_the_draw_inside_the_program():
    """``forest_draws`` is the draw a forest makes inside its program:
    the same keys, round by round."""
    draws = np.asarray(forest_draws(n=N, n_rounds=ROUNDS, seed=3,
                                    subsample=1.0))
    keys = jax.random.split(jax.random.PRNGKey(3), ROUNDS)
    for r in range(ROUNDS):
        want = jax.random.poisson(jax.random.split(keys[r])[0], 1.0, (N,))
        np.testing.assert_array_equal(draws[r], np.asarray(want))
    live = (draws > 0).sum(axis=1)
    assert (live < N_CAP).all() and (live > 0.6 * N).all(), live


def test_draws_the_cap_does_not_hold_are_not_handed_out(monkeypatch):
    """No drawn row is ever dropped: where some round draws more rows
    than the cap (here a cap forced under the live count, about 12,640 of
    20,000) ``drawn_rows`` hands out no draws and the forest grows on all
    rows; and a caller that passes such draws all the same gets NaN
    leaves, not a forest of fewer rows."""
    spec = dict(n_rounds=ROUNDS, seed=3, subsample=1.0, hist="sorted")
    assert drawn_rows(N, **spec) is not None
    assert drawn_rows(N, **{**spec, "hist": "scatter"}) is None
    assert drawn_rows(1_000, **spec) is None            # the cap is n
    draws = forest_draws(n=N, n_rounds=ROUNDS, seed=3, subsample=1.0)
    monkeypatch.setattr(trees, "forest_rows_carried",
                        lambda n, *a, **k: 8_192)
    assert drawn_rows(N, **spec) is None
    Xb, y, w, _ = _table("squared", 1, 1, seed=1)
    (_f, _b, leaves), _g = jax.jit(functools.partial(   # a fresh trace
        train_ensemble.__wrapped__, **_kw("squared", 1)))(
            Xb[0], y[0], w[0], draws)
    assert np.isnan(np.asarray(leaves)).all()


def _conds(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "cond"]


def _row_gather_lengths(jaxpr, at_least):
    """Leading dimensions of the gathers that look ``at_least`` rows or
    more up one by one, whatever batch axes come first."""
    out = set()
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "gather":
            out |= {s for s in eqn.outvars[0].aval.shape if s >= at_least}
    return out


def _stacked_jaxpr(bootstrap, n, n_out=1, loss="squared", folds=2, lanes=2,
                   draws=None):
    args = (jnp.zeros((folds, n, D), jnp.int8), jnp.zeros((folds, n)),
            jnp.ones((folds, n)), jnp.zeros((folds, 64, D), jnp.int8),
            jnp.zeros(folds), *(jnp.ones(lanes),) * 4, draws)
    return jax.make_jaxpr(lambda *a: train_score_stacked(
        *a, n_rounds=2, max_depth=3, n_bins=B, loss=loss, subsample=1.0,
        colsample=0.7, bootstrap=bootstrap, seed=0, hist="sorted",
        forest_margin=bootstrap, n_out=n_out))(*args).jaxpr


@pytest.mark.parametrize("n_out,loss", [(1, "squared"),
                                        (3, "squared_onehot")])
def test_stacked_forest_program_compacts_once_a_round(n_out, loss):
    """Under the sweep's fold and lane ``vmap``s (and the class loop) the
    drawn rows' positions are computed once a round a program: the draws
    are an argument no batch axis reaches, so the ``[n_cap]`` index
    scatter stays unbatched, each fold gathers ``n_cap`` rows by it, and
    the program holds ONE grower (no ``cond``, no second engine)."""
    draws = forest_draws(n=N, n_rounds=2, seed=0, subsample=1.0)
    jaxpr = _stacked_jaxpr(True, N, n_out=n_out, loss=loss, draws=draws)
    assert not _conds(jaxpr)
    index_scatters = [
        e for e in _eqns(jaxpr) if e.primitive.name == "scatter"
        and e.outvars[0].aval.dtype == jnp.int32
        and N_CAP in e.outvars[0].aval.shape]
    assert [e.outvars[0].aval.shape for e in index_scatters] == [(N_CAP,)]
    lengths = _row_gather_lengths(jaxpr, 4_096)
    assert N_CAP in lengths and N not in lengths


def test_forest_without_draws_is_the_program_it_always_was():
    """Where ``drawn_rows`` hands out none (a small table, draws the cap
    does not hold) the stacked forest program draws inside and moves every
    row: no ``n_cap``-long gather, no ``cond``."""
    jaxpr = _stacked_jaxpr(True, N)
    assert not _conds(jaxpr)
    assert min(_row_gather_lengths(jaxpr, 4_096)) >= N
    with pytest.raises(ValueError, match="draws"):       # the cap is n
        train_ensemble(jnp.zeros((1_000, D), jnp.int8), jnp.zeros(1_000),
                       jnp.ones(1_000), jnp.ones((ROUNDS, 1_000), jnp.int32),
                       **_kw("squared", 1))


@pytest.mark.parametrize("program", ["gbt_stacked", "single_tree",
                                     "sharded_body", "scatter_forest"])
def test_programs_that_carry_every_row_take_no_draws(program, mesh8):
    """Boosting (every row's prediction feeds the next round), a single
    tree (no bootstrap), a shard of ``train_ensemble_sharded`` (its live
    count differs a shard) and a forest on the scatter engine: the rule
    gives them all their rows, ``train_ensemble`` refuses draws for them,
    and their programs hold no ``cond`` and no row gather shorter than the
    rows given (compacted, a level's padded slots are fewer than the
    rows)."""
    from jax.sharding import PartitionSpec as P
    from transmogrifai_tpu.parallel.mesh import DATA_AXIS
    n = N
    Xb, y, w = (jnp.zeros((n, D), jnp.int8), jnp.zeros(n), jnp.ones(n))
    over = {"gbt_stacked": dict(bootstrap=False),
            "single_tree": dict(bootstrap=False, n_rounds=1, colsample=1.0),
            "sharded_body": dict(data_axis=DATA_AXIS),
            "scatter_forest": dict(hist="scatter")}[program]
    kw = _kw("logistic" if program == "gbt_stacked" else "squared", 1,
             **over)
    assert forest_rows_carried(
        n, 1.0, bootstrap=kw["bootstrap"], hist=kw["hist"],
        data_axis=kw.get("data_axis")) == n
    if program == "gbt_stacked":
        jaxpr = _stacked_jaxpr(False, n, loss="logistic")
    elif program == "sharded_body":
        fn = jax.shard_map(
            functools.partial(train_ensemble, **kw), mesh=mesh8.mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(), check_vma=False)
        jaxpr = jax.make_jaxpr(fn)(     # 20,000 rows a shard
            jnp.zeros((8 * n, D), jnp.int8), jnp.zeros(8 * n),
            jnp.ones(8 * n)).jaxpr
    else:
        jaxpr = jax.make_jaxpr(functools.partial(
            train_ensemble, **kw))(Xb, y, w).jaxpr
    assert not _conds(jaxpr)
    assert min(_row_gather_lengths(jaxpr, 4_096)) >= N   # slots >= rows
    if program != "sharded_body":       # outside a mesh there is no axis
        with pytest.raises(ValueError, match="draws"):
            train_ensemble(Xb, y, w, jnp.ones((kw["n_rounds"], n),
                                              jnp.int32), **kw)


# -- the counters at the dispatch sites ----------------------------------------

@pytest.fixture
def sorted_engine(monkeypatch):
    """The TPU's engine choice for a large fit, off the TPU."""
    monkeypatch.setattr(trees, "_hist_engine",
                        lambda *a, **k: "sorted")


def _forest_counters():
    from transmogrifai_tpu.utils.profiling import sweep_counters
    run = sweep_counters.run_to_json()
    return run.get("forestRowsTotal"), run.get("forestRowsCarried")


@pytest.mark.parametrize("n,carried", [(N, N_CAP), (1_000, 1_000)])
def test_forest_rows_are_counted_where_a_program_is_dispatched(
        n, carried, sorted_engine):
    from transmogrifai_tpu.utils.profiling import sweep_counters
    folds, lanes, rounds, depth = 2, 2, 2, 2
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.normal(size=(n, 4)).astype(np.float32))
    y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    w = jnp.ones(n, jnp.float32)
    est = trees.OpRandomForestClassifier()
    params = {"num_trees": rounds, "max_depth": depth, "max_bins": 16}
    merged = {**est.default_params, "num_rounds": rounds,
              "max_depth": depth, "max_bins": 16}
    _, Xb, _ = est._binned(X, 16)
    sweep_counters.reset()
    assert _forest_counters() == (None, None)
    # a sweep unit: folds x lanes x rounds trees of n rows each
    est.tree_stack_scores(
        jnp.stack([Xb] * folds), jnp.stack([y] * folds),
        jnp.stack([w] * folds), jnp.stack([Xb[:64]] * folds),
        [merged] * lanes, ("squared", 1, 0.0))
    trees_swept = folds * lanes * rounds
    assert _forest_counters() == (n * trees_swept, carried * trees_swept)
    # the winner's refit: rounds more trees
    est.refit_winner(X, y, w, params)
    total = trees_swept + rounds
    assert _forest_counters() == (n * total, carried * total)
    # a warm process dispatches without retracing, and still counts
    est.refit_winner(X, y, w, params)
    assert _forest_counters() == (n * (total + rounds),
                                  carried * (total + rounds))


def test_a_forest_the_cap_does_not_hold_counts_every_row(
        sorted_engine, monkeypatch):
    """The dispatch site of a forest whose draws pass the cap (forced
    here) hands the program no draws, counts all rows carried, and fits
    the all-rows forest."""
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.normal(size=(N, 4)).astype(np.float32))
    y = jnp.asarray((rng.random(N) < 0.5).astype(np.float32))
    w = jnp.ones(N, jnp.float32)
    est = trees.OpRandomForestClassifier()
    params = {"num_trees": 2, "max_depth": 3, "max_bins": 16}
    sweep_counters.reset()
    compacted = est.fit_arrays(X, y, w, params)
    assert _forest_counters() == (2 * N, 2 * N_CAP)
    monkeypatch.setattr(trees, "forest_rows_carried",
                        lambda n, *a, **k: 8_192 if n == N else n)
    sweep_counters.reset()
    all_rows = est.fit_arrays(X, y, w, params)
    assert _forest_counters() == (2 * N, 2 * N)
    for a, b in zip(jax.tree_util.tree_leaves(compacted.trees),
                    jax.tree_util.tree_leaves(all_rows.trees)):
        np.testing.assert_array_equal(a, b)      # whole-number operands


def test_boosting_counts_no_forest_rows(sorted_engine):
    from transmogrifai_tpu.utils.profiling import sweep_counters
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(N, 4)).astype(np.float32))
    y = jnp.asarray((rng.random(N) < 0.5).astype(np.float32))
    sweep_counters.reset()
    trees.OpGBTClassifier().fit_arrays(
        X, y, jnp.ones(N), {"num_rounds": 2, "max_depth": 2, "max_bins": 16})
    run = sweep_counters.run_to_json()
    assert "forestRowsTotal" not in run and "forestRowsCarried" not in run
