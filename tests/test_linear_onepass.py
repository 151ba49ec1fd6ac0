"""The linear descent's one-pass step (``models/linear.py::_onepass_terms``):
every Adam step reads the matrix once, a Pallas kernel over row blocks
computing the lanes' margins, loss derivative and gradient together.

Off the chip the kernel runs in Pallas's interpret mode. Its data term and
gradient against ``jax.value_and_grad`` of the objective ``_linear_descent``
writes, at ``highest`` (a single bfloat16 pass would miss by 1e-3), over
one block, several, and a table of fewer rows than one; twenty steps of the
descent, cold and warm-started, against the XLA program it replaces; the
gate that keeps the XLA program (narrow matrices, a kernel past its VMEM,
a mesh, any backend but the TPU's); the ``linearOnePassLanes`` counter.
The gate is asked while a program is traced, so a test that sets its
answer traces afresh (``gate``, ``_xla_and_one_pass``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import linear
from transmogrifai_tpu.models.linear import OpLinearSVC, OpLogisticRegression
from transmogrifai_tpu.utils.profiling import sweep_counters

#: (loss, classes): the softmax lanes at two and three classes, the hinge
#: and the squared loss
KINDS = [("softmax", 2), ("softmax", 3), ("hinge", 2), ("squared", 2)]


def _table(kind: str, C: int, seed: int, n: int = 700, d: int = 200,
           k: int = 3):
    """Columns of offset 5 and scale 3, one constant everywhere and one
    constant among the rows fold 0 trains on; ``wf [k, n]``, each fold 0
    on its validation rows. 700 rows are one block of 640 and 60 more; 200
    columns are no whole number of 128-lane tiles. Labels from a linear
    rule, no fold's two classes exactly as many (a fold's exactly balanced
    labels leave its intercept a gradient of 0 in one summation order and
    of rounding in another, which Adam makes a whole step)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32) * 3.0 + 5.0
    fold = np.arange(n) % k
    X[:, 7] = 2.0
    X[fold != 0, 11] = 1.5
    s = (X - 5.0) @ rng.normal(size=d).astype(np.float32) / 40.0
    if kind == "softmax":
        y = np.digitize(s, [-0.5, 0.5][:C - 1]).astype(np.float32)
    elif kind == "hinge":
        y = (s > 0.3).astype(np.float32)
    else:
        y = (4.0 * s + 10.0 + rng.normal(size=n)).astype(np.float32)
    wf = np.stack([(fold != f).astype(np.float32) for f in range(k)])
    return jnp.asarray(X), jnp.asarray(y), jnp.asarray(wf)


def _close(got, want, rel, floor=1e-6):
    """``got`` within ``rel`` of ``want``'s largest magnitude, or of
    ``floor`` where that is smaller."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    err = np.max(np.abs(got - want))
    assert err <= rel * max(np.max(np.abs(want)), floor), (err, rel)


@pytest.fixture
def gate(monkeypatch):
    """``gate(one_pass)`` sets what ``_one_pass_ok`` answers. jax keeps
    traces by function and shapes, so what a test traced under it is
    dropped after the test, and no other test meets those programs."""
    def take(one_pass: bool):
        monkeypatch.setattr(linear, "_one_pass_ok", lambda *_: one_pass)
    yield take
    jax.clear_caches()


def _xla_and_one_pass(gate, train, *args):
    """``train(*args)`` traced afresh, a new function each time, under each
    answer of the gate: the XLA program's result, then the one-pass
    step's."""
    out = []
    for one_pass in (False, True):
        gate(one_pass)
        out.append(jax.jit(lambda *a: train(*a))(*args))
    return out


@pytest.mark.parametrize("kind,C", KINDS)
def test_data_term_and_gradient_match_value_and_grad(kind, C):
    _check_data_term(kind, C, n=700)


@pytest.mark.parametrize("kind,C", KINDS)
@pytest.mark.parametrize("n,max_rows", [(100, None), (128, None), (700, 128)],
                         ids=["under_one_block", "one_block", "five_blocks"])
def test_data_term_over_any_count_of_blocks(monkeypatch, kind, C, n,
                                            max_rows):
    """100 rows are no whole block: all of them take the XLA block, and the
    kernel does not run; 128 are one block and no tail; 700 in blocks of
    at most 128 rows are five blocks, accumulated over the grid, and 60
    more."""
    if max_rows is not None:
        monkeypatch.setattr(linear, "_ONEPASS_MAX_ROWS", max_rows)
    assert n // linear._onepass_rows(n, 200) == {100: 0, 128: 1, 700: 5}[n]
    _check_data_term(kind, C, n=n)


def _check_data_term(kind, C, n):
    X, y, _ = _table(kind, C, seed=1, n=n)
    n, d = X.shape
    rng = np.random.default_rng(2)
    L, Ck = 6, (C if kind == "softmax" else 1)
    w = rng.uniform(size=(L, n)).astype(np.float32) * 3.0
    w[:, ::5] = 0.0                                 # rows no lane holds
    w = jnp.asarray(w)
    center, scale = linear._standardize_stats(X, jnp.ones(n, jnp.float32))
    inv = 1.0 / scale
    We = jnp.asarray(rng.normal(size=(L, d, Ck)).astype(np.float32)) * 0.05
    off = jnp.asarray(rng.normal(size=(L, Ck)).astype(np.float32))
    ym = jnp.asarray(rng.normal(size=L).astype(np.float32)) + 10.0
    ysd = jnp.full(L, 4.0, jnp.float32)

    def data(We, off):
        z = jnp.einsum("nd,ldc->cln", (X - center) * inv, We,
                       precision="highest") + off.T[:, :, None]
        if kind == "softmax":
            hot = jax.nn.one_hot(y.astype(jnp.int32), C, axis=0)
            per_row = -jnp.sum(jax.nn.log_softmax(z, axis=0)
                               * hot[:, None, :], axis=0)
        elif kind == "hinge":
            per_row = jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * z[0])
        else:
            per_row = 0.5 * (z[0] - (y - ym[:, None]) / ysd[:, None]) ** 2
        lanes = jnp.sum(per_row * w, axis=1)
        return jnp.sum(lanes), lanes

    (_, loss), (dWe, db) = jax.value_and_grad(
        data, (0, 1), has_aux=True)(We, off)
    got = linear._onepass_terms(
        X, center, inv, We, off, *linear._onepass_rows_of(w, y, ym, ysd),
        loss_kind=kind)
    for g, want in zip(got, (loss, dWe, db)):
        _close(g, want, 2e-5)


def _descent(gate, kind, C, X, y, wf):
    rp = jnp.asarray([0.01, 0.1], jnp.float32)
    en = jnp.asarray([0.0, 0.5], jnp.float32)
    kw = dict(loss_kind=kind, n_classes=C, max_iter=20, fit_intercept=True,
              standardize=True)
    return _xla_and_one_pass(
        gate, lambda *a: linear._train_linear.__wrapped__(*a, **kw),
        X, y, wf, rp, en)


@pytest.mark.parametrize("kind,C", KINDS)
@pytest.mark.parametrize("seed", [0, 1])
def test_twenty_steps_match_the_xla_descent(gate, kind, C, seed):
    """3 folds x 2 points, pure L2 and elastic net; W, b and the last loss
    of every lane. The kernel's products are three bfloat16 passes where
    XLA:CPU's are exact float32: the gap is that rounding after Adam's
    steps, 5e-5 of the weights' largest at most over these tables."""
    X, y, wf = _table(kind, C, seed)
    _check_descent(gate, kind, C, X, y, wf)


@pytest.mark.parametrize("kind,C", KINDS)
def test_a_table_under_one_block_descends_as_xla(gate, kind, C):
    """100 rows, no whole block of the kernel: every step takes the XLA
    block alone, and the descent is the XLA program's."""
    X, y, wf = _table(kind, C, seed=5, n=100)
    _check_descent(gate, kind, C, X, y, wf)


def _check_descent(gate, kind, C, X, y, wf):
    xla, one = _descent(gate, kind, C, X, y, wf)
    assert np.all(np.isfinite(np.asarray(one[0])))
    _close(one[0], xla[0], 5e-4)
    _close(one[1], xla[1], 5e-4)
    _close(one[2], xla[2], 2e-5)
    # the column constant everywhere, and among fold 0's rows, get nothing
    assert np.all(np.asarray(one[0])[:, :, 7] == 0.0)
    assert np.all(np.asarray(one[0])[0, :, 11] == 0.0)


@pytest.mark.parametrize("kind,C", [("softmax", 2), ("hinge", 2),
                                    ("squared", 2)])
def test_warm_refit_matches_the_xla_descent(gate, kind, C):
    """The winner's refit: one lane on all rows from the fold-averaged
    parameters of a short cold descent. Its intercept may lie near 0 (the
    weights' offsets cancel in it), so its gap is held against 1."""
    X, y, wf = _table(kind, C, seed=3)
    cold, _ = _descent(gate, kind, C, X, y, wf)
    W0, b0 = jnp.mean(cold[0][:, 1], axis=0), jnp.mean(cold[1][:, 1], axis=0)
    n = X.shape[0]
    kw = dict(loss_kind=kind, max_iter=20, fit_intercept=True,
              standardize=True)
    got = _xla_and_one_pass(
        gate, lambda *a: linear._train_linear_from(*a, **kw),
        X, y, jnp.ones(n, jnp.float32), jnp.float32(0.1), jnp.float32(0.5),
        W0, b0)
    for a, b, rel, floor in zip(got[0], got[1], (5e-4, 5e-4, 2e-5),
                                (1e-6, 1.0, 1e-6)):
        _close(b, a, rel, floor)


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(n, d):
    return jax.ShapeDtypeStruct((n, d), jnp.float32)


def test_gate_takes_wide_matrices_on_the_tpu(as_tpu):
    assert linear._one_pass_ok(_shape(4, linear._ONEPASS_MIN_D), 24, 2)


@pytest.mark.parametrize("d", [28, 54])
def test_gate_keeps_narrow_matrices_on_xla(as_tpu, d):
    """``higgs_zoo``'s 28 columns and ``covtype_multi``'s 54."""
    assert not linear._one_pass_ok(_shape(4, d), 24, 2)


@pytest.mark.parametrize("fits,past", [((37_120, 12, 1), (37_248, 12, 1)),
                                      ((8_960, 24, 17), (8_960, 24, 18))],
                         ids=["columns", "classes"])
def test_gate_keeps_a_kernel_past_its_vmem_on_xla(as_tpu, fits, past):
    """(columns, lanes, classes) on both sides of the VMEM the kernel may
    ask for: one 128-column tile more than the hinge family's 4 x 3 lanes
    take, and one class more than a softmax of 8 x 3 lanes takes over
    ``criteo_ctr``'s 8,960 columns (``tests/test_tpu_compile.py`` compiles
    both sides)."""
    (d, lanes, outputs), (d_past, lanes_past, outputs_past) = fits, past
    assert linear._one_pass_ok(_shape(108_000, d), lanes, outputs)
    assert not linear._one_pass_ok(_shape(108_000, d_past), lanes_past,
                                   outputs_past)


def test_gate_keeps_a_mesh_on_xla(as_tpu, mesh8):
    assert not linear._one_pass_ok(_shape(8, linear._ONEPASS_MIN_D), 24, 2)


def test_gate_keeps_the_cpu_on_xla():
    assert jax.default_backend() == "cpu"
    assert not linear._one_pass_ok(_shape(4, 4 * linear._ONEPASS_MIN_D),
                                   24, 2)


def _wide(n=400, d=160, seed=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=n) > 0.2).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y), jnp.ones(n, jnp.float32)


@pytest.mark.parametrize("one_pass", [False, True])
def test_onepass_lanes_counted_at_dispatch(gate, one_pass):
    """A fold weighting x grid point of the sweep, and a warm refit, count
    in ``linearOnePassLanes`` where the gate took the one-pass step, and in
    ``linearDescentLanes`` either way."""
    gate(one_pass)
    X, y, w = _wide()
    grid = [{"reg_param": 0.01, "elastic_net_param": e, "max_iter": 5}
            for e in (0.0, 0.5)]
    sweep_counters.reset()
    svc = OpLinearSVC()
    folds = svc._lane_params(X, y, jnp.stack([w, w, w]), grid, 2)
    lr = OpLogisticRegression(elastic_net_param=0.5, max_iter=5)
    lr.grid_fit_arrays(X, y, w, grid)
    _, warm = svc.refit_winner(X, y, w, grid[1], warm=folds, lane=1)
    counts = sweep_counters.run_to_json()
    assert warm
    assert counts["linearDescentLanes"] == 3 * 2 + 1 + 1   # LR: one Newton
    lanes = 3 * 2 + 1 + 1 if one_pass else 0
    assert counts["linearOnePassLanes"] == lanes
    from transmogrifai_tpu.utils.prometheus import build_registry
    assert f"transmogrifai_sweep_linear_onepass_lanes_total {lanes}\n" in \
        build_registry().render()
