"""Programs of the main path compiled for the TPU v5e at a benchmark cell's
own size, off the chip: the TPU's compiler is installed here and compiles
for a chip that is described and not attached. Nothing runs, so nothing
here is a time or a result: what is held is that the compiler accepts the
program and how much device memory it plans for it.

The topology is described inside a fixture of THIS file (never at import,
never in ``conftest.py``): only one process may load the TPU's library,
and only the worker that is handed this file does.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _row_major(shape, sharding, dtype=jnp.float32):
    """An argument as the program meets it on the chip: the layout a
    previous program left it in, not one the compiler may choose."""
    from jax.experimental.layout import Format, Layout
    return jax.ShapeDtypeStruct(shape, dtype, sharding=Format(
        Layout(major_to_minor=tuple(range(len(shape)))), sharding))


@pytest.mark.parametrize("k,g", [(3, 4), (1, 1)])
def test_newton_in_place_makes_no_copy_of_the_matrix(one_chip, k, g):
    """``amazon_text_train``'s Newton points (the sweep's 3 folds x 4
    strengths, the winner's refit) over the 900,000 x 1,027 training split:
    the compiler plans well under a gigabyte of temporaries beside the 4.1
    GB matrix. With the folds' moments taken as weighted reductions
    (``_lane_stats``) it planned 3.9 GB: a column-major copy of the
    matrix."""
    from transmogrifai_tpu.models import linear
    n, d = 900_000, 1_027
    arg = lambda *shape: _row_major(shape, one_chip)  # noqa: E731
    compiled = linear._newton_in_place.lower(
        arg(n, d), arg(n), arg(k, n), arg(g),
        chunk=linear._newton_chunk_rows(n, d, k * g), fit_intercept=True,
        standardize=True).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 4.0e9      # the padded matrix
    assert memory.temp_size_in_bytes < 1.0e9, memory.temp_size_in_bytes


def _broadcasts(text):
    """(dims, minor-to-major layout) of every broadcast in compiled text."""
    import re
    pat = re.compile(r"\[([0-9,]+)\]\{([0-9,]+)[^}]*\} broadcast\(")
    for m in pat.finditer(text):
        yield ([int(x) for x in m.group(1).split(",")],
               [int(x) for x in m.group(2).split(",")])


@pytest.mark.parametrize("k,n", [(3, 174_303), (1, 58_101)],
                         ids=["validation", "holdout"])
def test_tree_walk_keeps_rows_on_the_lanes(one_chip, monkeypatch, k, n):
    """``covtype_multi``'s depth-12 forest walked under the sweep's fold
    and lane ``vmap``s (1 lane, 3 rounds, 7 classes, 54 int8 columns) over
    its validation folds and its holdout, row counts that are no whole
    number of 128-lane tiles: every comparison of a row's node with a
    level table of 128 entries or more keeps the ROWS minor. Over the
    unpadded rows the compiler put the table axis there (4,467 ms against
    253 ms a validation walk on the v5e)."""
    from transmogrifai_tpu.models import trees
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rounds, classes, depth, d = 3, 7, 12, 54
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    lead = (k, 1, rounds, classes)
    stack = (tuple(arg(lead + (2 ** lv,), jnp.int32) for lv in range(depth)),
             tuple(arg(lead + (2 ** lv,), jnp.int32) for lv in range(depth)),
             arg(lead + (2 ** depth,), jnp.float32))

    def program(Xva, forest):
        def fold_fn(X_k, forest_k):
            def lane_fn(t):
                return trees.predict_ensemble(
                    X_k, t, n_out=classes, learning_rate=1.0,
                    base_score=0.0, bootstrap=True).T
            return jax.vmap(lane_fn)(forest_k)
        return jax.vmap(fold_fn)(Xva, forest)

    text = jax.jit(program).trace(arg((k, n, d), jnp.int8), stack).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    walked = (n, n + (-n % 128))
    compared = [(dims, layout) for dims, layout in _broadcasts(text)
                if len(dims) == 6 and dims[4] >= 128 and dims[5] in walked]
    assert len(compared) >= depth - 7 + 1   # levels 7-11 and the leaves
    assert all(layout[0] == 5 for _, layout in compared), compared[:3]


@pytest.mark.parametrize("k,g", [(3, 8), (1, 1)], ids=["sweep", "refit"])
def test_gram_folds_makes_no_copy_of_the_matrix(one_chip, k, g):
    """``taxi_duration_train``'s least squares (the sweep's 3 fold
    weightings x 8 points, a linear winner's refit) over the 1,312,780 x 9
    training split: one pass of row chunks, so the compiler plans a chunk's
    working set beside the matrix, not a weighted copy of it a fold, and
    accepts the lanes' Cholesky and coordinate-descent loops."""
    from transmogrifai_tpu.models import linear
    n, d = 1_312_780, 9
    arg = lambda *shape: _row_major(shape, one_chip)  # noqa: E731
    compiled = linear._gram_folds.lower(
        arg(n, d), arg(n), arg(k, n), arg(g), arg(g),
        chunk=linear._gram_chunk_rows(n, d, k)).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.2e9, memory.temp_size_in_bytes


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("k,g,loss,C", [(3, 8, "softmax", 2),
                                        (3, 4, "hinge", 2),
                                        (1, 1, "softmax", 2)],
                         ids=["lr", "svc", "refit"])
def test_onepass_descent_makes_no_copy_of_the_matrix(one_chip, monkeypatch,
                                                     k, g, loss, C):
    """``criteo_ctr``'s Adam lanes over the 108,000 x 8,960 training split
    (logistic regression's 8 points x 3 folds x 2 classes, the hinge
    family's 4 x 3, a winner's one lane): every step is the one-pass
    kernel, which Mosaic accepts inside the VMEM it asks for, beside no
    copy of the 3.9 GB matrix."""
    from transmogrifai_tpu.models import linear
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d = 108_000, 8_960
    arg = lambda *shape: _row_major(shape, one_chip)  # noqa: E731
    assert linear._one_pass_ok(arg(n, d), k * g,
                               C if loss == "softmax" else 1)
    compiled = linear._train_linear.lower(
        arg(n, d), arg(n), arg(k, n), arg(g), arg(g), loss_kind=loss,
        n_classes=C, max_iter=200, fit_intercept=True,
        standardize=True).compile()
    assert _custom_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("d,g,loss,C,kernels", [
    (37_120, 4, "hinge", 2, 1), (37_248, 4, "hinge", 2, 0),
    (8_960, 8, "softmax", 17, 1), (8_960, 8, "softmax", 18, 0)],
    ids=["columns_fit", "columns_past", "classes_fit", "classes_past"])
def test_onepass_kernel_fits_its_vmem_or_is_not_taken(one_chip, monkeypatch,
                                                      d, g, loss, C, kernels):
    """Both sides of the VMEM the kernel may ask for, as
    ``_onepass_vmem_bytes`` bounds it: the widest matrix the hinge
    family's 4 x 3 lanes take the kernel over and one 128-column tile more,
    the most classes a softmax of 8 x 3 lanes takes it for over
    ``criteo_ctr``'s 8,960 columns and one class more. Where the bound
    lets the kernel in, Mosaic accepts it inside that VMEM; one step past,
    the program holds no kernel and compiles as XLA's."""
    from transmogrifai_tpu.models import linear
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, k = 4_096, 3
    arg = lambda *shape: _row_major(shape, one_chip)  # noqa: E731
    compiled = linear._train_linear.lower(
        arg(n, d), arg(n), arg(k, n), arg(g), arg(g), loss_kind=loss,
        n_classes=C, max_iter=200, fit_intercept=True,
        standardize=True).compile()
    assert _custom_calls(compiled) == kernels


@pytest.mark.parametrize("loss,C", [("softmax", 2), ("hinge", 1)])
def test_onepass_kernel_takes_a_width_of_no_whole_tiles(one_chip, monkeypatch,
                                                        loss, C):
    """``amazon_polarity_text``'s 900,000 x 1,027 training split, its Adam
    points' 4 x 3 lanes: a block of rows spans all 1,027 columns, no whole
    number of 128-lane tiles, and the kernel copies nothing of the matrix.
    (The whole descent there also plans the column-major copy its folds'
    moments take, ``_lane_stats``, as the XLA program does.)"""
    from transmogrifai_tpu.models import linear
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, L = 900_000, 1_027, 12
    arg = lambda *shape: _row_major(shape, one_chip)  # noqa: E731

    def terms(X, center, inv_scale, We, off, w, y, y_mean, y_sd):
        return linear._onepass_terms(
            X, center, inv_scale, We, off,
            *linear._onepass_rows_of(w, y, y_mean, y_sd), loss_kind=loss)
    compiled = jax.jit(terms).trace(
        arg(n, d), arg(d), arg(d), arg(L, d, C), arg(L, C), arg(L, n),
        arg(n), arg(L), arg(L)).lower(lowering_platforms=("tpu",)).compile()
    assert _custom_calls(compiled) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_narrow_descent_keeps_the_xla_program(one_chip, monkeypatch):
    """``higgs_zoo``'s 28 columns (480,000 training rows a fold batch's
    split, its L1 points' 4 x 3 lanes) stay below the gate's width: the
    program the gate picks holds no kernel."""
    from transmogrifai_tpu.models import linear
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d = 480_000, 28
    arg = lambda *shape: _row_major(shape, one_chip)  # noqa: E731
    compiled = linear._train_linear.lower(
        arg(n, d), arg(n), arg(3, n), arg(4), arg(4), loss_kind="softmax",
        n_classes=2, max_iter=200, fit_intercept=True,
        standardize=True).compile()
    assert _custom_calls(compiled) == 0
