"""Operations and bytes of a REGRESSION zoo's trainers, from shapes.

What the stated algorithm needs, whatever implements it (as
``chipbench.opcount`` counts): least squares solved from the normal
equations needs every weighting's Gram ``Xbᵀ diag(w) Xb`` over the
``n x (d+1)`` matrix with its ones column, ``2 n (d+1)^2`` operations a
weighting, and ONE read of the ``n x d`` float32 training split and of the
weightings' ``n`` float32 weights for all of them together; a lane's solve
after that is ``d``-sized and not counted. A squared-loss tree level is the
histograms ``opcount.tree_hist_ensemble`` counts.
"""

from __future__ import annotations

from chipbench import opcount

LINEAR = "least_squares"


def gram_work(n: int, d: int, k: int) -> tuple[float, float]:
    """``(operations, bytes)`` of ``k`` weightings' Grams over ``n`` rows of
    ``d`` columns."""
    return 2.0 * k * n * (d + 1.0) ** 2, 4.0 * n * d + 4.0 * k * n


def gram_train_work(zoo: list, shapes: dict) -> tuple[float, float]:
    """The Gram passes of one whole train: the sweep's ``k`` fold
    weightings of the training split, and a linear winner's refit (one
    weighting of it)."""
    n, d, k = (shapes[key] for key in ("n_train", "d", "k"))
    flops, bytes_ = 0.0, 0.0
    for ci, fam in enumerate(zoo):
        if fam["trainer"] != LINEAR:
            continue
        f, b = gram_work(n, d, k)
        flops, bytes_ = flops + f, bytes_ + b
        if ci == shapes["winner"][0]:
            f, b = gram_work(n, d, 1)
            flops, bytes_ = flops + f, bytes_ + b
    return flops, bytes_


def zoo_train_work(zoo: list, shapes: dict) -> dict:
    """``{"linear": (operations, bytes), "tree": (operations, bytes)}`` of
    one whole train at the ``shapes`` a run read back: every grid point's
    k-fold sweep and the winner's refit on all training rows."""
    n_fold, n_train, d, k = (shapes[key] for key in (
        "n_fold_train", "n_train", "d", "k"))
    out = {"linear": list(gram_train_work(zoo, shapes)), "tree": [0.0, 0.0]}
    for ci, fam in enumerate(zoo):
        if fam["trainer"] == LINEAR:
            continue
        for gi, g in enumerate(fam["grid"]):
            trees = int(g.get("num_trees", g.get("num_rounds")))
            depth = int(g["max_depth"])
            f, b = opcount.tree_hist_ensemble(n_fold, d, depth, trees)
            out["tree"][0] += k * f
            out["tree"][1] += k * b
            if (ci, gi) == tuple(shapes["winner"]):
                f, b = opcount.tree_hist_ensemble(n_train, d, depth, trees)
                out["tree"][0] += f
                out["tree"][1] += b
    return {kind: tuple(v) for kind, v in out.items()}
