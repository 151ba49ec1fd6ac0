"""Operations and bytes of a MULTICLASS zoo's trainers, from shapes.

What the stated algorithm needs, whatever implements it (as
``chipbench.opcount`` counts for one output): a forest past two classes
grows K one-vs-all trees a member, so every level of a member is K
histograms, each two additions a (row, feature) and one code byte read a
(row, feature) (``opcount.tree_hist_ensemble`` times K); a softmax point is
full-batch Adam over K outputs, 4ndK operations a step and lane and one read
of the fold's rows a step for all lanes (``opcount.linear_adam``).
"""

from __future__ import annotations

from chipbench import opcount

STEPS = 200
LINEAR, FOREST = "logistic", "forest"


def zoo_train_work(zoo: list, shapes: dict) -> dict:
    """``{"linear": (operations, bytes), "tree": (operations, bytes)}`` of
    one whole train at the ``shapes`` a run read back: every grid point's
    k-fold sweep and the winner's refit on all training rows."""
    n_fold, n_train, d, k, K = (shapes[key] for key in (
        "n_fold_train", "n_train", "d", "k", "K"))
    out = {"linear": [0.0, 0.0], "tree": [0.0, 0.0]}

    def add(kind, work, times):
        out[kind][0] += times * work[0]
        out[kind][1] += times * work[1]

    for ci, fam in enumerate(zoo):
        if fam["trainer"] == LINEAR:
            G = len(fam["grid"])
            add("linear", opcount.linear_adam(n_fold, d, K, STEPS, G), k)
            if ci == shapes["winner"][0]:
                add("linear", opcount.linear_adam(n_train, d, K, STEPS), 1)
            continue
        for gi, g in enumerate(fam["grid"]):
            trees, depth = int(g["num_trees"]), int(g["max_depth"])
            add("tree", opcount.tree_hist_ensemble(n_fold, d, depth, trees),
                k * K)
            if (ci, gi) == tuple(shapes["winner"]):
                add("tree", opcount.tree_hist_ensemble(n_train, d, depth,
                                                       trees), K)
    return {kind: tuple(v) for kind, v in out.items()}
