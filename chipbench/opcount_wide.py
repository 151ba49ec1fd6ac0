"""Operations and bytes of the linear sweep over a WIDE matrix, from shapes.

Above ``models/linear.py::_NEWTON_MAX_D`` columns every linear grid point,
pure-L2 logistic regression too, is trained by full-batch Adam, so
``opcount.linear_family`` (which counts Newton for those points whatever the
width, 2np^2 a step) does not describe what the configuration states. Here
every point is counted as Adam: per step and lane a forward ``X @ W`` and a
backward ``X^T @ r`` (4ndC), and per step ONE read of the training split's
n x d float32 for all lanes and all folds together: the least any
implementation needs, so a share of this roofline stays under 100% whatever
a later PR does.
"""

from __future__ import annotations

from chipbench import opcount

STEPS = 200


def family_lanes(fam: dict) -> tuple[int, int]:
    """``(grid points, outputs a point)`` of a linear zoo entry."""
    return len(fam["grid"]), 1 if fam["trainer"] == "hinge" else 2


def sweep_and_refit(config: dict, shapes: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one whole train's linear work at the
    ``shapes`` a run read back: every family's k-fold sweep (the lanes of a
    fold work on its ``n_fold_train`` rows; a step reads the
    ``n_train``-row split once for all of them) and the winner's refit on
    all ``n_train`` rows."""
    n_fold_train, n_train, d, k = (shapes[key] for key in (
        "n_fold_train", "n_train", "d", "k"))
    flops = bytes_ = 0.0
    for ci, fam in enumerate(config["pipeline"]["zoo"]):
        if fam["trainer"] not in opcount.LINEAR:
            continue
        G, C = family_lanes(fam)
        f, _ = opcount.linear_adam(n_fold_train, d, C, STEPS, lanes=k * G)
        flops += f
        bytes_ += STEPS * n_train * d * 4.0
        if ci == shapes["winner"][0]:
            f, b = opcount.linear_adam(n_train, d, C, STEPS)
            flops, bytes_ = flops + f, bytes_ + b
    return flops, bytes_
