"""Operations and bytes that each kernel's ALGORITHM needs, from shapes.

Counts what the mathematics asks for, whatever implements it, so that a
later kernel swap leaves the count valid: a histogram is two additions per
(row, feature) however it is laid out on the MXU; recomputation and padding
do not count. Copied in spirit from ``utils/flops.py`` (which counts what the
current engine executes); listed in PERF.md for a later PR to reconcile. A
zoo entry is known by its ``trainer`` (the algorithm), not by a class name.
"""

from __future__ import annotations


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def tree_hist_level(n_rows: int, d: int, n_nodes: int, n_bins: int
                    ) -> tuple[float, float]:
    """One level's (gradient, hessian) histograms over all nodes: 2 adds per
    (row, feature); reads one code byte per (row, feature) and two float32
    per row, writes ``nodes x d x bins`` pairs of float32."""
    flops = 2.0 * n_rows * d
    bytes_ = n_rows * d * 1.0 + n_rows * 8.0 + n_nodes * d * n_bins * 8.0
    return flops, bytes_


def tree_hist_ensemble(n_rows: int, d: int, depth: int, n_trees: int,
                       n_bins: int = 64) -> tuple[float, float]:
    """All levels of all trees of one ensemble on ``n_rows`` rows."""
    f = b = 0.0
    for level in range(depth):
        lf, lb = tree_hist_level(n_rows, d, 2 ** level, n_bins)
        f += lf
        b += lb
    return f * n_trees, b * n_trees


def linear_adam(n: int, d: int, n_out: int, steps: int, lanes: int = 1
                ) -> tuple[float, float]:
    """Full-batch first-order training of ``lanes`` grid points together:
    per step and lane a forward ``X @ W`` and a backward ``X^T @ r``
    (2 x 2ndC); X need be read once per step for all lanes (row blocks
    stream through the forward and the backward product in one pass)."""
    return lanes * steps * 4.0 * n * d * n_out, steps * n * d * 4.0


def linear_newton(n: int, d: int, steps: int, lanes: int = 1
                  ) -> tuple[float, float]:
    """Damped Newton for binary logistic regression, ``lanes`` grid points
    together: per step and lane the margin and gradient products (4n(d+1)),
    the Hessian build (2n(d+1)^2) and the solve ((2/3)(d+1)^3); X with its
    ones column is read once per step."""
    p = d + 1.0
    return (lanes * steps * (4.0 * n * p + 2.0 * n * p * p
                             + (2.0 / 3.0) * p ** 3),
            steps * n * p * 4.0)


def linear_family(fam: dict, n: int, d: int) -> tuple[float, float]:
    """Operations and bytes of one fit of a linear family's whole grid on
    ``n`` rows: its Newton points together, its Adam points together."""
    hinge = fam["trainer"] == "hinge"
    newton = 0 if hinge else sum(
        1 for g in fam["grid"] if float(g.get("elastic_net_param", 0.0)) == 0)
    adam = len(fam["grid"]) - newton
    f = b = 0.0
    if newton:
        f, b = linear_newton(n, d, 15, newton)
    if adam:
        fa, ba = linear_adam(n, d, 1 if hinge else 2, 200, adam)
        f, b = f + fa, b + ba
    return f, b


LINEAR = ("logistic", "hinge")


def zoo_train_work(zoo: list, n_fold_train: int, n_train: int, d: int,
                   k: int, winner: tuple) -> dict:
    """``{"linear": (operations, bytes), "tree": (operations, bytes)}`` of
    every trainer one whole train runs: the k-fold sweep of every grid point
    and the winner's refit on all training rows."""
    out = {"linear": [0.0, 0.0], "tree": [0.0, 0.0]}

    def add(kind, work, times):
        out[kind][0] += times * work[0]
        out[kind][1] += times * work[1]

    for ci, fam in enumerate(zoo):
        if fam["trainer"] in LINEAR:
            add("linear", linear_family(fam, n_fold_train, d), k)
            if ci == winner[0]:
                one = dict(fam, grid=[fam["grid"][winner[1]]])
                add("linear", linear_family(one, n_train, d), 1)
            continue
        for gi, g in enumerate(fam["grid"]):
            trees = int(g.get("num_trees", g.get("num_rounds")))
            depth = int(g["max_depth"])
            add("tree", tree_hist_ensemble(n_fold_train, d, depth, trees), k)
            if (ci, gi) == tuple(winner):
                add("tree", tree_hist_ensemble(n_train, d, depth, trees), 1)
    return {kind: tuple(v) for kind, v in out.items()}


def trainer_roofline_pct(run, kind: str, seconds: float):
    """Share of its roofline that ``kind``'s trainers reached in a traced
    window: the least time the chip could take for what every train of the
    window needs, over the device ``seconds`` they took. ``None`` where
    there is nothing to read."""
    if seconds <= 0 or not run.peaks:
        return None
    s = run.shapes
    flops, bytes_ = zoo_train_work(
        run.config["pipeline"]["zoo"], s["n_fold_train"], s["n_train"],
        s["d"], s["k"], s["winner"])[kind]
    least = roofline_seconds(flops * run.units, bytes_ * run.units, run.peaks)
    return 100.0 * least / seconds if least > 0 else None
