"""Roofline share of the tree histogram contraction.

Device time: every leaf op of the tree-growing programs
(``train_score_stacked`` for the sweep, ``train_ensemble`` for a tree
winner's refit), summed from the trace. XLA fuses the one-hot contraction
with its neighbours into ops named only ``fusion``, so the contraction
cannot be told from the partition and split evaluation by name; the whole
grower's time is taken, which the contraction dominates, and the share is a
lower bound. Against it, what a histogram needs from its shapes
(``opcount.tree_hist_ensemble``): two additions per (row, feature) per
level, one code byte per (row, feature) read. Bound by memory here.
"""

from chipbench import opcount

MODULES = ("jit_train_score_stacked", "jit_train_ensemble")


def is_hist(name: str) -> bool:
    return name.partition("/")[0].startswith(MODULES)


def read(run):
    if run.trace is None:
        return None
    return opcount.trainer_roofline_pct(
        run, "tree", run.trace.kernel_seconds(is_hist))
