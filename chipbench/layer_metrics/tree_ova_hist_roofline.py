"""Roofline share of the one-vs-all tree histograms of a multiclass zoo.

Device time: every leaf op of the tree-growing programs, as
``tree_hist_roofline`` takes it (``train_score_stacked`` for the sweep,
``train_ensemble`` for a tree winner's refit). Against it, what K one-vs-all
histograms a level need from their shapes (``opcount_multi``): two additions
and one code byte a (row, feature, level, class). Bound by memory here. A
run whose shapes name no class count reports nothing.
"""

from chipbench import opcount, opcount_multi
from chipbench.layer_metrics import tree_hist_roofline


def read(run):
    if run.trace is None or not run.peaks or "K" not in (run.shapes or {}):
        return None
    seconds = run.trace.kernel_seconds(tree_hist_roofline.is_hist)
    flops, bytes_ = opcount_multi.zoo_train_work(
        run.config["pipeline"]["zoo"], run.shapes)["tree"]
    least = opcount.roofline_seconds(flops * run.units, bytes_ * run.units,
                                     run.peaks)
    return 100.0 * least / seconds if seconds > 0 and least > 0 else None
