"""Roofline share of the linear trainers over a wide matrix.

Device time: every leaf op of the ``_train_linear*`` programs (the
fold-stacked sweep of each family and the winner's refit), summed from the
trace. Against it, what full-batch Adam needs from its shapes
(``opcount_wide.sweep_and_refit``): 4ndC operations a step and lane, and one
read of the training split a step for all lanes and folds together. Bound by
memory here.
"""

from chipbench import opcount, opcount_wide

MODULE = "jit__train_linear"


def is_linear(name: str) -> bool:
    return name.partition("/")[0].startswith(MODULE)


def read(run):
    if run.trace is None or not run.peaks or not run.shapes:
        return None
    seconds = run.trace.kernel_seconds(is_linear)
    flops, bytes_ = opcount_wide.sweep_and_refit(run.config, run.shapes)
    least = opcount.roofline_seconds(flops * run.units, bytes_ * run.units,
                                     run.peaks)
    return 100.0 * least / seconds if seconds > 0 and least > 0 else None
