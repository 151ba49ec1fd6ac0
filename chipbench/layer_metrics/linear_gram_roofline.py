"""Roofline share of the least-squares Gram program.

Device time: every leaf op of the ``_gram_folds`` programs (the sweep's
fold weightings and a linear winner's refit: the Gram pass and the lanes'
solves), summed from the trace. Against it, what solving from the normal
equations needs from its shapes (``opcount_regression.gram_train_work``):
``2 k n (d+1)^2`` operations and ONE read of the training split and the
fold weights. A program without that kernel reports nothing.
"""

from chipbench import opcount, opcount_regression

MODULE = "jit__gram_folds"


def is_gram(name: str) -> bool:
    return name.partition("/")[0].startswith(MODULE)


def read(run):
    if run.trace is None or not run.peaks or not run.shapes:
        return None
    seconds = run.trace.kernel_seconds(is_gram)
    flops, bytes_ = opcount_regression.gram_train_work(
        run.config["pipeline"]["zoo"], run.shapes)
    least = opcount.roofline_seconds(flops * run.units, bytes_ * run.units,
                                     run.peaks)
    return 100.0 * least / seconds if seconds > 0 and least > 0 else None
