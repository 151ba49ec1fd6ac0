"""Host seconds of the multiclass evaluator's report (spans
``evaluate.multiclass``: confusion, top-K, threshold and misclassification
counts over the pulled holdout scores), per train. A program older than the
span records none: nothing to read."""

from chipbench.layer_metrics import fe_spans


def read(run):
    return fe_spans.seconds_per_unit(run, ("evaluate.multiclass",))
