"""Host seconds in the native tokenize-and-hash pass of the free-text
columns (``fe.tokenize`` spans), per train."""

from chipbench.layer_metrics import fe_spans


def read(run):
    return fe_spans.seconds_per_unit(run, ("fe.tokenize",))
