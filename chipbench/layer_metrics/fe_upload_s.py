"""Host seconds in the host-to-device transfer of vector blocks
(``fe.upload`` spans), per train."""

from chipbench.layer_metrics import fe_spans


def read(run):
    return fe_spans.seconds_per_unit(run, ("fe.upload",))
