"""Host seconds in the string columns' dictionary encoding, pivot and
hashing (``fe.dict_encode`` + ``fe.pivot`` + ``fe.hash`` spans), per
train."""

from chipbench.layer_metrics import fe_spans


def read(run):
    return fe_spans.seconds_per_unit(
        run, ("fe.dict_encode", "fe.pivot", "fe.hash"))
