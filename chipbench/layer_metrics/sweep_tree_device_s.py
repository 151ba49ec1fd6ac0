"""Device seconds of the sweep's tree programs (the ``sweep.device`` spans
of tree depth groups, every lane chunk), per train."""

from chipbench.layer_metrics import sweep_device_spans


def read(run):
    return sweep_device_spans.seconds_per_unit(run, "tree")
