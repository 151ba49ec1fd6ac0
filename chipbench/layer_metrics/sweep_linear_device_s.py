"""Device seconds of the sweep's fold-stacked families (the
``sweep.device`` spans of the linear families), per train."""

from chipbench.layer_metrics import sweep_device_spans


def read(run):
    return sweep_device_spans.seconds_per_unit(run, "stacked")
