"""All ``sweep.device`` seconds over the CrossValidation wall: the share of
the sweep that the per-program device intervals account for. The rest is
the sweep's own operands (binning, fold gathers), dispatch and host work."""

from chipbench.layer_metrics import sweep_device_spans


def read(run):
    spans = sweep_device_spans.in_window(run)
    wall = run.phases.get("CrossValidation", 0.0)
    if spans is None or wall <= 0:
        return None
    return 100.0 * sum(sec for sec, _attrs in spans) / wall
