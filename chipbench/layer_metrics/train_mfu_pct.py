"""Analytic FLOPs of every trainer run in the window over the window times
the chip's bf16 peak."""


def read(run):
    total = sum(run.flops.values()) if run.flops else 0.0
    if run.trace is None or not total or not run.peaks:
        return None
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops_per_s"])
