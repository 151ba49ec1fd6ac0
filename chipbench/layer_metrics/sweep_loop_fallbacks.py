"""Families and tree lane groups that left the fold-stacked sweep for the
per-fold loop (``sweepLoopFallbacks``), per train. The aim is 0. A program
older than the counter reports nothing."""


def read(run):
    if not run.units or "sweepLoopFallbacks" not in run.counters:
        return None
    return run.counters["sweepLoopFallbacks"] / run.units
