"""Hashed columns that fell to the per-row loop past the per-unique table's
cap (``feHashPerRowFallbacks``), per train. The aim is 0."""


def read(run):
    if not run.units or "feHashPerRowFallbacks" not in run.counters:
        return None
    return run.counters["feHashPerRowFallbacks"] / run.units
