"""Persistent-cache hits inside the window: the small programs each new
Workflow re-jits, loaded instead of compiled."""


def read(run):
    return float(run.cache_loads_in_window) if run.units else None
