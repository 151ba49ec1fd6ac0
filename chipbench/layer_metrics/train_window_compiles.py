"""Programs the backend compiled inside the window (0 on a warm cache)."""


def read(run):
    return float(run.compiles_in_window) if run.units else None
