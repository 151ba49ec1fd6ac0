"""Linear lanes that took the Adam descent and were not solved
(``linearDescentLanes``: a row weighting x grid point, or one warm refit),
per train. A program older than the counter reports nothing."""


def read(run):
    if not run.units or "linearDescentLanes" not in run.counters:
        return None
    return run.counters["linearDescentLanes"] / run.units
