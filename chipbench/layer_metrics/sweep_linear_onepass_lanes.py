"""Linear descent lanes whose every Adam step read the matrix once
(``linearOnePassLanes``: a row weighting x grid point, or one warm refit,
that took ``models/linear.py::_onepass_terms``), per train. A program
older than the counter reports nothing."""


def read(run):
    if not run.units or "linearOnePassLanes" not in run.counters:
        return None
    return run.counters["linearOnePassLanes"] / run.units
