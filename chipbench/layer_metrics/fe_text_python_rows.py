"""Rows of free-text columns that took the Python tokenizer (not ASCII, or
longer than the native row length: ``feTextPythonRows``), per train: the
table's quota of odd rows, or the whole column if eligibility went back to
a column."""


def read(run):
    if not run.units or "feTextPythonRows" not in run.counters:
        return None
    return run.counters["feTextPythonRows"] / run.units
