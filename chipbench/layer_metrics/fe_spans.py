"""What the two ``fe_*_s`` readers share: seconds of the window's host
feature-engineering spans by name, per train.

The program records ``fe.dict_encode``, ``fe.pivot`` and ``fe.hash`` around
the string work of a column (attrs: column, rows, distinct values,
``perRowFallback``) and ``fe.upload`` around the host-to-device transfer of
a vector block (attr bytes). A program older than the spans records none:
nothing to read.
"""


def seconds_per_unit(run, names: tuple):
    secs = [t1 - t0 for t0, t1, name in run.spans if name in names]
    if not secs or not run.units:
        return None
    return sum(secs) / run.units
