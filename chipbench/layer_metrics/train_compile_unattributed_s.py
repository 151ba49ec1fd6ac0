"""Seconds of in-window compiles and cache loads whose site is
``unattributed`` (built outside every ``building(...)`` block), per train.
The aim is 0: every program a train builds names where it was built."""

from chipbench.layer_metrics import compile_spans


def read(run):
    spans = compile_spans.in_window(run)
    if spans is None or not run.units:
        return None
    return sum(sec for _kind, site, sec in spans
               if site == "unattributed") / run.units
