"""Programs the backend compiled inside the window, counted from the
program's own ``compile.program:<site>`` spans, per train. Must equal
``train_window_compiles / units`` (the benchmark's own listener): the
program then tells compiles from cache loads as the benchmark does, and
its by-site list is the list of what compiled."""

from chipbench.layer_metrics import compile_spans


def read(run):
    spans = compile_spans.in_window(run)
    if spans is None or not run.units:
        return None
    return sum(1 for kind, _site, _sec in spans
               if kind == "compile.program") / run.units
