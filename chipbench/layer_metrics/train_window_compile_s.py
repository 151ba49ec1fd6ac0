"""Seconds the backend spent compiling, or loading from the persistent
cache, inside the window, per train: the host-side part of a train that
varies most from run to run."""


def read(run):
    return run.compile_s_in_window / run.units if run.units else None
