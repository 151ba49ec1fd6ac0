"""Shared device-timing helpers for the on-chip benches and diagnostics.

JAX dispatch is asynchronous: a wall that does not wait for the result
times the enqueue, not the execution. Every timing here fences with
``jax.block_until_ready`` (a real fence on the installed runtime).
Callers should pass ``variants`` — a list of distinct input tuples longer
than ``repeats`` — so no timed call repeats an input that already ran.
"""

from __future__ import annotations

import time

import jax
import numpy as np

__all__ = ["fence", "med_fetch"]


def fence(x):
    """Wait until ``x`` (any pytree of device arrays) has been computed."""
    return jax.block_until_ready(x)


def med_fetch(fn, variants, repeats: int = 3) -> float:
    """Median fenced wall of ``fn(*args)`` over fresh-input repeats.

    ``variants``: list of argument tuples. The first is burned on
    warmup/compile; timed repeats walk the remaining variants so no
    timed call reuses an input that has already executed (when
    ``len(variants) >= repeats + 1``, which callers should ensure).
    """
    fence(fn(*variants[0]))
    ts = []
    for i in range(repeats):
        args = variants[1 + i % (len(variants) - 1)] if len(variants) > 1 \
            else variants[0]
        t0 = time.perf_counter()
        fence(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))
